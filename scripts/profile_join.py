"""Per-phase profiling of the bench workload with explicit device blocking.

Runs the join+groupby pipeline's compiled phases one at a time, blocking
after each, so costs attribute to the phase that incurs them (the bench's
async regions smear attribution).  Not part of the test suite — a
measurement tool for kernel work.

Usage: python scripts/profile_join.py [--rows=N] [--unique=F]
"""

from __future__ import annotations

import sys
import time

import os
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import cylon_tpu as ct
from cylon_tpu import config
from cylon_tpu.ctx.context import device_config
from cylon_tpu.relational import groupby_aggregate, join_tables


def timed(label, fn, *args, iters=3):
    fn(*args)  # warm
    jax.block_until_ready(fn(*args))
    best = 1e9
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    print(f"{label:40s} {best*1e3:10.1f} ms")
    return out


def main():
    rows = 64_000_000
    unique = 0.9
    for a in sys.argv[1:]:
        if a.startswith("--rows="):
            rows = int(a.split("=", 1)[1])
        if a.startswith("--unique="):
            unique = float(a.split("=", 1)[1])

    env = ct.CylonEnv(config=device_config())
    w = env.world_size
    n = rows * w
    max_val = max(int(n * unique), 1)
    rng = np.random.default_rng(42)
    lt = ct.Table.from_pydict(
        {"k": rng.integers(0, max_val, n).astype(np.int64),
         "a": rng.integers(0, max_val, n).astype(np.int64)}, env)
    rt = ct.Table.from_pydict(
        {"k": rng.integers(0, max_val, n).astype(np.int64),
         "b": rng.integers(0, max_val, n).astype(np.int64)}, env)

    # ---- end-to-end first --------------------------------------------------
    def full():
        j = join_tables(lt, rt, "k", "k", how="inner")
        return groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])

    g = full()
    jax.block_until_ready([c.data for c in g.columns.values()])
    t0 = time.perf_counter()
    g = full()
    jax.block_until_ready([c.data for c in g.columns.values()])
    print(f"{'TOTAL join+groupby':40s} {(time.perf_counter()-t0)*1e3:10.1f} ms")

    # ---- join phases -------------------------------------------------------
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import join as rj
    from cylon_tpu.relational.common import (col_arrays, narrow32_flags)

    lwork, rwork = lt, rt
    l_key = [lwork.column("k")]
    r_key = [rwork.column("k")]
    l_datas, l_valids = col_arrays(l_key)
    r_datas, r_valids = col_arrays(r_key)
    narrow = narrow32_flags(l_key, r_key)
    print("narrow32 flags:", narrow)
    vcl = np.asarray(lwork.valid_counts, np.int32)
    vcr = np.asarray(rwork.valid_counts, np.int32)

    r_cols_list = [rwork.column("b")]
    l_cols_list = [lwork.column("k"), lwork.column("a")]
    rspec = lanes.plan_lanes(tuple(str(c.data.dtype) for c in r_cols_list),
                             tuple(c.validity is not None for c in r_cols_list),
                             narrow32_flags(r_cols_list))
    lspec = lanes.plan_lanes(tuple(str(c.data.dtype) for c in l_cols_list),
                             tuple(c.validity is not None for c in l_cols_list),
                             narrow32_flags(l_cols_list))
    print("lspec lanes:", lspec.n_lanes, "rspec lanes:", rspec.n_lanes)
    r_gather_args = (tuple(c.data for c in r_cols_list),
                     tuple(c.validity for c in r_cols_list))

    l_gather_args = (tuple(c.data for c in l_cols_list),
                     tuple(c.validity for c in l_cols_list))
    from cylon_tpu.ops import join as joink
    layout = joink.payload_layout(lspec, rspec, (0,),
                                  tuple(str(d.dtype) for d in l_datas),
                                  (False,), narrow, True)
    print("sort operands:", layout.sort_operands)
    fn1 = rj._count_fn(env.mesh, "inner", narrow, lspec, rspec, layout,
                       all_live=True)
    res = timed("join phase1 (sort+carry+count)", fn1, vcl, vcr, l_datas,
                l_valids, r_datas, r_valids, *l_gather_args, *r_gather_args)
    counts_dev, carry = res[0], res[1:7]
    pl_s = tuple(res[7:])
    counts = np.asarray(counts_dev).astype(np.int64)
    out_cap = config.pow2ceil(int(counts.max()))
    print("join out rows:", counts.sum(), "cap:", out_cap)

    plan = (("l", 0, False), ("l", 1, False), ("r", 0, False))
    fn2 = rj._materialize_fn(env.mesh, "inner", out_cap, lwork.capacity,
                             plan, lspec, rspec, layout)
    mat_args = (carry, pl_s, *l_gather_args, *r_gather_args)
    timed("join phase2 (materialize)", fn2, *mat_args)

    # ---- groupby on grouped join output ------------------------------------
    j = join_tables(lt, rt, "k", "k", how="inner")
    jax.block_until_ready([c.data for c in j.columns.values()])

    def gb():
        return groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])

    g = gb()
    jax.block_until_ready([c.data for c in g.columns.values()])
    for _ in range(2):
        t0 = time.perf_counter()
        g = gb()
        jax.block_until_ready([c.data for c in g.columns.values()])
        print(f"{'groupby (grouped fast path)':40s} "
              f"{(time.perf_counter()-t0)*1e3:10.1f} ms")


if __name__ == "__main__":
    main()
