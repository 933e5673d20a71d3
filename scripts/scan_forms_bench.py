"""An exact int64 prefix sum of values that fit 32 bits, at the cells'
shard shapes: what each way of writing the scan costs on the chip
(ROADMAP S6(a), PR 40).  Not part of the suite.

Every form returns the two u32 lanes (hi, lo) of ``concatenate([0,
cumsum(where(mask, v, 0).astype(int64))])`` - what
``ops/groupby.grouped_reduce`` stacks for its one gather - from an int64
value column whose values fit int32 and a row mask, each inside one jitted
program of its own:

``pair64``      the statement until PR 40: ``jnp.cumsum`` in int64 (XLA:TPU
                lowers it to a variadic two-operand (hi, lo)
                ``reduce-window``); on a mesh of more than one device
                ``ops/groupby.blocked_cumsum``, as the programs write it
``block_b64``   form 1: int32 ``cumsum`` inside blocks of B = 64 rows
                (B * max|v| < 2^31 at the cells' bounds), an int64 scan
                over the N/B block totals alone, ``before[block] + local``
                and the lane split elementwise
``block_b128``  form 1 at B = 128 (one limb: exact for max|v| < 2^24, so
                its values are drawn under that; the cells' are not)
``limbs_b128``  form 1 for ANY int32 column: two 16-bit limbs, each an
                int32 ``cumsum`` in blocks of 128
``carry``       form 2: ``lo = cumsum(v)`` wrapping mod 2^32 IS the lo
                lane; ``hi = cumsum(carry - neg)`` with ``carry = lo <u
                u32(v)``, ``neg = v < 0`` IS the hi lane: two flat int32
                scans, no 64-bit operation
``block32_bB``  form 1 in form 2's arithmetic: int32 ``cumsum`` inside
                blocks of B rows, ``carry`` over the N/B block totals, and
                ``before + local`` as a 32-bit add with its carry: ONE
                row-length scan and no 64-bit operation (B = 128 exact
                under 2^24, 64 under 2^25, 32 under 2^26, 16 under 2^27)

``carry`` and ``block32_bB`` are what was kept, and call it:
``ops/groupby.carried_cumsum32(x, block)``, flat at block 1; the block
comes from the column's bounds (``relational/groupby.sum_scan_form``).

Values are drawn on the device from ``--seed``, uniform in ``[0,
28_800_000)`` (the 32M-row cells' ``0.9 n``) with every 16th negated so
the sign path is priced too, under a mask that keeps about half of a live
prefix; every form's lanes are checked against ``pair64``'s bit for bit
before it is timed.  Times are host clock around ``block_until_ready``,
the median of ``--reps`` calls after one warm call.

    chiprun -- python scripts/scan_forms_bench.py --out chiprun_out/scan_forms_bench.json
    chiprun --chips 4 -- python scripts/scan_forms_bench.py --chips 4 \
        --cells dist_join_groupby_8m_x4,dist_join_groupby_8m_zipf_x4 \
        --out chiprun_out/scan_forms_bench_x4.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu.ops import groupby as gbk  # noqa: E402

#: (cell, rows of the shard's state N, live rows): PERF.md §4/§5
SHAPES = (
    ("join_groupby_32m", 65_011_712, 64_000_000),
    ("groupby_sort_25m", 25_165_824, 25_000_000),
    ("dist_join_groupby_8m_x4", 17_825_792, 16_777_216),
    ("dist_join_groupby_8m_zipf_x4", 20_447_232, 19_610_000),
)

#: the 32M-row cells' value bound, 0.9 n (under 2^25, over 2^24)
BOUND = 28_800_000


def make_inputs(key, n: int, n_live: int, bound: int):
    """(v int64 with |v| < bound, mask) of one shard."""
    k1, k2 = jax.random.split(key)
    pos = jnp.arange(n, dtype=jnp.int32)
    v = jax.random.randint(k1, (n,), 0, bound, dtype=jnp.int32)
    v = jnp.where(pos % 16 == 5, -v, v).astype(jnp.int64)
    mask = (jax.random.bits(k2, (n,), dtype=jnp.uint32) & 1).astype(bool) \
        & (pos < n_live)
    return v, mask


def _lanes32(hi, lo):
    """int32 (hi, lo) words of the inclusive prefix -> the exclusive
    prefix's two u32 lanes."""
    z = jnp.zeros(1, jnp.int32)
    return tuple(jax.lax.bitcast_convert_type(jnp.concatenate([z, w]),
                                              jnp.uint32) for w in (hi, lo))


def _lanes64(ps):
    ps = jnp.concatenate([jnp.zeros(1, jnp.int64), ps])
    return ((ps >> 32).astype(jnp.uint32),
            (ps & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32))


def pair64(v, mask, multi: bool):
    x = jnp.where(mask, v, 0)
    return _lanes64(gbk.blocked_cumsum(x) if multi else jnp.cumsum(x))


def _blocks(x, b: int):
    n = x.shape[0]
    m = -(-n // b)
    return jnp.pad(x, (0, m * b - n)).reshape(m, b)


def _carried(inner64, multi: bool):
    """``before[block] + local`` over (m, B) int64 block-local prefixes."""
    total = inner64[:, -1]
    scan = gbk.blocked_cumsum if multi else jnp.cumsum
    return inner64 + (scan(total) - total)[:, None]


def block(v, mask, multi: bool, b: int):
    n = v.shape[0]
    x = jnp.where(mask, v, 0).astype(jnp.int32)
    inner = jnp.cumsum(_blocks(x, b), axis=1)            # int32, exact
    return _lanes64(_carried(inner.astype(jnp.int64), multi)
                    .reshape(-1)[:n])


def limbs(v, mask, multi: bool, b: int):
    n = v.shape[0]
    x = jnp.where(mask, v, 0).astype(jnp.int32)
    lo16 = _blocks(x & 0xFFFF, b)                        # [0, 2^16)
    hi16 = _blocks(x >> 16, b)                           # [-2^15, 2^15)
    inner = (jnp.cumsum(hi16, axis=1).astype(jnp.int64) << 16) \
        + jnp.cumsum(lo16, axis=1).astype(jnp.int64)
    return _lanes64(_carried(inner, multi).reshape(-1)[:n])


def carried(v, mask, multi: bool, b: int):
    """The kept statement: ``ops/groupby.carried_cumsum32`` - flat
    (``b`` = 1) or in blocks of ``b``."""
    x = jnp.where(mask, v, 0).astype(jnp.int32)
    return _lanes32(*gbk.carried_cumsum32(x, b))


FORMS = {
    "pair64": (pair64, BOUND),
    "block_b64": (partial(block, b=64), BOUND),
    "block_b128": (partial(block, b=128), 1 << 24),
    "limbs_b128": (partial(limbs, b=128), BOUND),
    "carry": (partial(carried, b=1), BOUND),
    "block32_b128": (partial(carried, b=128), 1 << 24),
    "block32_b64": (partial(carried, b=64), BOUND),
    "block32_b32": (partial(carried, b=32), BOUND),
    "block32_b16": (partial(carried, b=16), BOUND),
}


def on_mesh(mesh, fn, n_in: int):
    """``fn`` per shard over the rows axis of ``mesh``."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P("x"),) * n_in,
                                 out_specs=P("x")))


def time_form(f, args, reps: int):
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first_s = time.perf_counter() - t0           # compile + one call
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times), min(times), first_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3500000411)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink every shape (CPU rehearsal)")
    ap.add_argument("--cells", default=",".join(s[0] for s in SHAPES))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    devs = jax.devices()[:a.chips]
    if len(devs) != a.chips:
        raise SystemExit(f"{a.chips} chips asked for, {len(devs)} found")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"device": device}), flush=True)
    mesh = Mesh(np.array(devs), ("x",))
    multi = a.chips > 1
    rows = []
    for cell, n, n_live in SHAPES:
        if cell not in a.cells.split(","):
            continue
        n, n_live = (max(int(v * a.scale), 8) for v in (n, n_live))
        ref_by_bound = {}
        for name in a.forms.split(","):
            fn, bound = FORMS[name]
            keys = jax.random.split(jax.random.PRNGKey(a.seed % (2**31)),
                                    a.chips)

            def gen(k):
                return make_inputs(k[0], n, n_live, bound)

            v, mask = jax.jit(jax.shard_map(
                gen, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(keys)
            if bound not in ref_by_bound:
                ref_by_bound[bound] = jax.block_until_ready(on_mesh(
                    mesh, partial(pair64, multi=multi), 2)(v, mask))
            out, med, best, first_s = time_form(
                on_mesh(mesh, partial(fn, multi=multi), 2), (v, mask),
                a.reps)
            row = {"cell": cell, "rows": n, "chips": a.chips, "form": name,
                   "ms_median": med, "ms_min": best, "first_call_s": first_s,
                   "ns_per_row": med * 1e6 / n,
                   "equal_to_pair64": bool(all(
                       jnp.array_equal(x, y)
                       for x, y in zip(out, ref_by_bound[bound])))}
            print(json.dumps(row), flush=True)
            rows.append(row)
            del out, v, mask
    if a.out:
        with open(a.out, "w", encoding="utf-8") as f:
            json.dump({"device": device, "seed": a.seed, "reps": a.reps,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
