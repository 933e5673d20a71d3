"""Segment starts at the cells' shard shapes: what each way of writing
``starts`` costs on one chip (ROADMAP S3, PR 33).  Not part of the suite.

``starts[g]`` = position of the first row of group ``g``; slots past the
last group hold ``n_live``.  Group ids are ``cumsum(start) - 1`` over the
position-ordered rows, so the g-th start is the g-th smallest start
position.  Forms, each inside one jitted program of its own:

``scatter``    the statement until PR 33: one candidate update per row,
               non-start rows sent out of range and dropped
``sort``       ``lax.sort(where(start, pos, n_live), is_stable=False)``,
               sliced to the segment space: one s32 operand
``min_sorted`` ROADMAP S3(a): every row writes its own group's slot,
               ``.at[maximum(gid, 0)].min(..., indices_are_sorted=True)``
               (colliding)
``sort_stable`` the sort form with ``lax.sort``'s default stability (the
               compiler adds an iota operand): why ``is_stable=False``

Start flags are drawn on the device from ``--seed`` at the cell's group
density over a live prefix; every form is checked against the scatter
element for element before it is timed.  Times are host clock around
``block_until_ready``, the median of ``--reps`` calls after one warm call.

    chiprun -- python scripts/starts_bench.py --out chiprun_out/starts_bench.json
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

#: (cell, rows of the shard's state N, live rows, segment space, group
#: density over the live rows): PERF.md §4/§5
SHAPES = (
    ("join_groupby_32m", 65_011_712, 64_000_000, 13_107_200, 0.2016),
    ("join_groupby_32m_zipf", 65_011_712, 64_000_000, 2_228_224, 0.0331),
    ("groupby_sort_25m", 25_165_824, 25_000_000, 15_204_352, 0.6036),
    ("dist_join_groupby_8m_x4", 17_825_792, 16_777_216, 3_407_872, 0.19),
)


def make_flags(seed: int, n: int, n_live: int, density: float):
    """(start, gid): start flags of about ``density * n_live`` groups in
    the live prefix, and the dense ids ``cumsum(start) - 1``."""
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    pos = jnp.arange(n, dtype=jnp.int32)
    start = ((u < density) | (pos == 0)) & (pos < n_live)
    gid = jnp.cumsum(start.astype(jnp.int32)).astype(jnp.int32) - 1
    return start, gid


def scatter(start, gid, n_live, seg_cap):
    pos = jnp.arange(start.shape[0], dtype=jnp.int32)
    return jnp.full(seg_cap, n_live, jnp.int32).at[
        jnp.where(start, gid, jnp.int32(seg_cap))].set(pos, mode="drop")


def _sort(start, n_live, seg_cap, stable):
    pos = jnp.arange(start.shape[0], dtype=jnp.int32)
    out = jax.lax.sort(jnp.where(start, pos, n_live), is_stable=stable)
    return out[:seg_cap]


def sort(start, gid, n_live, seg_cap):
    return _sort(start, n_live, seg_cap, False)


def sort_stable(start, gid, n_live, seg_cap):
    return _sort(start, n_live, seg_cap, True)


def min_sorted(start, gid, n_live, seg_cap):
    pos = jnp.arange(start.shape[0], dtype=jnp.int32)
    return jnp.full(seg_cap, n_live, jnp.int32).at[jnp.maximum(gid, 0)].min(
        jnp.where(start, pos, n_live), indices_are_sorted=True, mode="drop")


FORMS = {"scatter": scatter, "sort": sort, "min_sorted": min_sorted,
         "sort_stable": sort_stable}


def time_form(fn, args, seg_cap, reps):
    f = jax.jit(fn, static_argnums=3)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args, seg_cap))
    first_s = time.perf_counter() - t0          # compile + one call
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args, seg_cap))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times), min(times), first_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3300000317)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink every shape (CPU rehearsal)")
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps({"device": device}), flush=True)
    rows = []
    for cell, n, n_live, seg_cap, density in SHAPES:
        n, n_live, seg_cap = (max(int(v * a.scale), 8)
                              for v in (n, n_live, seg_cap))
        start, gid = jax.jit(make_flags, static_argnums=(1, 2, 3))(
            a.seed % (2**31), n, n_live, density)
        groups = int(gid[-1]) + 1
        args = (start, gid, jnp.int32(n_live))
        ref = None
        for name in a.forms.split(","):
            out, med, best, first_s = time_form(FORMS[name], args, seg_cap,
                                                a.reps)
            if ref is None:
                ref = scatter(*args, seg_cap) if name != "scatter" else out
            row = {"cell": cell, "form": name, "rows": n, "slots": seg_cap,
                   "groups": groups, "density": round(groups / n_live, 4),
                   "ms_median": med, "ms_min": best,
                   "ns_per_row": med * 1e6 / n,
                   "first_call_s": round(first_s, 2),
                   "equal_to_scatter": bool(jnp.array_equal(out, ref))}
            rows.append(row)
            print(json.dumps(row), flush=True)
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"device": device, "seed": a.seed, "reps": a.reps,
                       "scale": a.scale, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
