"""A filter's two halves at Q3's ``lineitem`` shape: what each way of making
the take index and of moving the rows costs on one chip (ROADMAP S13, PR 43).
Not part of the suite; run by nothing in a cell.

A filter keeps rows in source order, so its take index is strictly
increasing.  Index forms, ns a ROW of the source:

``scatter``   ``ops/sort.compact_by_flag``: exclusive prefix sum of the flag,
              one ``.at[pos].set(idx)`` scatter (the statement until PR 43)
``sort``      ``ops/groupby.grouped_starts``: ONE one-operand unstable
              ``lax.sort`` of ``where(flag, pos, fill)``

Row forms, ns a SLOT of the output, at ``--lanes`` u32 lanes (int32 columns
in, int32 columns out) and ``--windows``:

``xla``       ``ops/lanes.gather_columns``: the (n, L) lane matrix gathered by
              XLA (a per-row dynamic-slice loop whatever the index looks like)
``windowed``  ``lanes.pack_lane_rows`` + ``pallas_gather.take_rows_t`` +
              ``lanes.unpack_lane_rows``: the lanes stacked as rows, the
              windowed Pallas take, a lane read back as a row
``kernel``    ``take_rows_t`` alone on a resident (L8, n) matrix: the
              kernel's own cost by lanes

The flag is drawn on the device from ``--seed`` at ``--density`` over the
live prefix; every form is checked against the first of its family element
for element before it is timed.  Times are host clock around
``block_until_ready``, the median of ``--reps`` calls after one warm call.

    chiprun -- python scripts/filter_compact_bench.py \\
        --out chiprun_out/filter_compact_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu import config  # noqa: E402
from cylon_tpu.ops import groupby as groupbyk  # noqa: E402
from cylon_tpu.ops import lanes  # noqa: E402
from cylon_tpu.ops import pallas_gather as pg  # noqa: E402
from cylon_tpu.ops import sort as sortk  # noqa: E402

#: Q3's ``lineitem`` filter (PERF.md §5): the shard's capacity, its live
#: rows, the kept density (16.16M of 30M)
ROWS, LIVE, DENSITY = 30_408_704, 30_000_000, 0.5387


def make_flag(seed: int, n: int, n_live: int, density: float):
    u = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
    return (u < density) & (jnp.arange(n, dtype=jnp.int32) < n_live)


def index_scatter(flag, out_cap):
    idx, total = sortk.compact_by_flag(flag, out_cap)
    last = idx[jnp.maximum(total - 1, 0)]
    return jnp.where(idx < 0, last, idx)       # padding as the sort form's


def index_sort(flag, out_cap):
    n = flag.shape[0]
    srt = groupbyk.grouped_starts(flag, jnp.ones(n, bool), jnp.int32(n), n)
    total = jnp.sum(flag, dtype=jnp.int32)
    return jnp.minimum(srt[:out_cap], srt[jnp.maximum(total - 1, 0)])


def rows_xla(spec, cols, idx, window):
    return lanes.gather_columns(spec, list(cols), [None] * len(cols), idx)[0]


def rows_windowed(spec, cols, idx, window):
    mat_t = lanes.pack_lane_rows(spec, list(cols), [None] * len(cols), 8)
    return lanes.unpack_lane_rows(spec, pg.take_rows_t(mat_t, idx, window))[0]


def timed(fn, args, reps):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0          # compile + one call
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times), min(times), first_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4300000043)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink the shape (CPU rehearsal)")
    ap.add_argument("--density", type=float, default=DENSITY)
    ap.add_argument("--lanes", default="8,16,24")
    ap.add_argument("--windows", default="1024,4096")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps({"device": device}), flush=True)
    n = config.pow2ceil(max(int(ROWS * a.scale), 4096))
    n_live = min(int(LIVE * a.scale), n)
    flag = jax.jit(make_flag, static_argnums=(1, 2, 3))(
        a.seed % (2**31), n, n_live, a.density)
    kept = int(jnp.sum(flag))
    out_cap = config.pow2ceil(kept)
    shape = {"rows": n, "kept": kept, "slots": out_cap,
             "density": round(kept / n_live, 4)}
    rows, ref = [], None
    for name, fn in (("scatter", index_scatter), ("sort", index_sort)):
        idx, med, best, first_s = timed(
            jax.jit(fn, static_argnums=1), (flag, out_cap), a.reps)
        ref = idx if ref is None else ref
        rows.append({"half": "index", "form": name, **shape,
                     "ms_median": med, "ms_min": best,
                     "ns_per_row": med * 1e6 / n,
                     "first_call_s": round(first_s, 2),
                     "equal": bool(jnp.array_equal(idx, ref))})
        print(json.dumps(rows[-1]), flush=True)
    idx = ref
    span = int(pg.max_tile_span(idx, idx[kept - 1]))
    for L in (int(v) for v in a.lanes.split(",")):
        spec = lanes.plan_lanes(["int32"] * L, [False] * L)
        key = jax.random.PRNGKey(a.seed % (2**31) + L)
        cols = tuple(jax.random.randint(k, (n,), -2**31, 2**31 - 1, jnp.int32)
                     for k in jax.random.split(key, L))
        ref = None
        forms = [("xla", rows_xla, 0)] + [
            ("windowed", rows_windowed, int(w)) for w in a.windows.split(",")]
        for name, fn, window in forms:
            if window and span > window:
                continue
            out, med, best, first_s = timed(
                jax.jit(fn, static_argnums=(0, 3)), (spec, cols, idx, window),
                a.reps)
            ref = out if ref is None else ref
            rows.append({"half": "rows", "form": name, "lanes": L,
                         "window": window, "max_tile_span": span, **shape,
                         "ms_median": med, "ms_min": best,
                         "ns_per_slot": med * 1e6 / out_cap,
                         "first_call_s": round(first_s, 2),
                         "equal": all(bool(jnp.array_equal(x, y))
                                      for x, y in zip(out, ref))})
            print(json.dumps(rows[-1]), flush=True)
        del ref, out
        mat_t = jax.block_until_ready(jax.jit(
            lambda c: lanes.pack_lane_rows(spec, list(c), [None] * L, 8))(cols))
        del cols
        _o = None
        for window in (int(w) for w in a.windows.split(",")):
            if span > window:
                continue
            _o, med, best, first_s = timed(
                jax.jit(pg.take_rows_t, static_argnums=2),
                (mat_t, idx, window), a.reps)
            rows.append({"half": "rows", "form": "kernel", "lanes": L,
                         "window": window, **shape, "ms_median": med,
                         "ms_min": best, "ns_per_slot": med * 1e6 / out_cap,
                         "first_call_s": round(first_s, 2)})
            print(json.dumps(rows[-1]), flush=True)
        del mat_t, _o
    if a.out:
        with open(a.out, "w") as f:
            json.dump({"device": device, "seed": a.seed, "reps": a.reps,
                       "scale": a.scale, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
