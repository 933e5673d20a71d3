"""Closed-loop streaming ingest bench (cylon_tpu/stream).

The ROADMAP's "incremental & streaming workloads" rung measured: a
micro-batch stream is appended into a :class:`StreamTable` (hash-shuffle
on arrival), absorbed by an :class:`IncrementalView` (long-lived
GroupBySink — sum/count/min/max/mean/var/std over integer-valued
fixed-point amounts, so the exactness contract holds) and buffered into
a :class:`TumblingWindowJoin` (event-time windows against a small
broadcast build side, watermark-driven close + spill-tier eviction) —
while, by default, a TPC-H query tenant runs CONCURRENTLY on the same
mesh under the serving scheduler (the ingest loop is a ``stream``-kind
session; docs/serving.md), so the numbers describe ingest under mixed
traffic, not a quiet machine.

What one run produces (a JSON report on stdout, and in the file ``--out``
names):

* sustained ingest rows/s over the whole loop;
* p50/p99 append-to-visible staleness — the wall time from an append's
  start to a finalized ``view.read()`` snapshot that includes it;
* watermark lag (max event time seen − agreed watermark) per vote;
* windows closed + ``window_evictions`` and the ledger-byte delta the
  close lifecycle (device → host → released) drained;
* a ``bit_equal`` verdict: the final incremental view vs a from-scratch
  batch groupby over every appended row, checked bitwise, and every
  closed window's join vs its batch recompute.

Usage::

    python scripts/bench_streaming.py                  # default config
    python scripts/bench_streaming.py --smoke          # tiny CI shape
    python scripts/bench_streaming.py --batches 60 --rows 4000 \
        --no-serve --out stream.json

Exit status 0 = completed, bit-equal, >= 1 window closed+evicted (the
acceptance criteria); 1 otherwise.  ``--smoke`` runs as a slow-marked
tier-1 test (tests/test_stream.py).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

AGGS = [("amount", "sum"), ("amount", "count"), ("amount", "min"),
        ("amount", "max"), ("amount", "mean"), ("amount", "var"),
        ("amount", "std"), ("qty", "sum")]


def _quantile(xs, frac):
    """Nearest-rank quantile at FRACTION ``frac`` in [0, 1] (sibling
    bench_serving.py's private helper takes a 0-100 percent — the name
    difference keeps the two conventions from being confused)."""
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(int(round(frac * (len(xs) - 1))), len(xs) - 1)
    return xs[i]


def make_batches(args):
    """Seeded micro-batch stream: keys uniform, amounts integer cents
    (f64 — exact sums, the bit-equality representation), event times
    advancing ~args.stride per batch with in-batch jitter and ~5% late
    stragglers (3 windows back — past the lateness allowance, so the
    late policy engages)."""
    import numpy as np
    rng = np.random.default_rng(args.seed)
    out = []
    for b in range(args.batches):
        n = args.rows
        t = (b * args.stride
             + rng.integers(0, args.stride, n)).astype(np.int64)
        late = rng.random(n) < 0.05
        t = np.where(late & (t >= 3 * args.window),
                     t - 3 * args.window, t)
        out.append({
            "k": rng.integers(0, args.keys, n).astype(np.int64),
            "qty": rng.integers(1, 51, n).astype(np.int64),
            "amount": rng.integers(100, 100_000, n).astype(np.float64),
            "t": t,
        })
    return out


def run(args) -> dict:
    import hashlib

    import numpy as np
    import pandas as pd

    import cylon_tpu as ct
    from cylon_tpu import obs, tpch
    from cylon_tpu.ctx.context import CPUMeshConfig
    from cylon_tpu.exec import memory
    from cylon_tpu.exec.scheduler import QueryScheduler
    from cylon_tpu.relational.groupby import groupby_aggregate
    from cylon_tpu.stream import (IncrementalView, StreamTable,
                                  TumblingWindowJoin)

    env = ct.CylonEnv(config=CPUMeshConfig(world_size=args.world))
    dims = ct.Table.from_pydict(
        {"k": np.arange(args.keys, dtype=np.int64),
         "dim": (np.arange(args.keys, dtype=np.int64) * 7 + 3)}, env)

    st = StreamTable(env, key="k", name="bench")
    view = IncrementalView(st, "k", AGGS, name="bench_view", env=env)
    wj = TumblingWindowJoin(env, key="k", time_col="t",
                            window=args.window, build=dims, build_on="k",
                            lateness=args.lateness, late_policy="drop",
                            name="bench_wjoin")
    batches = make_batches(args)
    memory.reset_stats()
    ledger_before = memory.balance()

    staleness: list[float] = []
    wm_lag: list[int] = []
    max_event = [np.int64(-1)]
    metrics: dict = {}

    closed_at: list[int] = []   # closed_through at each batch's arrival
    #                             (the late-policy replay oracle input)

    def ingest():
        t_loop = time.perf_counter()
        for b in batches:
            t0 = time.perf_counter()
            st.append(dict(b))
            closed_at.append(wj._closed_through)
            wj.append(dict(b))
            wj.watermark()
            # append-to-visible: the snapshot INCLUDING this batch is
            # finalized and host-materialized before the clock stops
            view.read().to_pandas()
            staleness.append(time.perf_counter() - t0)
            max_event[0] = max(max_event[0], int(b["t"].max()))
            wm = wj._closed_through * args.window
            wm_lag.append(int(max_event[0]) - wm)
        # drain: vote the final watermark (closes every ripe window)
        wj.watermark()
        metrics["ingest_wall_s"] = time.perf_counter() - t_loop
        return True

    def query_tenant():
        pdfs = tpch.generate_pandas(scale=args.tpch_scale, seed=6)
        dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
        outs = []
        for _ in range(args.tpch_iters):
            outs.append(float(tpch.q6(dfs, env=env)))
            outs.append(len(tpch.q1(dfs, env=env).to_pandas()))
        return outs

    if args.serve:
        sched = QueryScheduler(env, policy="fair")
        sched.submit("ingest", ingest, kind="stream")
        sched.submit("tpch", query_tenant)
        sessions = sched.run(raise_errors=True)
        serving = {s.name: {"kind": s.kind, "slices": s.slices,
                            "latency_s": round(s.latency_s or 0.0, 4)}
                   for s in sessions}
        sched_stats = sched.stats()
    else:
        ingest()
        serving, sched_stats = {}, {}

    # ---- verdicts --------------------------------------------------------
    def sha(df) -> str:
        h = hashlib.sha256()
        for col in df.columns:
            h.update(str(col).encode())
            h.update(np.ascontiguousarray(df[col].to_numpy()).tobytes())
        return h.hexdigest()

    got = view.read().to_pandas().sort_values("k").reset_index(drop=True)
    exp = groupby_aggregate(st.snapshot(), "k", AGGS).to_pandas() \
        .sort_values("k").reset_index(drop=True)
    bit_equal = sha(got[exp.columns]) == sha(exp)

    # every closed window's join vs its batch recompute: the oracle
    # replays the drop policy against ARRIVAL order — a batch's rows
    # survive only if their window was still open when the batch landed
    # (closed_at[i] = windows already closed at batch i's arrival)
    frames = []
    for i, b in enumerate(batches):
        f = pd.DataFrame(b)
        frames.append(f[(f.t // args.window) >= closed_at[i]]
                      if i < len(closed_at) else f)
    full = pd.concat(frames)
    dims_pd = dims.to_pandas()
    windows_equal = True
    for wid, out in wj.closed:
        if out is None:
            continue
        g = out.to_pandas().sort_values(["k", "t", "qty", "amount"]) \
            .reset_index(drop=True)
        w = full[(full.t >= wid * args.window)
                 & (full.t < (wid + 1) * args.window)]
        e = w.merge(dims_pd, on="k").sort_values(
            ["k", "t", "qty", "amount"]).reset_index(drop=True)
        if len(g) != len(e) or sha(g[e.columns].astype(e.dtypes)) != sha(e):
            windows_equal = False

    total_rows = sum(len(b["k"]) for b in batches)
    wall = metrics.get("ingest_wall_s", 1e-9)
    detail = {
        "world": env.world_size,
        "batches": args.batches, "rows_per_batch": args.rows,
        "keys": args.keys, "window": args.window,
        "lateness": args.lateness,
        "serve_concurrent": bool(args.serve),
        "rows_ingested": total_rows,
        "ingest_wall_s": round(wall, 4),
        "staleness_p50_s": round(_quantile(staleness, 0.50), 4),
        "staleness_p99_s": round(_quantile(staleness, 0.99), 4),
        "watermark_lag_p50": _quantile(wm_lag, 0.50),
        "watermark_lag_max": max(wm_lag) if wm_lag else 0,
        "windows_closed": wj.windows_closed,
        "late_dropped": wj.late_dropped,
        # spill-tier counters through the shared collector
        # (cylon_tpu.obs.bench_detail — same keys as the hand-rolled
        # block it replaces)
        **obs.bench_detail(spill_keys=("window_evictions",
                                       "bytes_spilled"),
                           ckpt_keys=(), events=None),
        "ledger_delta_bytes": memory.balance() - ledger_before,
        "bit_equal": bool(bit_equal),
        "windows_bit_equal": bool(windows_equal),
        "view_stats": view.stats(),
        "stream_stats": st.stats(),
        "window_stats": wj.stats(),
        "serving": serving, "scheduler": sched_stats,
    }
    return {
        "metric": "sustained streaming ingest (view + windowed join, "
                  + ("concurrent TPC-H tenant" if args.serve
                     else "solo") + ")",
        "value": round(total_rows / wall, 1),
        "unit": "rows/s",
        "detail": detail,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batches", type=int, default=24)
    ap.add_argument("--rows", type=int, default=2500)
    ap.add_argument("--keys", type=int, default=64)
    ap.add_argument("--window", type=int, default=100)
    ap.add_argument("--stride", type=int, default=60)
    ap.add_argument("--lateness", type=int, default=30)
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tpch-scale", type=float, default=0.002)
    ap.add_argument("--tpch-iters", type=int, default=2)
    ap.add_argument("--no-serve", dest="serve", action="store_false",
                    help="run the ingest loop solo (no concurrent "
                         "TPC-H tenant / serving scheduler)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI shape; assert the acceptance criteria")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report here")
    args = ap.parse_args()
    if args.smoke:
        args.batches, args.rows, args.keys = 6, 250, 16
        args.tpch_scale, args.tpch_iters = 0.001, 1

    res = run(args)
    d = res["detail"]
    print(json.dumps(res, indent=2))
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(res, f, indent=2)
            f.write("\n")
    ok = (res["value"] > 0 and d["bit_equal"] and d["windows_bit_equal"]
          and d["windows_closed"] >= 1 and d["window_evictions"] >= 1)
    print(f"# {'OK' if ok else 'FAIL'}: {res['value']} rows/s, "
          f"p99 staleness {d['staleness_p99_s']}s, "
          f"{d['windows_closed']} windows closed, "
          f"{d['window_evictions']} evicted, bit_equal={d['bit_equal']}",
          flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
