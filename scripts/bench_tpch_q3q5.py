"""BASELINE config 4 as written: TPC-H SF10 Q3/Q5 (one chip or CPU mesh).

The full 10-query suite keeps every base table and every query's
intermediates resident, which exceeds one v5e's 16 GB past SF5.  Config 4
names exactly two queries, so this driver ingests only the columns Q3/Q5
touch (the reference's scaling drivers do the same: cylon_scaling.py
materializes just the workload columns) — at SF10 that is ~3 GB of base
tables, leaving HBM for the join intermediates; joins that still exceed
memory fall back to the range-partitioned pipeline automatically
(relational/join.py OOM fallback).

Usage: python scripts/bench_tpch_q3q5.py [scale] [iters]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

Q3_COLS = {
    "customer": ["c_custkey", "c_mktsegment", "c_nationkey"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"],
    "lineitem": ["l_orderkey", "l_suppkey", "l_extendedprice", "l_discount",
                 "l_shipdate"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
}


def main():
    scale = float(sys.argv[1]) if len(sys.argv) > 1 else 10.0
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 2

    import jax
    import cylon_tpu as ct
    from cylon_tpu import obs, tpch
    from cylon_tpu.ctx.context import device_config
    from cylon_tpu.exec import checkpoint, memory, recovery

    recovery.reset_events()
    memory.reset_stats()
    checkpoint.reset_stats()

    devs = jax.devices()
    env = ct.CylonEnv(config=device_config())

    pdfs = tpch.generate_pandas(scale=scale)
    dfs = {name: ct.DataFrame(pdfs.pop(name)[cols], env=env)
           for name, cols in Q3_COLS.items()}
    del pdfs

    times = {}
    plans = {}
    for name, fn in (("q3", tpch.q3), ("q5", tpch.q5)):
        def step():
            out = fn(dfs, env=env)
            out.to_pandas()
            return out
        step()  # warmup/compile
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            step()
            ts.append(time.perf_counter() - t0)
        times[name] = min(ts)
        # one extra ANALYZE-profiled run per query: the emitted JSON
        # carries the plan tree (per-node rows/bytes/seconds + the
        # phase-table reconcile block) alongside the wall times
        plans[name] = obs.explain_analyze(step).to_dict()
        print(f"# {name}: {times[name]:.3f}s", flush=True)

    print(json.dumps({
        "metric": f"TPC-H SF{scale:g} Q3+Q5 wall time (BASELINE config 4)",
        "value": round(sum(times.values()), 4),
        "unit": "seconds",
        "detail": {"world": env.world_size, "platform": devs[0].platform,
                   "scale": scale,
                   # recovery + spill + checkpoint counters through the
                   # shared collector (cylon_tpu.obs.bench_detail):
                   # happy path vs post-degradation, resident vs
                   # host-spilled, re-shard vs thrown-away checkpoint
                   **obs.bench_detail(spill_keys=(
                       "spill_events", "bytes_spilled",
                       "peak_ledger_bytes")),
                   # EXPLAIN ANALYZE trees, one per query (obs/plan)
                   "plans": plans,
                   **{f"{n}_s": round(t, 4) for n, t in times.items()}},
    }))


if __name__ == "__main__":
    main()
