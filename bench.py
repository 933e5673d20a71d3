"""Benchmark driver: distributed join + groupby throughput.

The BASELINE.json north-star workload: inner merge on random int64 keys
followed by groupby-sum, measured as rows/sec/chip.  The table shape follows
the reference's scaling driver (rivanna/scripts/cylon_scaling.py:31-37): two
int64 columns per side — a key column and a value column — with keys drawn
from [0, total_rows * 0.9) ("uniqueness factor" u = 0.9), per-rank rows =
rows_per_chip.  Our pipeline additionally groupby-sums the joined values
(BASELINE.json: join+groupby).

Runs on every visible TPU chip.  Only ``--cpu-mesh`` selects the CPU (a
virtual 8-device mesh); without it, no TPU is an error, and an OOM at the
asked size is the result — the row count is never changed.  Prints ONE
JSON line:
  {"metric": ..., "value": N, "unit": "rows/sec/chip", "vs_baseline": N}

vs_baseline anchors to the reference's published weak-scaling join number
(BASELINE.md: 1M rows/rank at 0.60 s/iter on Summit, 42 ranks/node =>
~1.67M rows/sec/rank for join alone; we use the same per-worker rows/sec
denominator for the join+groupby pipeline).

Flags: --rows=N (per chip; default 125M on TPU — the BASELINE.json
north-star per-chip share, auto-routed through the range-partitioned
pipeline — 1M with --cpu-mesh), --unique=F, --iters=K, --cpu-mesh, --tpch (TPC-H
instead, see cylon_tpu.tpch), --slices=S (declare an S-slice two-tier
fabric — exchanges route through the hierarchical two-hop engine and
the detail records per-tier rows/bytes/messages; cylon_tpu/topo,
docs/topology.md).
"""

from __future__ import annotations

import json
import os
import sys
import time

CPU_MESH = "--cpu-mesh" in sys.argv
if CPU_MESH:
    # the virtual CPU mesh is chosen before jax is imported
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402


def require_tpu() -> None:
    """Without --cpu-mesh the bench measures a TPU or nothing."""
    if CPU_MESH:
        return
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"bench.py: no TPU found — jax reports platform "
                 f"{d.platform!r} ({d.device_kind}); pass --cpu-mesh to run "
                 "on the virtual CPU mesh")

#: reference anchor: Summit weak scaling, 1M rows/rank/iter at 0.60 s
#: (BASELINE.md summit results-1000000) => rows/sec/worker
BASELINE_ROWS_PER_SEC_PER_WORKER = 1_000_000 / 0.60


def _sync(arr):
    """Force execution and wait (see cylon_tpu.utils.host.sync_pull).
    Under async profiling this is THE iteration-end block — its
    ``bench.output_sync.block`` entry absorbs all device time the
    dispatch-only phase markers enqueued and nothing else pulled."""
    from cylon_tpu.utils import timing
    from cylon_tpu.utils.host import sync_pull
    with timing.sync_region("bench.output_sync"):
        sync_pull(arr)


def run(rows_per_chip: int, unique: float = 0.9, iters: int = 4,
        skew: float = 0.0) -> dict:
    import cylon_tpu as ct
    from cylon_tpu import config, obs
    from cylon_tpu.ctx.context import CPUMeshConfig, TPUConfig
    from cylon_tpu.exec import checkpoint, memory, recovery
    from cylon_tpu.relational import groupby_aggregate, join_tables
    from cylon_tpu.utils import timing

    if os.environ.get("CYLON_TPU_DISTRIBUTED", "0") == "1":
        # multi-host pod launch (deploy/): form the world first
        cfg = TPUConfig(distributed=True)
    else:
        cfg = CPUMeshConfig() if CPU_MESH else TPUConfig()
    env = ct.CylonEnv(config=cfg)
    devs = jax.devices()
    w = env.world_size

    n = rows_per_chip * w
    max_val = max(int(n * unique), 1)
    rng = np.random.default_rng(42)
    lk = rng.integers(0, max_val, n).astype(np.int64)
    if skew > 0.0:
        # BASELINE.json config 5 (skewed-key join): a ``skew`` fraction of
        # probe rows share ONE hot key (tests/test_skew.py convention) —
        # exercises the heavy-hitter split path (probe hot keys spread
        # round-robin, build hot rows duplicate-broadcast).  The build side
        # stays uniform so the join output stays ~O(n).
        hot = np.int64(max_val // 2)
        lk = np.where(rng.random(n) < skew, hot, lk)
    lt = ct.Table.from_pydict(
        {"k": lk, "a": rng.integers(0, max_val, n).astype(np.int64)}, env)
    rk = rng.integers(0, max_val, n).astype(np.int64)
    if skew > 0.0:
        # apples-to-apples across skew levels: the hot key appears
        # EXACTLY once on the build side, so every probe row — hot or
        # not — joins ~1 build row and the output stays ~n rows at any
        # skew (a hot key that randomly drew 2+ build rows would double
        # the skewed config's output and poison the throughput ratio)
        rk[rk == hot] = hot + 1
        rk[0] = hot
    rt = ct.Table.from_pydict(
        {"k": rk, "b": rng.integers(0, max_val, n).astype(np.int64)}, env)

    # Route by size: the monolithic fused join+groupby OOMs past ~48M
    # rows/chip in 16 GB HBM; the north-star config (125M rows/chip = 1B
    # rows on v5e-8, BASELINE.json) runs through the range-partitioned
    # pipeline (exec/pipeline.py), whose per-piece working set is 1/R.
    # CYLON_TPU_BENCH_PIPELINE=1 forces the pipelined route at any size —
    # e.g. to demonstrate the HBM-budget spill tier on a CPU rig
    # (CYLON_TPU_HBM_BUDGET below the resident working set makes the
    # detail's spill_events go positive; docs/robustness.md).
    pipelined = (rows_per_chip > 48_000_000
                 or os.environ.get("CYLON_TPU_BENCH_PIPELINE") == "1")
    n_chunks = max(2, -(-rows_per_chip // 21_000_000)) if pipelined else 1

    if pipelined:
        from cylon_tpu.exec import GroupBySink, pipelined_join

        def step():
            sink = GroupBySink("k", [("a", "sum"), ("b", "sum")])
            pipelined_join(lt, rt, "k", "k", how="inner",
                           n_chunks=n_chunks, sink=sink)
            g = sink.finalize()
            _sync(next(iter(g.columns.values())).data)
            return g
    else:
        def step():
            j = join_tables(lt, rt, "k", "k", how="inner")
            g = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
            _sync(next(iter(g.columns.values())).data)
            return g

    # timed iterations run with region timings OFF: timing.maybe_block
    # inserts per-phase device syncs that serialize the pipelined sink's
    # dispatch/pull overlap — the phase profile comes from ONE extra
    # profiled iteration afterwards.  That iteration runs in ASYNC
    # attribution mode (CYLON_TPU_TIMING=async semantics): regions record
    # dispatch-only markers and the step's final output sync is the one
    # block — the phase numbers no longer serialize (or hide) the overlap
    # they are meant to expose.  Set CYLON_TPU_TIMING=block to profile
    # with per-phase device syncs instead (exact attribution, perturbed
    # overlap).
    timing_async = os.environ.get("CYLON_TPU_TIMING", "async") == "async"
    prev_flag = config.BENCH_TIMINGS
    prev_async = config.TIMING_ASYNC
    config.BENCH_TIMINGS = False
    recovery.reset_events()  # detail reports THIS workload's recoveries
    memory.reset_stats()     # ... and THIS workload's spill traffic
    checkpoint.reset_stats()  # ... and THIS workload's checkpoint traffic
    try:
        step()  # warmup + compile
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            step()
            times.append(time.perf_counter() - t0)
        config.BENCH_TIMINGS = True
        config.TIMING_ASYNC = timing_async
        timing.reset()
        t0 = time.perf_counter()
        # profiled (async mode: one block at the final sync), wrapped in
        # the query profiler: the bench JSON carries the EXPLAIN ANALYZE
        # plan tree alongside the phase table it reconciles with
        # (obs/plan, docs/observability.md).  profile_keys=False: the
        # key sampler would add device programs + mid-iteration host
        # pulls, breaking profiled_iter_s comparability with the
        # BENCH_rNN baselines and the one-designated-block async
        # contract above (the --skew heavy-hitter profile below runs
        # OUTSIDE the timed iteration instead)
        qplan = obs.explain_analyze(step, reset_timings=False,
                                    profile_keys=False)
        profiled_s = time.perf_counter() - t0
    finally:
        config.BENCH_TIMINGS = prev_flag
        config.TIMING_ASYNC = prev_async
    best = min(times)
    rows_per_sec_per_chip = (2 * n) / best / w
    # dispatch/block attribution split (utils/timing.split_snapshot):
    # under async profiling every plain region is host time to ENQUEUE
    # its work and every ".block" twin (sync_region — the pipelined
    # join's batched phase pull) is deliberate blocking time.  A phase
    # whose dispatch AND block are both near zero has left the critical
    # path — its device work hides under another phase's block point,
    # which is how piece r+1's overlap with piece r's consume shows up.
    snap = timing.snapshot()
    dispatch_s, block_s = timing.split_snapshot(snap)
    # --slices: the multi-slice topology decision + per-tier traffic
    # (cylon_tpu/topo, docs/topology.md).  The registry counters are
    # process-cumulative; this process ran only this workload, so the
    # snapshot IS the run's traffic.  dcn_rows/bytes are route-invariant
    # payload (each remote row crosses DCN once either way); the
    # two-hop win reads off dcn_messages (~1/R of the flat route's) and,
    # on concentrated count matrices, dcn_wire_bytes.
    topo_detail = {}
    topo_t = env.topology
    if topo_t.n_slices > 1:
        from cylon_tpu.topo import model as topo_model
        tplan = topo_model.last_plan()
        topo_detail = {
            "topology": {"n_slices": topo_t.n_slices,
                         "ranks_per_slice": topo_t.ranks_per_slice,
                         "source": topo_t.source},
            "topo_plan": tplan.summary() if tplan is not None else None,
            "tier_traffic": {
                name: int(obs.counter(f"exchange_{name}_total").value)
                for name in ("ici_rows", "dcn_rows", "ici_bytes",
                             "dcn_bytes", "ici_wire_bytes",
                             "dcn_wire_bytes", "ici_messages",
                             "dcn_messages")},
        }
    # capture the ARMED per-rank report of the (split-armed) profiled
    # iteration BEFORE the unsplit baseline leg below resets the timing
    # accumulators for its own "before" snapshot
    rank_rep = obs.rank_report.report() if obs.rank_report.armed() else None
    # ... and the recovery/spill/checkpoint counters: they were reset to
    # report THIS workload's events, and the unsplit audit leg below can
    # spill/degrade on its own (the hot key concentrates on one rank
    # there) — its events must not read as the measured run's
    bench_counters = obs.bench_detail(plan=qplan)

    # --skew: the adaptive skew-split decision + an UNSPLIT baseline leg
    # (CYLON_TPU_SKEW_SPLIT=0 semantics) on the same config, so the win —
    # and the plan that bought it — are auditable in one BENCH row
    # (docs/skew.md; ISSUE 14 acceptance: skew-0.9 throughput >= 80% of
    # skew-0.0 on the same config).
    skew_detail = {}
    if skew > 0.0:
        from cylon_tpu.relational import skew as skew_facade
        plan_rec = skew_facade.last_plan()
        skew_detail["skew_route"] = ("skew_split" if plan_rec is not None
                                     else "hash")
        skew_detail["skew_plan"] = (plan_rec.summary()
                                    if plan_rec is not None else None)
        if plan_rec is not None:
            skew_detail["skew_split_keys"] = int(len(plan_rec))
            skew_detail["skew_fanout"] = [int(f) for f in plan_rec.fanout]
    # the audit leg only means something when the profiled run actually
    # split — on the pipelined route (plain hashing, no plan) or a
    # detection decline the re-run would compare two identical unsplit
    # executions at full workload cost
    if skew > 0.0 and skew_detail.get("skew_route") == "skew_split":
        prev_split = config.SKEW_SPLIT
        prev_bench2 = config.BENCH_TIMINGS
        config.SKEW_SPLIT = False
        config.BENCH_TIMINGS = False
        try:
            step()  # warmup/compile the unsplit programs
            # min-of-N against min-of-N: `best` is the split run's best
            # of `iters` samples, so the unsplit leg gets the same
            # treatment — a one-shot sample would let ordinary
            # per-iteration jitter inflate the recorded speedup
            un_times = []
            for _ in range(iters):
                t0 = time.perf_counter()
                step()
                un_times.append(time.perf_counter() - t0)
            skew_detail["unsplit_iter_s"] = round(min(un_times), 4)
            skew_detail["unsplit_all_iters_s"] = [round(t, 4)
                                                  for t in un_times]
            skew_detail["split_vs_unsplit_speedup"] = round(
                skew_detail["unsplit_iter_s"] / best, 3)
            if obs.rank_report.armed():
                # the "before" half of the before/after rank-skew pair
                # (the armed main report above is the "after")
                config.BENCH_TIMINGS = True
                config.TIMING_ASYNC = timing_async
                timing.reset()
                step()
                skew_detail["rank_phase_skew_unsplit"] = \
                    obs.rank_report.report()
        finally:
            config.SKEW_SPLIT = prev_split
            config.BENCH_TIMINGS = prev_bench2
            config.TIMING_ASYNC = prev_async
    return {
        "metric": ("dist join+groupby throughput (int64 keys"
                   + (f", skew={skew:g}" if skew else "") + ")"),
        "value": round(rows_per_sec_per_chip, 1),
        "unit": "rows/sec/chip",
        "vs_baseline": round(rows_per_sec_per_chip
                             / BASELINE_ROWS_PER_SEC_PER_WORKER, 3),
        "detail": {
            "world": w,
            "platform": devs[0].platform,
            "rows_per_chip": rows_per_chip,
            "pipelined": pipelined,
            "n_chunks": n_chunks,
            "unique": unique,
            "skew": skew,
            "best_iter_s": round(best, 4),
            "all_iters_s": [round(t, 4) for t in times],
            "timing_mode": "async" if timing_async else "block",
            "profiled_iter_s": round(profiled_s, 4),
            # dispatch-path config: which of the three ISSUE-6 rungs were
            # active for this number (escape hatches: CYLON_TPU_PACKED_*,
            # CYLON_TPU_DONATE, CYLON_TPU_PALLAS_PROBE)
            "packed_pieces": config.PACKED_PIECES,
            "packed_overlap": config.PACKED_OVERLAP,
            "donate_buffers": config.DONATE_BUFFERS,
            "pallas_probe": config.PALLAS_PROBE,
            "phases_s": {k: v["s"] for k, v in snap.items()},
            "phases_dispatch_s": dispatch_s,
            "phases_block_s": block_s,
            # per-rank min/median/max phase skew (obs/rank_report,
            # CYLON_TPU_RANK_REPORT=1): the measurement rung the
            # heavy-hitter work stands on — one hot rank's piece_join
            # seconds towering over the median IS the skew signal.
            # Unarmed: not called, zero extra collectives.
            **({"rank_phase_skew": rank_rep}
               if rank_rep is not None else {}),
            # --skew: plan decision + unsplit-baseline audit leg
            **skew_detail,
            # --slices: topology decision + per-tier traffic
            **topo_detail,
            # heavy-hitter profile of the skewed key column (obs/plan
            # key_profile — Misra-Gries over shard-weighted samples):
            # names the hot keys and their estimated share, the ROADMAP
            # item 2 detection baseline.  Only computed when --skew
            # asked for a skewed workload (one small device sample).
            **({"heavy_hitters": obs.plan.key_profile(lt, "k")}
               if skew > 0.0 else {}),
            # armed comm matrix (CYLON_TPU_COMM_MATRIX=1): the
            # per-(src,dst) rows/bytes report rides the plan section
            # below (detail.plan.comm_matrix — QueryPlan.to_dict embeds
            # it; a second top-level copy would just be payload drift)
            # recovery events + spill-tier + durable-checkpoint counters
            # (cylon_tpu.obs.bench_detail — the collector every bench
            # script shares): recovery_events says whether the number
            # was achieved on the happy path or after degradation;
            # spill_events > 0 means PCIe-assisted, not HBM-resident;
            # checkpoint_events > 0 paid page writes in-loop, and
            # resume_world_mismatch vs resume_resharded_pieces tells
            # "resharded and fast-forwarded" apart from "threw the
            # checkpoint away" after a topology change (elastic resume);
            # plan= attaches the profiled iteration's EXPLAIN ANALYZE
            # tree as the "plan" section.  Snapshotted BEFORE the
            # unsplit audit leg so its events stay out of this run's
            # counters.
            **bench_counters,
        },
    }


def main() -> dict:
    require_tpu()
    rows = None
    unique = 0.9
    iters = 4
    scale = None
    skew = 0.0
    for a in sys.argv[1:]:
        if a.startswith("--rows="):
            rows = int(a.split("=", 1)[1])
        elif a.startswith("--scale="):
            scale = float(a.split("=", 1)[1])
        elif a.startswith("--unique="):
            unique = float(a.split("=", 1)[1])
        elif a.startswith("--iters="):
            iters = int(a.split("=", 1)[1])
        elif a.startswith("--skew="):
            skew = float(a.split("=", 1)[1])
        elif a.startswith("--slices="):
            # declare an n-slice two-tier fabric BEFORE the env (and
            # therefore the topology cache) exists — the hierarchical
            # two-hop route then carries every exchange and the bench
            # detail records per-tier bytes/messages (cylon_tpu/topo,
            # docs/topology.md)
            os.environ["CYLON_TPU_SLICES"] = a.split("=", 1)[1]

    if "--tpch" in sys.argv:
        from cylon_tpu.tpch import bench_tpch
        return bench_tpch(scale=scale if scale is not None else 0.1,
                          iters=iters)

    if rows is None:
        # 125M/chip: the north-star per-chip share (BASELINE.json: 1B rows
        # on v5e-8).  Out-of-HBM scale routes through the range-partitioned
        # pipeline automatically (see run()); --rows=32000000 measures the
        # monolithic in-HBM regime.
        rows = 1_000_000 if CPU_MESH else 125_000_000
    return run(rows_per_chip=rows, unique=unique, iters=iters, skew=skew)


if __name__ == "__main__":
    print(json.dumps(main()))
