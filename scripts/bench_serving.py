"""Closed-loop multi-tenant TPC-H serving bench (exec/scheduler).

The ROADMAP's "millions of users" rung measured: N tenants share ONE
mesh, each running a closed loop over its own TPC-H query mix (a new
query is issued the moment the previous one finishes), multiplexed by
the admission-controlled scheduler — tenants interleave at piece-loop /
shuffle boundaries, the HBM ledger is the admission controller, cold
tenants' packed sources spill under pressure, and every tenant's result
must stay BIT-EQUAL to its solo (single-session) run.

What one run produces (a JSON report: on stdout, or in the file ``--out``
names):

* per-tenant p50/p99 query latency, queries and rows/s served;
* aggregate rows/s across the mix;
* admission waits (count + seconds) and cross-tenant eviction / spill /
  recovery event counts — was the number achieved on the happy path or
  under managed pressure?
* a ``bit_equal`` verdict: sha256 over every query result vs the solo
  pass (the acceptance criterion; a serving tier that changes answers
  under load is not a serving tier).

The default budget ("auto") is sized to ~2.2 tenants' footprints so a
4-tenant run exercises BOTH acceptance events: later tenants wait at
admission until earlier ones drain, and concurrent packers evict each
other's cold sources through the consensus'd admission path.

``--families`` switches to the SHAPE-FAMILY compile-cost round
(docs/serving.md "Compile-cost contract"): N single-
controller tenants whose ingest row counts are near-misses inside ONE
pow2 shape family run the same join+groupby mix, and the facade's
compiled-program count must stay FLAT as the tenant count grows 4×
(tenants 2..N ride tenant 1's executables).  The report carries cold
(first-iteration, compiles included) vs warm p50/p99 and their gap, the
compiled-program trajectory, the ``CYLON_TPU_SHAPE_FAMILIES=0`` contrast
run (per-shape recompiles — the cost the canonicalization removes), and
a ``bit_equal`` verdict of every canonicalized result against its
exact-shape families-off oracle.

Usage::

    python scripts/bench_serving.py                    # 4 tenants
    python scripts/bench_serving.py --tenants 6 --queries 4 \
        --policy fair --budget-mb 24 --out serving.json
    python scripts/bench_serving.py --tenants 64 --smoke --preempt 8 \
        --slo-ms 2000                          # preemptive serving round
    python scripts/bench_serving.py --families         # shape-family round

Exit status 0 = completed and bit-equal; 1 otherwise.  A trimmed run is
wired as a slow-marked test (tests/test_scheduler.py).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import struct
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

#: per-tenant query mixes, cycled tenant i -> MIXES[i % len(MIXES)].
#: ``qpipe`` is the pipelined join+sink workload (piece-loop interleave
#: + spillable PieceSource registrations — the tenants that exercise
#: admission pressure); the rest are tpch.py queries (monolithic plans,
#: interleaving at shuffle boundaries).
MIXES = [
    ("qpipe", "q6", "q1"),
    ("qpipe", "q12"),
    ("q3", "q14", "q15"),
    ("q5", "q17"),
    ("q1", "q19", "q11"),
    ("qpipe", "q22"),
]

#: tables each query reads — the rows/s numerator and the footprint
#: estimate's input set
QUERY_TABLES = {
    "q1": ("lineitem",), "q3": ("customer", "orders", "lineitem"),
    "q5": ("customer", "orders", "lineitem", "supplier", "nation",
           "region"),
    "q6": ("lineitem",), "q11": ("partsupp", "supplier", "nation"),
    "q12": ("orders", "lineitem"), "q14": ("lineitem", "part"),
    "q15": ("lineitem", "supplier"), "q17": ("lineitem", "part"),
    "q19": ("lineitem", "part"), "q22": ("customer", "orders"),
    "qpipe": ("orders", "lineitem"),
}


def _result_sha(out) -> str:
    """sha256 over a query result's raw bytes (frames sorted by their
    columns first so row order is canonical).  Deliberately NOT shared
    with chaos_soak's hash helper: that one hashes pre-sorted frames of
    one fixed schema, this one must canonicalize arbitrary query
    outputs (row order, column names, float scalars) — the digests are
    only ever compared within this script."""
    import numpy as np
    h = hashlib.sha256()
    if isinstance(out, float):
        h.update(struct.pack("<d", out))
        return h.hexdigest()
    df = out.to_pandas() if hasattr(out, "to_pandas") else out
    # object-dtype columns (e.g. a groupby max that surfaced through
    # python scalars) hash their POINTER bytes — coerce to concrete
    # dtypes first or the digest is a fresh random per materialization
    df = df.infer_objects()
    df = df.sort_values(list(df.columns)).reset_index(drop=True)
    for col in df.columns:
        h.update(str(col).encode())
        h.update(np.ascontiguousarray(df[col].to_numpy()).tobytes())
    return h.hexdigest()


def _make_qpipe(env, dfs):
    """The pipelined sink workload: orders ⋈ lineitem per order key,
    quantity/price sums — runs through pipelined_join's range loop, so
    the tenant yields per piece and its PieceSource registrations are
    the spillable state the admission controller manages."""
    from cylon_tpu.exec import GroupBySink, pipelined_join

    def qpipe(dfs_, env_=None):
        sink = GroupBySink("l_orderkey", [("l_quantity", "sum"),
                                          ("l_extendedprice", "sum")])
        pipelined_join(dfs_["lineitem"]._table, dfs_["orders"]._table,
                       "l_orderkey", "o_orderkey", how="inner",
                       n_chunks=4, sink=sink)
        return sink.finalize()
    return qpipe


def _tenant_fn(name, mix, queries, dfs, env, qfuncs, record, hist=None,
               on_start=None):
    """Closed loop: cycle the mix for ``queries`` iterations, recording
    (query, latency, sha) into ``record`` as each completes.  ``hist``
    (concurrent pass only) is the tenant's streaming latency histogram
    in the metrics registry (cylon_tpu.obs) — the SLO-attainment
    source, bit-consistent with the sorted-list quantiles by the
    histogram's exact-sample contract.

    The fn RESETS its record and histogram on entry: a preempted tenant
    is requeued and its fn replayed from the top (committed qpipe
    pieces fast-forward), so stale partial observations from the
    drained attempt must not double-count — bit-equality compares the
    LAST full replay against the solo oracle."""
    def fn():
        if on_start is not None:
            on_start()
        record.clear()
        if hist is not None:
            hist.reset()
        for k in range(queries):
            qname = mix[k % len(mix)]
            t0 = time.perf_counter()
            out = qfuncs[qname](dfs, env_=env) if qname == "qpipe" \
                else qfuncs[qname](dfs, env=env)
            if hasattr(out, "to_pandas"):
                out = out.to_pandas()
            lat = time.perf_counter() - t0
            if hist is not None:
                hist.observe(lat)
            record.append({"q": qname, "latency_s": lat,
                           "sha": _result_sha(out)})
        return len(record)
    return fn


def _percentile(xs, p):
    import numpy as np
    # empty -> nan, matching the histogram edge contract
    # (obs/metrics.Histogram.percentile; docs/observability.md)
    return float(np.percentile(np.asarray(xs, float), p)) if xs \
        else float("nan")


def run_serving(tenants: int = 4, queries: int = 4, scale: float = 0.01,
                policy: str = "fair", budget_mb=None, world: int = 4,
                seed: int = 0, slo_ms: float | None = None,
                preempt_tenants: int = 0,
                ckpt_dir: str | None = None) -> dict:
    """Drive the bench in-process and return the report dict (the CLI
    wraps this; tests call it directly with trimmed parameters).
    ``budget_mb``: None = unlimited (no pressure), "auto" = ~2.2 tenant
    footprints (the acceptance configuration), or explicit MiB.
    ``slo_ms``: per-query latency SLO target — each tenant's report
    then carries its attainment fraction from the latency histogram.

    ``preempt_tenants``: hold back the LAST N tenants and submit them
    from inside the first tenant's closed loop at priority 5 — a
    high-priority arrival against an already-running fleet, which is
    the preemptive-scheduling trigger (docs/serving.md).  Requires a
    preemptive policy and ``ckpt_dir`` (victims drain at checkpoint
    boundaries and requeue; without durable stages preemption is
    flag-only best-effort).  ``ckpt_dir`` is armed for the CONCURRENT
    pass only — the solo oracle stays unarmed so the bit-equality
    baseline carries zero checkpoint machinery."""
    import jax
    import cylon_tpu as ct
    from cylon_tpu import config, obs, tpch
    from cylon_tpu.ctx.context import device_config
    from cylon_tpu.exec import checkpoint, memory, recovery
    from cylon_tpu.exec.scheduler import (QueryScheduler,
                                          estimate_footprint)

    env = ct.CylonEnv(config=device_config(world_size=world))
    dfs = tpch.generate_tables(scale=scale, env=env, seed=seed)
    row_counts = {k: int(v._table.row_count) for k, v in dfs.items()}

    qfuncs = {k: getattr(tpch, k) for k in
              {q for mix in MIXES for q in mix} - {"qpipe"}}
    qfuncs["qpipe"] = _make_qpipe(env, dfs)

    plans = []
    for i in range(int(tenants)):
        mix = MIXES[i % len(MIXES)]
        foot = estimate_footprint(
            *[dfs[t] for t in sorted({t for q in mix
                                      for t in QUERY_TABLES[q]})])
        plans.append({"name": f"t{i}", "mix": mix, "footprint": foot})

    # ---- solo pass: the bit-equality oracle -----------------------------
    solo = {}
    for p in plans:
        rec: list = []
        _tenant_fn(p["name"], p["mix"], queries, dfs, env, qfuncs, rec)()
        solo[p["name"]] = rec

    # ---- concurrent pass ------------------------------------------------
    # Budgets under "auto" (the acceptance configuration): the SCHEDULER
    # budget admits the two smallest tenants together and makes the
    # third wait (admission gates on declared footprints); the LEDGER
    # budget is 1.6x one measured qpipe resident peak, so two
    # concurrently packing tenants must evict each other's cold sources
    # through the consensus'd admission path.
    ledger_budget = 0
    if budget_mb == "auto":
        foots = sorted(p["footprint"] for p in plans)
        budget = int(1.05 * (foots[0] + foots[1])) if len(foots) > 2 \
            else int(2.2 * foots[-1])
        memory.reset_stats()
        qfuncs["qpipe"](dfs, env_=env)
        peak = memory.stats()["peak_ledger_bytes"]
        ledger_budget = int(1.6 * peak) if peak else 0
    elif budget_mb is None:
        budget = 0
    else:
        budget = int(float(budget_mb) * (1 << 20))
        ledger_budget = budget
    preempt_tenants = min(int(preempt_tenants), max(tenants - 1, 0))
    if preempt_tenants and policy not in QueryScheduler.PREEMPTIVE_POLICIES:
        raise ValueError(f"preempt_tenants requires a preemptive policy "
                         f"({QueryScheduler.PREEMPTIVE_POLICIES}), "
                         f"got {policy!r}")
    prev_budget = config.HBM_BUDGET_BYTES
    prev_ckpt = os.environ.get("CYLON_TPU_CKPT_DIR")
    memory.reset_stats()
    recovery.reset_events()
    checkpoint.reset_stats()
    checkpoint.reset_stages()
    records: dict[str, list] = {p["name"]: [] for p in plans}
    sched = QueryScheduler(env, policy=policy,
                           budget_bytes=budget or None)
    if ledger_budget:
        # the ledger's own allocation-time admission (PieceSource pack)
        # gates on the config budget
        config.HBM_BUDGET_BYTES = ledger_budget
    obs.metrics.reset("serving_latency")   # fresh histograms per round

    early = plans[:tenants - preempt_tenants]
    late = plans[tenants - preempt_tenants:]

    def _submit(p, priority=0, on_start=None):
        sched.submit(p["name"],
                     _tenant_fn(p["name"], p["mix"], queries, dfs,
                                env, qfuncs, records[p["name"]],
                                hist=obs.histogram(
                                    f"serving_latency_{p['name']}"),
                                on_start=on_start),
                     footprint_bytes=p["footprint"], priority=priority)

    fired = []

    def _submit_late():
        # runs on the first tenant's thread (under the baton); guarded
        # so a requeued replay of that tenant does not resubmit
        if fired or not late:
            return
        fired.append(True)
        for p in late:
            _submit(p, priority=5)

    try:
        if ckpt_dir is not None:
            os.environ["CYLON_TPU_CKPT_DIR"] = ckpt_dir
        for i, p in enumerate(early):
            _submit(p, on_start=_submit_late if i == 0 else None)
        t0 = time.perf_counter()
        sessions = sched.run()
        elapsed = time.perf_counter() - t0
    finally:
        config.HBM_BUDGET_BYTES = prev_budget
        if ckpt_dir is not None:
            if prev_ckpt is None:
                os.environ.pop("CYLON_TPU_CKPT_DIR", None)
            else:
                os.environ["CYLON_TPU_CKPT_DIR"] = prev_ckpt

    # ---- verdicts + metrics ---------------------------------------------
    failures = []
    for s in sessions:
        if s.error is not None:
            failures.append(f"{s.name}: {type(s.error).__name__}: "
                            f"{s.error}")
    bit_equal = True
    for p in plans:
        got = records[p["name"]]
        want = solo[p["name"]]
        if len(got) != len(want) or any(
                g["sha"] != w["sha"] or g["q"] != w["q"]
                for g, w in zip(got, want)):
            bit_equal = False
            failures.append(f"{p['name']}: concurrent results diverged "
                            "from the solo run")

    per_tenant = {}
    total_rows = 0
    for s in sessions:
        rec = records[s.name]
        lats = [r["latency_s"] for r in rec]
        rows = sum(sum(row_counts[t] for t in QUERY_TABLES[r["q"]])
                   for r in rec)
        total_rows += rows
        # SLO quantiles come from the streaming histogram registry
        # (obs.metrics) — the exact-sample contract makes them
        # BIT-CONSISTENT with the sorted-list np.percentile this script
        # used to compute, which the assert pins (acceptance criterion)
        hist = obs.histogram(f"serving_latency_{s.name}")
        p50, p99 = hist.percentile(50), hist.percentile(99)
        def _same(a, b):
            import math
            return a == b or (math.isnan(a) and math.isnan(b))
        assert _same(p50, _percentile(lats, 50)) and \
            _same(p99, _percentile(lats, 99)), \
            (s.name, p50, p99, _percentile(lats, 50), _percentile(lats, 99))
        per_tenant[s.name] = {
            "mix": list(next(p["mix"] for p in plans
                             if p["name"] == s.name)),
            "queries": len(rec),
            # NaN (no completed queries) reports as 0 like the old
            # None did — `or 0` no longer works because NaN is truthy
            "p50_latency_s": 0.0 if p50 != p50 else round(p50, 4),
            "p99_latency_s": 0.0 if p99 != p99 else round(p99, 4),
            **({"slo_target_s": slo_ms / 1e3,
                "slo_attainment": round(
                    hist.attainment(slo_ms / 1e3) or 0.0, 4)}
               if slo_ms is not None else {}),
            **{k: v for k, v in s.summary().items()
               if k not in ("name", "tenant", "state")},
        }

    # recovery events + spill counters through the shared collector
    # (cylon_tpu.obs.bench_detail — same keys the report always carried)
    bd = obs.bench_detail(
        spill_keys=("spill_events", "bytes_spilled", "readmit_events",
                    "cross_session_evictions", "peak_ledger_bytes"),
        ckpt_keys=())
    report = {
        "metric": f"TPC-H SF{scale:g} serving mix, {tenants} tenants "
                  f"x {queries} queries ({policy})",
        "value": round(total_rows / elapsed, 1) if elapsed else 0.0,
        "unit": "rows/s aggregate",
        "vs_baseline": 0.0,
        "detail": {
            "world": env.world_size,
            "platform": jax.devices()[0].platform,
            "scale": scale, "policy": policy,
            "budget_bytes": budget,
            "ledger_budget_bytes": ledger_budget,
            "elapsed_s": round(elapsed, 4),
            "queries_total": sum(len(r) for r in records.values()),
            "queries_per_s": round(
                sum(len(r) for r in records.values()) / elapsed, 3)
            if elapsed else 0.0,
            "bit_equal": bit_equal,
            "failures": failures,
            "scheduler": sched.stats(),
            "spill": {k: v for k, v in bd.items()
                      if k != "recovery_events"},
            "recovery_events": bd["recovery_events"],
            "tenants": per_tenant,
        },
    }
    return report


def run_families(tenants: int = 16, queries: int = 3,
                 family: int = 1024, seed: int = 0) -> dict:
    """The shape-family compile-cost round (docs/serving.md,
    "Compile-cost contract").  ``tenants`` single-controller tenants —
    ingest row counts spread across ONE pow2 family ``(family/2,
    family]`` — each run ``queries`` closed-loop join+groupby queries.
    Phase 1 (families on) measures the compiled-program trajectory:
    after tenant 1, after the first quarter of the fleet, and after the
    full 4× fleet — the contract is FLAT (misses_after_all ==
    misses_after_first).  Cold is each tenant's first iteration (tenant
    1's includes every real compile; later tenants' measure the family
    hit), warm is every subsequent iteration.  Phase 2 re-runs every
    tenant once with ``SHAPE_FAMILIES`` off — the exact-shape oracle for
    ``bit_equal`` AND the per-shape recompile contrast (its miss count
    must GROW with tenant count)."""
    import numpy as np
    import pandas as pd

    import cylon_tpu as ct
    from cylon_tpu import config
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import groupby_aggregate, join_tables

    env = ct.CylonEnv(config=ct.LocalConfig())
    n_keys = 64

    # distinct near-miss row counts inside one pow2 family: every
    # tenant canonicalizes onto the same padded ingest (and, with
    # unique build keys, the same data-independent join output cap)
    lo, hi = family // 2 + 8, family - 4
    sizes = sorted({int(x) for x in np.linspace(lo, hi, tenants)})
    while len(sizes) < tenants:     # collisions only at tiny counts
        sizes.append(sizes[-1] - 1)
    sizes = sorted(sizes)[:tenants]

    def make_inputs(i: int, n: int):
        r = np.random.default_rng(seed * 7919 + 1000 + i)
        ldf = pd.DataFrame({"k": r.integers(0, n_keys, n).astype(np.int32),
                            "v": r.integers(0, 10_000, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": np.arange(n_keys, dtype=np.int32),
                            "w": r.integers(0, 10_000,
                                            n_keys).astype(np.int64)})
        return ldf, rdf

    def run_query(ldf, rdf):
        lt = ct.Table.from_pandas(ldf, env)
        rt = ct.Table.from_pandas(rdf, env)
        j = join_tables(lt, rt, "k", "k", how="inner")
        out = groupby_aggregate(j, "k", [("v", "sum"), ("w", "max")])
        return out.to_pandas()

    inputs = [make_inputs(i, n) for i, n in enumerate(sizes)]

    # ---- phase 1: families on — the compile-cost trajectory ------------
    prev = config.SHAPE_FAMILIES
    config.SHAPE_FAMILIES = True
    compiler.reset_stats()
    cold, warm, fam_shas = [], [], []
    misses_after_first = misses_after_quarter = 0
    quarter = max(tenants // 4, 1)
    try:
        for i, (ldf, rdf) in enumerate(inputs):
            lats = []
            for it in range(queries):
                t0 = time.perf_counter()
                df = run_query(ldf, rdf)
                lats.append(time.perf_counter() - t0)
                if it == 0:
                    fam_shas.append(_result_sha(df))
            cold.append(lats[0])
            warm.extend(lats[1:])
            if i == 0:
                misses_after_first = compiler.stats()["cache_misses"]
            if i == quarter - 1:
                misses_after_quarter = compiler.stats()["cache_misses"]
        st = compiler.stats()
        misses_after_all = st["cache_misses"]
        programs_live = st["programs_live"]
        family_hits = st["cache_hits"]

        # ---- phase 2: families off — exact-shape oracle + contrast -----
        config.SHAPE_FAMILIES = False
        compiler.reset_stats()
        off_shas, off_first = [], 0
        for i, (ldf, rdf) in enumerate(inputs):
            off_shas.append(_result_sha(run_query(ldf, rdf)))
            if i == 0:
                off_first = compiler.stats()["cache_misses"]
        off_all = compiler.stats()["cache_misses"]
    finally:
        config.SHAPE_FAMILIES = prev

    flat = misses_after_all == misses_after_first
    bit_equal = fam_shas == off_shas
    failures = []
    if not flat:
        failures.append(f"compiled programs grew with tenant count: "
                        f"{misses_after_first} -> {misses_after_all}")
    if not bit_equal:
        bad = [i for i, (a, b) in enumerate(zip(fam_shas, off_shas))
               if a != b]
        failures.append(f"canonicalized results diverged from the "
                        f"exact-shape oracle for tenants {bad}")
    if off_all <= off_first:
        failures.append(f"families-off contrast did not recompile per "
                        f"shape: {off_first} -> {off_all}")

    cold_p50, warm_p50 = _percentile(cold, 50), _percentile(warm, 50)
    return {
        "metric": f"shape-family serving, {tenants} tenants x {queries} "
                  f"queries (single-controller, family {family})",
        "value": misses_after_all,
        "unit": "compiled programs at 4x tenant count",
        "vs_baseline": 0.0,
        "detail": {
            "tenants": tenants, "queries": queries,
            "family": family, "ingest_rows": sizes,
            "compiled_programs": {
                "after_first_tenant": misses_after_first,
                "after_quarter_fleet": misses_after_quarter,
                "after_full_fleet": misses_after_all,
                "flat": flat,
                "programs_live": programs_live,
                "family_cache_hits": family_hits,
            },
            "families_off_contrast": {
                "after_first_tenant": off_first,
                "after_full_fleet": off_all,
                "recompiles_added": off_all - off_first,
            },
            # tenant 1's first iteration is the only TRUE cold query
            # (every real compile happens there); tenants 2.. first
            # iterations measure the family hit — the contract is that
            # they land near warm, nowhere near cold
            "cold_first_tenant_s": round(cold[0], 4),
            "family_first_iters": {
                "p50_s": round(_percentile(cold[1:], 50), 4),
                "p99_s": round(_percentile(cold[1:], 99), 4),
                "n": len(cold) - 1},
            "cold": {"p50_s": round(cold_p50, 4),
                     "p99_s": round(_percentile(cold, 99), 4),
                     "n": len(cold)},
            "warm": {"p50_s": round(warm_p50, 4),
                     "p99_s": round(_percentile(warm, 99), 4),
                     "n": len(warm)},
            "cold_warm_gap": (round(cold[0] / warm_p50, 2)
                              if warm_p50 else 0.0),
            "bit_equal": bit_equal,
            "failures": failures,
        },
    }


def _write_report(report: dict, out: str | None) -> None:
    if out is None:
        print(json.dumps(report, indent=1))
        return
    with open(out, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    print(f"# wrote {out}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--queries", type=int, default=4,
                    help="closed-loop queries per tenant")
    ap.add_argument("--scale", type=float, default=0.01)
    ap.add_argument("--policy", default="fair",
                    choices=["fifo", "priority", "fair"])
    ap.add_argument("--budget-mb", default="auto",
                    help='"auto" (acceptance pressure), "none", or MiB')
    ap.add_argument("--world", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="per-query latency SLO target (ms): per-tenant "
                         "attainment is reported from the latency "
                         "histogram registry (docs/observability.md)")
    ap.add_argument("--smoke", action="store_true",
                    help="trimmed acceptance smoke: caps queries/tenant "
                         "at 2 and scale at SF0.004 (tenant count is NOT "
                         "trimmed — the slow-lane test runs 64)")
    ap.add_argument("--preempt", type=int, default=0, metavar="N",
                    help="hold back the last N tenants and submit them "
                         "mid-run at priority 5 (forces --policy "
                         "priority and arms --ckpt-dir so victims "
                         "drain at boundaries and requeue)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint root for the concurrent pass "
                         "(default with --preempt: a fresh temp dir)")
    ap.add_argument("--families", action="store_true",
                    help="run the shape-family compile-cost round "
                         "(single-controller: 4x tenant count at a FLAT "
                         "compiled-program count, cold vs warm latency, "
                         "bit-equality vs the SHAPE_FAMILIES=0 exact-"
                         "shape oracle); --tenants defaults to 16 here")
    ap.add_argument("--out", default=None,
                    help="write the JSON report here (default: stdout)")
    args = ap.parse_args()

    if args.families:
        tenants = args.tenants if args.tenants != 4 else 16
        report = run_families(tenants=tenants,
                              queries=max(args.queries, 2),
                              seed=args.seed)
        _write_report(report, args.out)
        d = report["detail"]
        cp = d["compiled_programs"]
        print(f"# {report['metric']}: {report['value']} {report['unit']}")
        print(f"# flat={cp['flat']} "
              f"({cp['after_first_tenant']} -> {cp['after_full_fleet']} "
              f"misses; families-off contrast adds "
              f"{d['families_off_contrast']['recompiles_added']})")
        print(f"# cold={d['cold_first_tenant_s']}s "
              f"warm_p50={d['warm']['p50_s']}s "
              f"gap={d['cold_warm_gap']}x "
              f"bit_equal={d['bit_equal']}")
        return 0 if (d["bit_equal"] and cp["flat"]
                     and not d["failures"]) else 1

    if args.smoke:
        args.queries = min(args.queries, 2)
        args.scale = min(args.scale, 0.004)
    ckpt_dir = args.ckpt_dir
    if args.preempt:
        args.policy = "priority"
        if ckpt_dir is None:
            import tempfile
            ckpt_dir = tempfile.mkdtemp(prefix="cylon_serving_ckpt_")

    budget = None if args.budget_mb in ("none", "0") else args.budget_mb
    report = run_serving(tenants=args.tenants, queries=args.queries,
                         scale=args.scale, policy=args.policy,
                         budget_mb=budget, world=args.world,
                         seed=args.seed, slo_ms=args.slo_ms,
                         preempt_tenants=args.preempt, ckpt_dir=ckpt_dir)
    _write_report(report, args.out)
    d = report["detail"]
    print(f"# {report['metric']}: {report['value']} {report['unit']}")
    print(f"# bit_equal={d['bit_equal']} "
          f"admission_waits={d['scheduler']['admission_waits']} "
          f"cross_session_evictions="
          f"{d['spill']['cross_session_evictions']} "
          f"spill_events={d['spill']['spill_events']}")
    print(f"# preemptions={d['scheduler']['preemptions']} "
          f"requeues={d['scheduler']['requeues']} "
          f"outcomes={d['scheduler']['outcomes']}")
    return 0 if (d["bit_equal"] and not d["failures"]) else 1


if __name__ == "__main__":
    sys.exit(main())
