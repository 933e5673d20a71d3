#!/usr/bin/env python
"""Tiny-shape smoke run of the pipelined join+groupby dispatch path.

The north-star configuration (125M rows/chip through the
range-partitioned pipeline with a fused GroupBySink) only runs on
accelerator rigs, and no benchmark cell takes that route yet — a
dispatch-path regression there (a phase silently dropped, the sink no
longer engaging, the packed-piece path bailing to materialize) would
otherwise surface first on a chip.
This script runs the SAME code path at <= 64k rows on whatever devices
exist (CPU mesh included), asserts the expected phase markers were
recorded, and checks the streamed result equals the monolithic
join+groupby bit-for-bit on the integer sums.

Usage:
    JAX_PLATFORMS=cpu python scripts/bench_smoke.py [--rows=N]

Exit status 0 and one JSON line on success; wired as a ``slow``-marked
tier-1 test in tests/test_pipeline.py (TestBenchSmoke).
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: phase keys the pipelined sink path must record (dispatch markers)
EXPECTED_PHASES = (
    "pipe.build_sort", "pipe.bounds", "pipe.targets", "pipe.probe_sort",
    "pipe.pack", "pipe.piece_join", "pipe.consume",
)


def run_smoke(env=None, rows: int = 65536, n_chunks: int = 4,
              overlap: bool | None = None, donate: bool | None = None,
              pallas: bool | None = None) -> dict:
    """Run the pipelined join+groupby at a tiny shape and verify the
    dispatch path: phase keys present, sink result == monolith.  Returns
    the phase snapshot dict.  Raises AssertionError on any regression.

    ``overlap``/``donate``/``pallas`` pin the ISSUE-6 dispatch rungs
    (CYLON_TPU_PACKED_OVERLAP / CYLON_TPU_DONATE / CYLON_TPU_PALLAS_PROBE)
    for the run; ``None`` keeps the session config.  With overlap ON the
    pre-loop batched sync marker (``pipe.phase_sync.block``) must appear;
    with the Pallas probe requested, the eligibility gate must actually
    route the kernel (no silent fallback at this tile-aligned shape)."""
    import numpy as np

    import cylon_tpu as ct
    from cylon_tpu import config
    from cylon_tpu.exec import GroupBySink, pipelined_join
    from cylon_tpu.ops import pallas_probe
    from cylon_tpu.relational import groupby_aggregate, join_tables
    from cylon_tpu.utils import timing

    assert rows <= 65536, "smoke stays tiny: <= 64k rows"
    if env is None:
        from cylon_tpu.ctx.context import device_config
        env = ct.CylonEnv(config=device_config())

    rng = np.random.default_rng(7)
    max_val = max(int(rows * 0.9), 1)
    lt = ct.Table.from_pydict(
        {"k": rng.integers(0, max_val, rows).astype(np.int64),
         "a": rng.integers(0, 1000, rows).astype(np.int64)}, env)
    rt = ct.Table.from_pydict(
        {"k": rng.integers(0, max_val, rows).astype(np.int64),
         "b": rng.integers(0, 1000, rows).astype(np.int64)}, env)

    prev = (config.BENCH_TIMINGS, config.TIMING_ASYNC,
            config.PACKED_OVERLAP, config.DONATE_BUFFERS,
            config.PALLAS_PROBE)
    probed = []
    orig_supported = pallas_probe.supported

    def spy(cap, n_split, kinds):
        ok = orig_supported(cap, n_split, kinds)
        probed.append(ok)
        return ok

    try:
        config.BENCH_TIMINGS = True
        config.TIMING_ASYNC = True      # dispatch-only markers (bench mode)
        if overlap is not None:
            config.PACKED_OVERLAP = overlap
        if donate is not None:
            config.DONATE_BUFFERS = donate
        if pallas is not None:
            config.PALLAS_PROBE = pallas
            pallas_probe.supported = spy
        timing.reset()
        sink = GroupBySink("k", [("a", "sum"), ("b", "sum")])
        pipelined_join(lt, rt, "k", "k", how="inner", n_chunks=n_chunks,
                       sink=sink)
        got = sink.finalize()
        snap = timing.snapshot()
        overlap_on = config.PACKED_OVERLAP
    finally:
        pallas_probe.supported = orig_supported
        (config.BENCH_TIMINGS, config.TIMING_ASYNC, config.PACKED_OVERLAP,
         config.DONATE_BUFFERS, config.PALLAS_PROBE) = prev
        timing.reset()

    missing = [p for p in EXPECTED_PHASES if p not in snap]
    assert not missing, f"pipelined phases missing from profile: {missing}"
    if overlap_on:
        assert "pipe.phase_sync" + timing.BLOCK_SUFFIX in snap, \
            "overlap on but the pre-loop batched sync marker is missing"
    if pallas:
        assert probed == [True], \
            f"Pallas probe requested but the gate saw {probed}"

    mono = groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                             "k", [("a", "sum"), ("b", "sum")])
    gp = got.to_pandas().sort_values("k").reset_index(drop=True)
    mp = mono.to_pandas().sort_values("k").reset_index(drop=True)
    assert len(gp) == len(mp), (len(gp), len(mp))
    for col in ("k", "a_sum", "b_sum"):
        # integer sums: the streamed decomposition must be EXACT
        assert (gp[col].to_numpy() == mp[col].to_numpy()).all(), col
    return snap


def main() -> int:
    rows = 65536
    all_rungs = "--all-rungs" in sys.argv
    for a in sys.argv[1:]:
        if a.startswith("--rows="):
            rows = int(a.split("=", 1)[1])
    kw = {"overlap": True, "donate": True, "pallas": True} if all_rungs \
        else {}
    snap = run_smoke(rows=rows, **kw)
    print(json.dumps({"metric": "pipelined smoke", "rows": rows,
                      "ok": True, "all_rungs": all_rungs, "phases_s":
                      {k: v["s"] for k, v in snap.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
