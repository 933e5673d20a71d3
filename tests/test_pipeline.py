"""Pipelined chunked execution (C9 analog, exec/pipeline.py): chunked
streaming join must equal the monolithic operator, chunk decomposition must
re-cover the table, and per-chunk capacities must stay bounded (the memory
property that lets oversized joins run at all).  Its sinks and fallbacks:
test_pipeline_sinks.py; the packed-piece entry: test_pipeline_packed.py."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import config
from cylon_tpu.exec import chunk_table, pipelined_join
from cylon_tpu.relational import concat_tables, join_tables

from utils import assert_table_matches


@pytest.fixture(params=["env1", "env4", "env8"])
def env(request):
    return request.getfixturevalue(request.param)


def test_chunks_recover_table(env, rng):
    df = pd.DataFrame({"k": rng.integers(0, 40, 333),
                       "s": rng.choice(["a", "b", "c"], 333),
                       "v": rng.random(333)})
    df.loc[df.index % 11 == 0, "v"] = None
    t = ct.Table.from_pandas(df, env)
    chunks = chunk_table(t, 4)
    assert sum(c.row_count for c in chunks) == t.row_count
    back = concat_tables(chunks)
    # per-shard chunk order re-covers each shard's prefix => global rows
    # are a permutation; compare as multisets
    assert_table_matches(back, df)


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
@pytest.mark.parametrize("n_chunks", [2, 5])
def test_pipelined_join_matches_monolithic(env, rng, how, n_chunks):
    n = 4000
    ldf = pd.DataFrame({"k": rng.integers(0, 300, n), "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(100, 400, n // 2),
                        "b": rng.random(n // 2)})
    lt = ct.Table.from_pandas(ldf, env)
    rt = ct.Table.from_pandas(rdf, env)
    out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=n_chunks)
    exp = ldf.merge(rdf, on="k", how=how)
    assert out.row_count == len(exp)
    assert_table_matches(out, exp)


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
def test_pipelined_join_null_and_string_keys(env4, rng, how):
    """Range partitioning must keep null-key and dictionary-coded string
    groups intact (splitter operands include the null flags, so a null
    run snaps to one range like any other key group)."""
    n = 1500
    ldf = pd.DataFrame({"k": rng.choice(["ant", "bee", "cow", "dog", "elk"],
                                        n).astype(object),
                        "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.choice(["bee", "cow", "dog", "fox"],
                                        n // 2).astype(object),
                        "b": rng.random(n // 2)})
    ldf.loc[ldf.index % 7 == 0, "k"] = None
    rdf.loc[rdf.index % 5 == 0, "k"] = None
    lt = ct.Table.from_pandas(ldf, env4)
    rt = ct.Table.from_pandas(rdf, env4)
    out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=3)
    exp = ldf.merge(rdf, on="k", how=how)
    assert out.row_count == len(exp)
    assert_table_matches(out, exp)


def test_pipelined_join_exact_capacity_max_key(env1, rng):
    """Regression (round-4 review): when a shard's valid count EQUALS its
    capacity there is no padding row to serve as the +inf splitter
    sentinel; the boundary gather must not fall back to the last live key
    or probe rows holding the shard's max key silently lose matches.
    Single-key build at an exact pow2 row count is the worst case (every
    candidate position lands inside the one run)."""
    n = 4096  # == pow2 capacity at world 1
    ldf = pd.DataFrame({"k": np.full(n, 7, np.int64), "a": rng.random(n)})
    rdf = pd.DataFrame({"k": np.full(n, 7, np.int64), "b": rng.random(n)})
    lt = ct.Table.from_pandas(ldf, env1)
    rt = ct.Table.from_pandas(rdf, env1)
    assert rt.capacity == rt.row_count  # the no-padding premise
    out = pipelined_join(lt, rt, "k", "k", n_chunks=4)
    assert out.row_count == n * n


@pytest.mark.parametrize("how", ["inner", "outer"])
def test_pipelined_join_multi_key(env4, rng, how):
    n = 2000
    ldf = pd.DataFrame({"k1": rng.integers(0, 30, n),
                        "k2": rng.integers(0, 9, n),
                        "a": rng.random(n)})
    rdf = pd.DataFrame({"k1": rng.integers(0, 30, n // 2),
                        "k2": rng.integers(0, 9, n // 2),
                        "b": rng.random(n // 2)})
    lt = ct.Table.from_pandas(ldf, env4)
    rt = ct.Table.from_pandas(rdf, env4)
    out = pipelined_join(lt, rt, ["k1", "k2"], ["k1", "k2"], how=how,
                         n_chunks=4)
    exp = ldf.merge(rdf, on=["k1", "k2"], how=how)
    assert out.row_count == len(exp)
    assert_table_matches(out, exp)


def test_chunked_capacity_bounded(env8, rng):
    """Each chunk's join materializes at ~1/C of the monolithic output
    capacity — the memory bound that lets oversized joins run."""
    n = 8000
    ldf = pd.DataFrame({"k": rng.integers(0, 50, n), "a": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(0, 50, n // 4),
                        "b": rng.random(n // 4)})
    lt = ct.Table.from_pandas(ldf, env8)
    rt = ct.Table.from_pandas(rdf, env8)
    mono = join_tables(lt, rt, "k", "k")
    chunks = chunk_table(lt, 8)
    assert max(c.capacity for c in chunks) <= -(-lt.capacity // 8)
    out = pipelined_join(lt, rt, "k", "k", n_chunks=8)
    assert out.row_count == mono.row_count


class TestLazyChunks:
    def test_sequence_protocol(self, env4, rng):
        df = pd.DataFrame({"k": rng.integers(0, 40, 500),
                           "v": rng.random(500)})
        t = ct.Table.from_pandas(df, env4)
        chunks = chunk_table(t, 4)
        assert len(chunks) == 4
        assert chunks[-1].row_count == chunks[3].row_count
        assert [c.row_count for c in chunks[1:3]] == \
            [chunks[1].row_count, chunks[2].row_count]
        with pytest.raises(IndexError):
            chunks[4]
        # re-indexing re-dispatches the same slice (pure function of i)
        assert chunks[0].row_count == chunks[0].row_count
        assert sum(c.row_count for c in chunks) == t.row_count


def test_async_timing_mode_records_dispatch_only(env1, rng):
    """CYLON_TPU_TIMING=async: maybe_block is a no-op and regions record
    dispatch-only markers — the pipelined phases still appear in the
    snapshot, without the per-phase device syncs."""
    from cylon_tpu.utils import timing
    prev_bench, prev_async = config.BENCH_TIMINGS, config.TIMING_ASYNC
    df = pd.DataFrame({"k": rng.integers(0, 60, 800).astype(np.int64),
                       "a": rng.integers(0, 9, 800).astype(np.int64)})
    t = ct.Table.from_pandas(df, env1)
    try:
        config.BENCH_TIMINGS = True
        config.TIMING_ASYNC = True
        timing.reset()
        out = pipelined_join(t, t, "k", "k", n_chunks=3)
        snap = timing.snapshot()
    finally:
        config.BENCH_TIMINGS = prev_bench
        config.TIMING_ASYNC = prev_async
        timing.reset()
    assert out.row_count == len(df.merge(df, on="k"))
    assert "pipe.piece_join" in snap and snap["pipe.piece_join"]["n"] >= 1
    assert "pipe.build_sort" in snap


@pytest.mark.slow
class TestBenchSmoke:
    def test_smoke_dispatch_path(self, env4):
        """scripts/bench_smoke.py: the bench driver's pipelined sink path
        at a tiny shape — phase markers recorded, streamed result equals
        the monolith exactly (dispatch-path regressions surface here
        instead of in a TPU bench round)."""
        import os
        import sys
        scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
        sys.path.insert(0, scripts)
        try:
            from bench_smoke import EXPECTED_PHASES, run_smoke
        finally:
            # remove by value: importing bench_smoke itself prepends the
            # repo root to sys.path, so pop(0) would strip the wrong entry
            sys.path.remove(scripts)
        snap = run_smoke(env=env4, rows=16384, n_chunks=4)
        assert all(p in snap for p in EXPECTED_PHASES)

    def test_smoke_all_dispatch_rungs(self, env4):
        """The same tiny-shape path with ALL ISSUE-6 dispatch rungs
        pinned on — overlap scheduler + buffer donation + Pallas probe
        (interpreter mode on CPU): the three flag paths stay covered by
        tier-1, run_smoke itself asserts the phase_sync marker and that
        the Pallas eligibility gate engaged (no silent fallback)."""
        import os
        import sys
        scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
        sys.path.insert(0, scripts)
        try:
            from bench_smoke import run_smoke
        finally:
            sys.path.remove(scripts)
        snap = run_smoke(env=env4, rows=16384, n_chunks=4,
                         overlap=True, donate=True, pallas=True)
        assert "pipe.phase_sync.block" in snap
