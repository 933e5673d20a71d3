"""Pipelined chunked execution (C9 analog, exec/pipeline.py): chunked
streaming join must equal the monolithic operator, chunk decomposition must
re-cover the table, and per-chunk capacities must stay bounded (the memory
property that lets oversized joins run at all)."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import config
from cylon_tpu.exec import chunk_table, pipelined_join
from cylon_tpu.relational import (concat_tables, groupby_aggregate,
                                  join_tables)

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


def test_pipelined_groupby_sink_combines(env4, rng):
    """Streaming aggregation: per-chunk groupby sink + one partial combine
    equals the monolithic join+groupby (the out-of-HBM recipe that
    scripts/bench_pipelined.py runs at 96M rows/chip)."""
    n = 4000
    ldf = pd.DataFrame({"k": rng.integers(0, 300, n),
                        "a": rng.integers(0, 50, n)})
    rdf = pd.DataFrame({"k": rng.integers(0, 300, n // 2),
                        "b": rng.integers(0, 50, n // 2)})
    lt = ct.Table.from_pandas(ldf, env4)
    rt = ct.Table.from_pandas(rdf, env4)
    parts = pipelined_join(
        lt, rt, "k", "k", n_chunks=3,
        sink=lambda c: groupby_aggregate(c, "k", [("a", "sum"),
                                                  ("b", "sum")]))
    partial = concat_tables(parts)
    got = groupby_aggregate(partial, "k", [("a_sum", "sum"),
                                           ("b_sum", "sum")])
    exp = (ldf.merge(rdf, on="k").groupby("k", as_index=False)
           .agg(a_sum_sum=("a", "sum"), b_sum_sum=("b", "sum")))
    assert_table_matches(got, exp)


class TestGroupBySink:
    def test_sink_matches_monolithic(self, env4, rng):
        import cylon_tpu as ct
        from cylon_tpu.exec import GroupBySink, pipelined_join
        from cylon_tpu.relational import groupby_aggregate, join_tables
        n = 8000
        ldf = pd.DataFrame({"k": rng.integers(0, 900, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 900, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        lt, rt = ct.Table.from_pandas(ldf, env4), ct.Table.from_pandas(rdf, env4)
        aggs = [("a", "sum"), ("b", "mean"), ("a", "min"), ("b", "max"),
                ("a", "count"), ("b", "var"), ("a", "std")]
        sink = GroupBySink("k", aggs)
        pipelined_join(lt, rt, "k", "k", n_chunks=5, sink=sink)
        got = sink.finalize().to_pandas().sort_values("k").reset_index(drop=True)
        mono = groupby_aggregate(join_tables(lt, rt, "k", "k"), "k", aggs)
        exp = mono.to_pandas().sort_values("k").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)

    def test_sink_var_overlapping_chunks(self, env4, rng):
        """var/std must combine across chunks that SHARE keys (the sumsq
        partial path, no key-disjoint shortcut): feed overlapping chunks
        by hand."""
        from cylon_tpu.exec import GroupBySink
        import cylon_tpu as ct
        df = pd.DataFrame({"k": rng.integers(0, 40, 3000).astype(np.int64),
                           "v": rng.random(3000)})
        sink = GroupBySink("k", [("v", "var"), ("v", "std"), ("v", "mean")])
        for lo, hi in ((0, 1000), (1000, 2600), (2600, 3000)):
            sink(ct.Table.from_pandas(df.iloc[lo:hi], env4))
        got = sink.finalize().to_pandas().sort_values("k") \
            .reset_index(drop=True)
        exp = (df.groupby("k", as_index=False)
               .agg(v_var=("v", "var"), v_std=("v", "std"),
                    v_mean=("v", "mean")))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False, rtol=1e-9)

    def test_sink_rejects_nonstreaming_op(self):
        from cylon_tpu.exec import GroupBySink
        from cylon_tpu.status import InvalidError
        with pytest.raises(InvalidError):
            GroupBySink("k", [("a", "nunique")])


class TestOOMFallback:
    def _data(self, env, rng, n=6000):
        import cylon_tpu as ct
        ldf = pd.DataFrame({"k": rng.integers(0, 700, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 700, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        return (ldf, rdf, ct.Table.from_pandas(ldf, env),
                ct.Table.from_pandas(rdf, env))

    def test_join_oom_falls_back_to_pipeline(self, env4, rng, monkeypatch):
        from cylon_tpu.relational import join as rj
        ldf, rdf, lt, rt = self._data(env4, rng)
        calls = {"n": 0}
        orig = rj._join_tables_impl

        def flaky(*a, **k):
            # OOM on the top-level attempt; chunk joins (assume_colocated)
            # succeed
            if not k.get("assume_colocated") and len(a) < 8:
                calls["n"] += 1
                if calls["n"] == 1:
                    raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return orig(*a, **k)

        monkeypatch.setattr(rj, "_join_tables_impl", flaky)
        j = rj.join_tables(lt, rt, "k", "k", how="inner")
        got = j.to_pandas().sort_values(["k", "a", "b"]).reset_index(drop=True)
        exp = ldf.merge(rdf, on="k").sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got[exp.columns], exp,
                                      check_dtype=False)

    def test_groupby_oom_falls_back_to_chunked(self, env4, rng, monkeypatch):
        import cylon_tpu as ct
        from cylon_tpu.relational import groupby as rg
        ldf, rdf, lt, rt = self._data(env4, rng)
        t = ct.Table.from_pandas(ldf, env4)
        calls = {"n": 0}
        orig = rg._groupby_aggregate_impl

        def flaky(table, by, aggs, ddof=1):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return orig(table, by, aggs, ddof)

        monkeypatch.setattr(rg, "_groupby_aggregate_impl", flaky)
        g = rg.groupby_aggregate(t, "k", [("a", "sum"), ("a", "mean")])
        got = g.to_pandas().sort_values("k").reset_index(drop=True)
        exp = (ldf.groupby("k", as_index=False)
               .agg(a_sum=("a", "sum"), a_mean=("a", "mean")))
        exp.columns = got.columns
        pd.testing.assert_frame_equal(got, exp.sort_values("k")
                                      .reset_index(drop=True),
                                      check_dtype=False, rtol=1e-12)
        assert calls["n"] > 1

    def test_groupby_var_oom_falls_back(self, env4, rng, monkeypatch):
        """var/std now stream through the sumsq partial — the OOM fallback
        covers them (round-3 verdict gap: can_fallback was False)."""
        import cylon_tpu as ct
        from cylon_tpu.relational import groupby as rg
        ldf, _, _, _ = self._data(env4, rng)
        t = ct.Table.from_pandas(ldf, env4)
        calls = {"n": 0}
        orig = rg._groupby_aggregate_impl

        def flaky(table, by, aggs, ddof=1):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return orig(table, by, aggs, ddof)

        monkeypatch.setattr(rg, "_groupby_aggregate_impl", flaky)
        g = rg.groupby_aggregate(t, "k", [("a", "var"), ("a", "std")])
        got = g.to_pandas().sort_values("k").reset_index(drop=True)
        exp = (ldf.groupby("k", as_index=False)
               .agg(a_var=("a", "var"), a_std=("a", "std")))
        exp.columns = got.columns
        pd.testing.assert_frame_equal(got, exp.sort_values("k")
                                      .reset_index(drop=True),
                                      check_dtype=False, rtol=1e-9)
        assert calls["n"] > 1


class TestPipelinedSetOps:
    @pytest.mark.parametrize("op", ["union", "intersect", "subtract"])
    @pytest.mark.parametrize("world", ["env1", "env4"])
    def test_matches_monolithic(self, op, world, request, rng):
        import cylon_tpu as ct
        from cylon_tpu.exec import pipelined_set_op
        from cylon_tpu.relational import set_operation
        env = request.getfixturevalue(world)
        adf = pd.DataFrame({"k": rng.integers(0, 120, 3000).astype(np.int64),
                            "v": rng.integers(0, 4, 3000).astype(np.int64)})
        bdf = pd.DataFrame({"k": rng.integers(0, 120, 900).astype(np.int64),
                            "v": rng.integers(0, 4, 900).astype(np.int64)})
        at, bt = ct.Table.from_pandas(adf, env), ct.Table.from_pandas(bdf, env)
        got = pipelined_set_op(at, bt, op, n_chunks=3).to_pandas()
        exp = set_operation(at, bt, op).to_pandas()
        key = ["k", "v"]
        got = got.sort_values(key).reset_index(drop=True)
        exp = exp.sort_values(key).reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_setop_oom_falls_back(self, env4, rng, monkeypatch):
        import cylon_tpu as ct
        from cylon_tpu.relational import setops as rs
        adf = pd.DataFrame({"k": rng.integers(0, 80, 2000).astype(np.int64)})
        bdf = pd.DataFrame({"k": rng.integers(0, 80, 500).astype(np.int64)})
        at, bt = ct.Table.from_pandas(adf, env4), ct.Table.from_pandas(bdf, env4)
        calls = {"n": 0}
        orig = rs._set_operation_impl

        def flaky(a, b, op, assume_colocated=False):
            calls["n"] += 1
            if calls["n"] == 1 and not assume_colocated:
                raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")
            return orig(a, b, op, assume_colocated)

        # pipelined_set_op resolves _set_operation_impl at call time from
        # the setops module, so this single patch covers both paths
        monkeypatch.setattr(rs, "_set_operation_impl", flaky)
        got = rs.set_operation(at, bt, "subtract").to_pandas()
        A, B = adf.drop_duplicates(), bdf.drop_duplicates()
        exp = A.merge(B, on="k", how="left", indicator=True)
        exp = exp[exp._merge == "left_only"][["k"]]
        assert sorted(got["k"].tolist()) == sorted(exp["k"].tolist())
        assert calls["n"] > 1


class TestPackedPieces:
    """The packed-piece join entry (relational/piece.py + join.py packed
    programs): window slice + lane unpack fused into the join program.
    Contract: EXACTLY equal — same rows, same order, same bits — to the
    seed's materialize-then-join path."""

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_packed_equals_materialized_exactly(self, env4, rng, how):
        n = 3000
        ldf = pd.DataFrame({
            "k": rng.integers(0, 200, n).astype(np.int64),
            "a": rng.random(n),                              # f64 side col
            "c": rng.integers(0, 9, n).astype(np.int32),
            "s": rng.choice(["x", "y", "z"], n).astype(object)})
        rdf = pd.DataFrame({"k": rng.integers(50, 260, n // 2).astype(np.int64),
                            "b": rng.random(n // 2)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = config.PACKED_PIECES
        try:
            config.PACKED_PIECES = True
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=4).to_pandas()
            config.PACKED_PIECES = False
            ref = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=4).to_pandas()
        finally:
            config.PACKED_PIECES = prev
        # exact: both paths must produce identical rows in identical order
        pd.testing.assert_frame_equal(got, ref, check_exact=True)
        exp = ldf.merge(rdf, on="k", how=how)
        assert len(got) == len(exp)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_donation_and_pallas_probe_bit_equal(self, env4, rng, how):
        """Buffer donation (CYLON_TPU_DONATE), the overlap scheduler
        (CYLON_TPU_PACKED_OVERLAP) and the Pallas probe kernel
        (CYLON_TPU_PALLAS_PROBE, interpreter mode on CPU) must each be
        EXACTLY equal — same rows, same order, same bits — to the plain
        per-phase-sync, no-donation dispatch."""
        from cylon_tpu.ops import pallas_probe
        n = 4096  # per-shard capacity 1024: Pallas tile-aligned
        ldf = pd.DataFrame({
            "k": rng.integers(0, 300, n).astype(np.int64),
            "a": rng.random(n),                              # f64 side col
            "s": rng.choice(["x", "y", "z"], n).astype(object)})
        rdf = pd.DataFrame({"k": rng.integers(100, 400, n).astype(np.int64),
                            "b": rng.random(n)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = (config.PACKED_OVERLAP, config.DONATE_BUFFERS,
                config.PALLAS_PROBE)
        probed = []
        orig_supported = pallas_probe.supported

        def spy(cap, n_split, kinds):
            ok = orig_supported(cap, n_split, kinds)
            probed.append(ok)
            return ok

        try:
            config.PACKED_OVERLAP = False
            config.DONATE_BUFFERS = False
            config.PALLAS_PROBE = False
            ref = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            config.PACKED_OVERLAP = True
            config.DONATE_BUFFERS = True
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            pd.testing.assert_frame_equal(got, ref, check_exact=True)
            config.PALLAS_PROBE = True
            pallas_probe.supported = spy
            got = pipelined_join(lt, rt, "k", "k", how=how,
                                 n_chunks=3).to_pandas()
            pd.testing.assert_frame_equal(got, ref, check_exact=True)
        finally:
            pallas_probe.supported = orig_supported
            (config.PACKED_OVERLAP, config.DONATE_BUFFERS,
             config.PALLAS_PROBE) = prev
        # the eligibility gate must have actually routed the probe
        # through the kernel — a silent fallback would make the pallas
        # leg of this test vacuous
        assert probed == [True]
        exp = ldf.merge(rdf, on="k", how=how)
        assert len(got) == len(exp)

    def test_pallas_probe_kernel_wide_operand_bit_equal(self, rng):
        """Kernel-level bit-equality over the operand shapes the narrow
        single-lane join test can't reach: a MULTI-operand key whose lo
        lane is uint32 (the wide-int64 (hi int32, lo uint32) pack pair —
        ops/pack) with values straddling the 0x80000000 rebase boundary
        and hi-lane ties forcing the lexicographic eq-chain."""
        import jax.numpy as jnp
        from cylon_tpu.ops import pack, pallas_probe
        cap, nsplit = 2048, 13
        hi = rng.integers(-3, 3, cap).astype(np.int32)   # heavy ties
        lo = rng.integers(0, 2**32, cap, dtype=np.uint64).astype(np.uint32)
        lo[:64] = np.uint32(0x80000000)                  # rebase boundary
        lo[64:128] = np.uint32(0x7FFFFFFF)
        live = np.ones(cap, np.int32)
        sel = rng.integers(0, cap, nsplit)
        kinds = ("i", "i", "i")
        assert pallas_probe.supported(cap, nsplit, kinds)
        ops = (jnp.asarray(live), jnp.asarray(hi), jnp.asarray(lo))
        sops = (jnp.asarray(live[sel]), jnp.asarray(hi[sel]),
                jnp.asarray(lo[sel]))
        ge = pack.rows_ge_splitters(pack.KeyOps(ops=ops, kinds=kinds), sops)
        ref = jnp.sum(ge, axis=1, dtype=jnp.int32)
        got = pallas_probe.count_ge_splitters(ops, sops)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_pallas_probe_wide_int64_keys_bit_equal(self, env4, rng):
        """End-to-end: wide int64 keys (bounds past int32, negatives
        included) pack as TWO value operands per key — the Pallas probe
        must engage (eligibility spy) and stay bit-equal to the XLA
        matrix path through the full pipelined join."""
        from cylon_tpu.ops import pallas_probe
        n = 4096
        pool = rng.integers(-2**62, 2**62, 300, dtype=np.int64)
        ldf = pd.DataFrame({"k": rng.choice(pool, n),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.choice(pool, n // 2),
                            "b": rng.random(n // 2)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        prev = config.PALLAS_PROBE
        probed = []
        orig_supported = pallas_probe.supported

        def spy(cap, n_split, kinds):
            ok = orig_supported(cap, n_split, kinds)
            probed.append(ok)
            return ok

        try:
            config.PALLAS_PROBE = False
            ref = pipelined_join(lt, rt, "k", "k", how="inner",
                                 n_chunks=3).to_pandas()
            config.PALLAS_PROBE = True
            pallas_probe.supported = spy
            got = pipelined_join(lt, rt, "k", "k", how="inner",
                                 n_chunks=3).to_pandas()
        finally:
            pallas_probe.supported = orig_supported
            config.PALLAS_PROBE = prev
        assert probed == [True]
        pd.testing.assert_frame_equal(got, ref, check_exact=True)
        assert len(got) == len(ldf.merge(rdf, on="k", how="inner"))

    def test_overlap_one_host_sync_per_piece(self, env4, rng):
        """Acceptance: under the overlap scheduler the range loop costs
        at most ONE sanctioned host pull per piece (the transfer funnel's
        ledger is the counter), and disabling overlap restores the
        per-phase pulls (strictly more) — the escape hatch contract."""
        from cylon_tpu.analysis import runtime
        n = 4096
        lt = ct.Table.from_pydict(
            {"k": rng.integers(0, 2000, n).astype(np.int64),
             "a": rng.integers(0, 50, n).astype(np.int64)}, env4)
        rt = ct.Table.from_pydict(
            {"k": rng.integers(0, 2000, n).astype(np.int64),
             "b": rng.integers(0, 50, n).astype(np.int64)}, env4)

        def pulls(nc, overlap):
            prev = config.PACKED_OVERLAP
            config.PACKED_OVERLAP = overlap
            try:
                with runtime.transfer_scope() as ledger:
                    pipelined_join(lt, rt, "k", "k", how="inner",
                                   n_chunks=nc)
                return sum(ledger.values())
            finally:
                config.PACKED_OVERLAP = prev

        p3, p6 = pulls(3, True), pulls(6, True)
        # dense uniform keys: every range qualifies, pieces == n_chunks.
        # marginal host syncs per extra piece <= 1
        assert p6 - p3 <= 3, (p3, p6)
        # the one batched pre-loop sync beats the per-phase pulls
        assert p3 < pulls(3, False)

    def test_packed_join_defers_with_lazy_counts(self, env4, rng):
        """A packed inner join with allow_defer hands back a DeferredTable
        whose output counts stay ON DEVICE until someone asks — the piece
        loop enqueues the next piece's programs before this one's host
        sync.  Materialization must still be exact."""
        from cylon_tpu.core.table import DeferredTable
        from cylon_tpu.relational.piece import PieceSource
        from cylon_tpu.relational.join import join_tables as jt
        from cylon_tpu.relational.sort import local_sort_table
        n = 2000
        ldf = pd.DataFrame({"k": rng.integers(0, 150, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 150, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        from cylon_tpu.relational.repart import shuffle_table
        lw = shuffle_table(lt, ["k"])
        rw = shuffle_table(rt, ["k"])
        ls = local_sort_table(lw, ["k"])
        rs = local_sort_table(rw, ["k"])
        src_l = PieceSource(ls, 0)
        src_r = PieceSource(rs, 0)
        w = env4.world_size
        zl = np.zeros(w, np.int64)
        pl = src_l.packed(zl, np.asarray(ls.valid_counts), ls.capacity)
        pr = src_r.packed(zl, np.asarray(rs.valid_counts), rs.capacity)
        out = jt(pl, pr, ["k"], ["k"], how="inner", allow_defer=True)
        assert isinstance(out, DeferredTable) and not out.materialized
        # counts pull on demand; materialization equals the reference join
        ref = jt(lw, rw, ["k"], ["k"], how="inner", assume_colocated=True,
                 allow_defer=False)
        assert out.row_count == ref.row_count
        got = out.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        exp = ref.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_exact=True)


class TestPackedWindowPadding:
    """A piece whose ``lens < piece_cap``: the rows of the window past
    ``lens`` are REAL rows of the source (the next range's keys), so a
    program that took one of them for live would join it.  Row liveness
    in the packed count program is the sorted position compare
    (ops/join.live_sides)."""

    @pytest.mark.parametrize("how,defer", [
        ("inner", False), ("inner", True),      # only inner joins defer
        ("left", False), ("right", False), ("outer", False)])
    def test_short_window_matches_pandas(self, env4, rng, how, defer):
        from cylon_tpu.relational.join import join_tables as jt
        from cylon_tpu.relational.piece import PieceSource
        from cylon_tpu.relational.repart import shuffle_table
        from cylon_tpu.relational.sort import local_sort_table
        n = 1500
        ldf = pd.DataFrame({"k": rng.integers(0, 90, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 90, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        ls = local_sort_table(shuffle_table(
            ct.Table.from_pandas(ldf, env4), ["k"]), ["k"])
        rs = local_sort_table(shuffle_table(
            ct.Table.from_pandas(rdf, env4), ["k"]), ["k"])
        w = env4.world_size
        zero = np.zeros(w, np.int64)
        # a third of each shard's left rows, two thirds of its right rows
        # (shard 1: no left row at all) in windows of the full capacity
        len_l = np.asarray(ls.valid_counts) // 3
        len_l[1] = 0
        len_r = 2 * np.asarray(rs.valid_counts) // 3
        pl = PieceSource(ls, 0).packed(zero, len_l, ls.capacity)
        pr = PieceSource(rs, 0).packed(zero, len_r, rs.capacity)
        assert (pl.lens < pl.piece_cap).all()
        got = jt(pl, pr, ["k"], ["k"], how=how, allow_defer=defer)
        exp = pl.to_table().to_pandas().merge(pr.to_table().to_pandas(),
                                              on="k", how=how)
        assert got.row_count == len(exp)
        assert_table_matches(got, exp, sort_by=list(exp.columns))


class TestRangeBoundsSentinel:
    """_range_bounds_fn's +inf sentinel edge: a build shard whose live
    prefix is exactly at capacity (n == cap) has NO padding row to serve
    as the boundary sentinel — the explicit sentinel slot must make
    boundary operands read +infinity, or probe rows holding the shard's
    max key silently lose matches (round-4 regression, now for all four
    join types)."""

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_exact_capacity_all_hows(self, env1, rng, how):
        n = 4096  # == pow2 capacity at world 1
        bdf = pd.DataFrame({"k": np.full(n, 7, np.int64),
                            "b": rng.random(n)})
        # probe: the build's max key (must hit all n rows) + a key beyond
        # it (must route to the last range, not vanish past the end)
        pdf = pd.DataFrame({"k": np.where(np.arange(96) % 2 == 0, 7, 9)
                            .astype(np.int64),
                            "a": rng.random(96)})
        lt = ct.Table.from_pandas(pdf, env1)
        rt = ct.Table.from_pandas(bdf, env1)
        assert rt.capacity == rt.row_count  # the no-padding premise
        out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4)
        exp = pdf.merge(bdf, on="k", how=how)
        assert out.row_count == len(exp)
        assert_table_matches(out, exp)

    @pytest.mark.parametrize("how", ["inner", "left", "right", "outer"])
    def test_no_qualifying_range_fallback(self, env1, how):
        """With a 2-row build, range 0 snaps empty and all probe keys
        (below the build's min) route there — for inner no range
        qualifies at all (the outs == [] fallback); every how must keep
        the uniform output schema and exact pandas semantics."""
        bdf = pd.DataFrame({"k": np.array([10, 20], np.int64),
                            "b": [1.0, 2.0]})
        pdf = pd.DataFrame({"k": np.array([1, 2, 3], np.int64),
                            "a": [0.1, 0.2, 0.3]})
        lt = ct.Table.from_pandas(pdf, env1)
        rt = ct.Table.from_pandas(bdf, env1)
        out = pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4)
        exp = pdf.merge(bdf, on="k", how=how)
        assert out.row_count == len(exp)
        assert list(out.column_names) == ["k", "a", "b"]
        if len(exp):
            assert_table_matches(out, exp)


class TestGroupBySinkHows:
    """pipelined_join(..., sink=GroupBySink) must match the monolithic
    join→groupby for every streaming join type, not just inner — and both
    with the key-disjoint fast path (sink keyed on the join keys) and
    without it (sink keyed on a payload column, cross-chunk combine)."""

    def _data(self, env, rng, n=3000):
        ldf = pd.DataFrame({"k": rng.integers(0, 250, n).astype(np.int64),
                            "g": rng.integers(0, 7, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(100, 350, n // 2)
                            .astype(np.int64),
                            "b": rng.integers(0, 50, n // 2)
                            .astype(np.int64)})
        return (ldf, rdf, ct.Table.from_pandas(ldf, env),
                ct.Table.from_pandas(rdf, env))

    @pytest.mark.parametrize("how", ["left", "right", "outer"])
    def test_sink_matches_monolithic(self, env4, rng, how):
        from cylon_tpu.exec import GroupBySink
        _ldf, _rdf, lt, rt = self._data(env4, rng)
        aggs = [("a", "sum"), ("b", "mean"), ("b", "count")]
        sink = GroupBySink("k", aggs)
        pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4, sink=sink)
        assert sink._disjoint  # keyed on the join keys: fast path taken
        got = sink.finalize().to_pandas().sort_values("k") \
            .reset_index(drop=True)
        mono = groupby_aggregate(
            join_tables(lt, rt, "k", "k", how=how), "k", aggs)
        exp = mono.to_pandas().sort_values("k").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                      rtol=1e-9)

    @pytest.mark.parametrize("how", ["inner", "outer"])
    def test_sink_non_key_by_combines_across_chunks(self, env4, rng, how):
        """by != join keys: groups SPAN chunks, so the cross-chunk combine
        (no disjoint shortcut) must run and still match the monolith."""
        from cylon_tpu.exec import GroupBySink
        _ldf, _rdf, lt, rt = self._data(env4, rng)
        aggs = [("a", "sum"), ("b", "mean")]
        sink = GroupBySink("g", aggs)
        pipelined_join(lt, rt, "k", "k", how=how, n_chunks=4, sink=sink)
        assert not sink._disjoint
        got = sink.finalize().to_pandas().sort_values("g") \
            .reset_index(drop=True)
        mono = groupby_aggregate(
            join_tables(lt, rt, "k", "k", how=how), "g", aggs)
        exp = mono.to_pandas().sort_values("g").reset_index(drop=True)
        pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                      rtol=1e-9)


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
