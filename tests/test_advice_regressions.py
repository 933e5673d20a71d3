"""Regression tests for the round-1 advisor findings."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.frame import DataFrame
from cylon_tpu.status import CylonKeyError, InvalidError


def _df(data, env):
    return DataFrame(pd.DataFrame(data), env=env)


class TestNullMaskFilter:
    """frame.py bool-mask filter must treat null predicate rows as False."""

    def test_null_rows_excluded(self, env1):
        df = _df({"s": ["a", None, "b"], "v": [1, 2, 3]}, env1)
        out = df[df["s"] < "b"].to_pandas()
        assert out["v"].tolist() == [1]

    def test_null_rows_excluded_dist(self, env4):
        df = _df({"s": ["a", None, "b", "c", None, "a", "b", "c"],
                  "v": list(range(8))}, env4)
        out = df[df["s"] < "b"].to_pandas()
        assert sorted(out["v"].tolist()) == [0, 5]


class TestNaNSkippingAggs:
    """groupby + Series reductions skip float NaN like pandas skipna=True."""

    def test_groupby_sum_skips_nan(self, env1):
        pdf = pd.DataFrame({"k": [0, 0, 1, 1], "x": [1.0, np.nan, 2.0, 3.0]})
        df = _df(pdf, env1)
        got = (df.groupby("k").sum().to_pandas()
               .sort_values("k").reset_index(drop=True))
        exp = pdf.groupby("k", as_index=False)["x"].sum()
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_groupby_mean_min_count_skip_nan(self, env4):
        rng = np.random.default_rng(0)
        x = rng.random(64)
        x[::5] = np.nan
        pdf = pd.DataFrame({"k": rng.integers(0, 4, 64), "x": x})
        df = _df(pdf, env4)
        got = (df.groupby("k").agg({"x": ["mean", "min", "count"]})
               .to_pandas().sort_values("k").reset_index(drop=True))
        exp = (pdf.groupby("k", as_index=False)
               .agg(x_mean=("x", "mean"), x_min=("x", "min"),
                    x_count=("x", "count")))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                      check_exact=False)

    def test_series_sum_skips_nan(self, env1):
        df = _df({"x": [1.0, np.nan, 2.0]}, env1)
        assert df["x"].sum() == pytest.approx(3.0)
        assert df["x"].count() == 2
        assert df["x"].mean() == pytest.approx(1.5)
        assert df["x"].min() == pytest.approx(1.0)


class TestIlocLocSemantics:
    def test_iloc_list_order_preserved(self, env1):
        df = _df({"v": [10, 11, 12, 13, 14]}, env1)
        assert df.iloc[[3, 1]].to_pandas()["v"].tolist() == [13, 11]

    def test_iloc_list_duplicates(self, env4):
        df = _df({"v": list(range(16))}, env4)
        assert df.iloc[[5, 5, 2]].to_pandas()["v"].tolist() == [5, 5, 2]

    def test_loc_partially_missing_label_raises(self, env1):
        df = _df({"k": [1, 2, 3], "v": [10, 20, 30]}, env1).set_index("k")
        with pytest.raises(CylonKeyError):
            df.loc[[1, 99]]

    def test_loc_string_missing_label_raises(self, env1):
        df = _df({"k": ["a", "b"], "v": [1, 2]}, env1).set_index("k")
        with pytest.raises(CylonKeyError):
            df.loc[["a", "zz"]]


class TestInt64Precision:
    def test_sum_beyond_2_53(self, env1):
        big = (1 << 53) + 1
        df = _df({"x": np.asarray([big, 2], np.int64)}, env1)
        assert df["x"].sum() == big + 2  # float64 round-trip would lose the +1
        assert df["x"].max() == big


class TestSetitemLayoutCheck:
    def test_misaligned_series_rejected(self, env4):
        # same per-shard capacity (8), different valid_counts -> must reject
        a = _df({"v": list(range(24))}, env4)          # (6, 6, 6, 6) cap 8
        b = _df({"w": list(range(24))}, env4)
        from cylon_tpu.relational import repartition
        t = repartition(b.table, (8, 8, 4, 4))          # cap 8 too
        misaligned = DataFrame.from_table(t)
        assert t.capacity == a.table.capacity
        with pytest.raises(InvalidError):
            a["w"] = misaligned["w"]


class TestReviewFindings:
    """Round-2 inline code-review findings."""

    def test_iloc_preserves_nullable_int_dtype(self, env1):
        # nullable int column (e.g. from an outer join) must survive iloc
        l = _df({"k": [1, 2], "a": [10, 20]}, env1)
        r = _df({"k": [2, 3], "b": [5, 6]}, env1)
        m = l.merge(r, on="k", how="outer").sort_values("k")
        out = m.iloc[[2, 0]]
        assert out.dtypes["a"] != "str"
        pdm = m.to_pandas().reset_index(drop=True)
        got = out.to_pandas().reset_index(drop=True)
        exp = pdm.iloc[[2, 0]].reset_index(drop=True)
        import pandas as pd
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_loc_slice_null_index_excluded(self, env1):
        df = _df({"k": ["a", None, "b"], "v": [1, 2, 3]}, env1).set_index("k")
        out = df.loc[:"z"].to_pandas()
        assert sorted(out["v"].tolist()) == [1, 3]  # null label filters False

    def test_min_of_all_nan_is_nan(self, env1):
        df = _df({"x": [np.nan, np.nan]}, env1)
        assert np.isnan(df["x"].min())
        assert np.isnan(df["x"].max())


class TestRound2Advice:
    """Round-2 advisor findings."""

    def test_bounded_cache_refresh_keeps_other_entries(self):
        from cylon_tpu.relational.common import BoundedCache
        c = BoundedCache(maxlen=2)
        c.put("a", 1)
        c.put("b", 2)
        c.put("a", 3)  # refresh at capacity must NOT evict "b"
        assert c.get("b") == 2 and c.get("a") == 3 and len(c) == 2

    def test_empty_agg_spec_raises(self, env1):
        df = _df({"k": [1, 1, 2], "v": [1.0, 2.0, 3.0]}, env1)
        with pytest.raises(InvalidError):
            df.groupby("k").agg([])
        with pytest.raises(InvalidError):
            df.groupby("k").agg({})

    def test_env_serial_monotonic(self, env1):
        assert isinstance(env1.serial, int)
        e2 = ct.CylonEnv()  # LocalConfig: no mesh cost
        assert e2.serial > env1.serial


class TestRound3Advice:
    """Round-3 advisor findings."""

    def test_fused_pushdown_rejects_string_agg(self, env1):
        # sum over a STRING column of a deferred inner join must raise the
        # same InvalidError the materialized path does — never silently
        # aggregate dictionary codes
        l = _df({"k": [1, 1, 2, 2], "s": ["x", "y", "z", "w"]}, env1)
        r = _df({"k": [1, 2, 2, 3], "b": [1, 2, 3, 4]}, env1)
        j = l.merge(r, on="k", how="inner")
        with pytest.raises(InvalidError):
            j.groupby("k").agg({"s": "sum"})

    def test_fused_pushdown_missing_column_keyerror(self, env1):
        # a nonexistent agg column on a deferred join must raise the same
        # CylonKeyError the materialized path does, not a raw ValueError
        l = _df({"k": [1, 2], "a": [1, 2]}, env1)
        r = _df({"k": [1, 2], "b": [3, 4]}, env1)
        j = l.merge(r, on="k", how="inner")
        with pytest.raises(CylonKeyError):
            j.groupby("k").agg({"nonexistent": "sum"})

    def test_fused_pushdown_allows_string_count(self, env1):
        l = _df({"k": [1, 1, 2, 2], "s": ["x", None, "z", "w"]}, env1)
        r = _df({"k": [1, 2, 2, 3], "b": [1, 2, 3, 4]}, env1)
        got = (l.merge(r, on="k", how="inner").groupby("k")
               .agg({"s": "count"}).to_pandas()
               .sort_values("k").reset_index(drop=True))
        exp = (l.to_pandas().merge(r.to_pandas(), on="k")
               .groupby("k", as_index=False).agg(s_count=("s", "count")))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_compiler_crash_matches_helper_death_messages(self):
        # a runtime that surfaces a compiler death does so under the
        # helper subprocess's name and/or the signal — the recovery
        # ladder's final rung (recovery._resumable) classifies each shape
        from cylon_tpu.exec.recovery import is_compiler_crash
        assert is_compiler_crash(
            RuntimeError("tpu_compile_helper exited with status 139"))
        assert is_compiler_crash(
            RuntimeError("Compilation failure: SIGSEGV in subprocess"))
        assert is_compiler_crash(RuntimeError(
            "INTERNAL: tpu_compile_helper terminated by SIGSEGV"))
        assert not is_compiler_crash(RuntimeError("shape mismatch"))

    def test_deferred_materialize_does_not_resort(self, env1, monkeypatch):
        # materializing a deferred join must NOT re-run phase 1 (the sort);
        # the carry rebuilds from the held slim state via scans
        from cylon_tpu.relational import join as join_mod
        calls = []
        orig = join_mod._count_fn

        def counting(*a, **k):
            calls.append(k.get("slim", False)
                         or (len(a) > 6 and a[6]))
            return orig(*a, **k)

        monkeypatch.setattr(join_mod, "_count_fn", counting)
        l = _df({"k": [1, 2, 2, 3], "a": [1, 2, 3, 4]}, env1)
        r = _df({"k": [2, 2, 3, 5], "b": [5, 6, 7, 8]}, env1)
        j = l.merge(r, on="k", how="inner")
        from cylon_tpu.core.table import DeferredTable
        assert isinstance(j.table, DeferredTable)
        got = (j.to_pandas().sort_values(["k", "a", "b"])
               .reset_index(drop=True))
        # exactly ONE phase-1 dispatch, and it was the slim one
        assert calls == [True]
        exp = (l.to_pandas().merge(r.to_pandas(), on="k")
               .sort_values(["k", "a", "b"]).reset_index(drop=True))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)

    def test_fused_first_sight_mispredict_redetects(self, env1, monkeypatch):
        # first-sight fused dispatch at a tiny segment space must detect
        # the mispredict via n_groups and re-dispatch at the true bucket
        from cylon_tpu.relational import groupby as gb_mod
        monkeypatch.setattr(gb_mod, "_FIRST_SEG_CAP", 2)
        n = 64
        ks = np.arange(n, dtype=np.int64) % 16     # 16 groups > 2
        l = _df({"k": ks, "a": np.arange(n, dtype=np.int64)}, env1)
        r = _df({"k": ks, "b": np.arange(n, dtype=np.int64)}, env1)
        got = (l.merge(r, on="k", how="inner").groupby("k")
               .agg({"a": "sum"}).to_pandas()
               .sort_values("k").reset_index(drop=True))
        exp = (l.to_pandas().merge(r.to_pandas(), on="k")
               .groupby("k", as_index=False).agg(a_sum=("a", "sum")))
        pd.testing.assert_frame_equal(got, exp, check_dtype=False)


class TestConcatDecimalScales:
    """Round-4 advisor (high): concat of >=3 decimal tables with mixed
    scales must rescale EVERY block to the common scale — the old pairwise
    promotion left middle blocks at a stale scale under the final (largest)
    dictionary, silently corrupting values."""

    def test_three_way_mixed_scales(self, env1):
        import decimal
        from cylon_tpu.frame import concat
        mk = lambda vals, sc: _df(
            {"m": np.asarray([decimal.Decimal(v).quantize(
                decimal.Decimal(1).scaleb(-sc)) for v in vals], object)},
            env1)
        a = mk(["1.5"], 1)
        b = mk(["2.5"], 1)     # the middle block the pairwise loop missed
        c = mk(["3.1234"], 4)
        out = concat([a, b, c]).to_pandas()
        assert sorted(map(float, out["m"])) == [1.5, 2.5, 3.1234]

    def test_three_way_mixed_scales_dist(self, env4):
        import decimal
        from cylon_tpu.frame import concat
        mk = lambda vals, sc: _df(
            {"m": np.asarray([decimal.Decimal(str(v)).quantize(
                decimal.Decimal(1).scaleb(-sc)) for v in vals], object)},
            env4)
        a = mk([1.5, 7.5, 0.5, 2.5], 1)
        b = mk([2.5, 8.5, 1.5, 3.5], 1)
        c = mk([3.1234, 4.5678, 0.0001, 9.9999], 4)
        out = concat([a, b, c]).to_pandas()
        exp = sorted([1.5, 7.5, 0.5, 2.5, 2.5, 8.5, 1.5, 3.5,
                      3.1234, 4.5678, 0.0001, 9.9999])
        assert sorted(map(float, out["m"])) == exp

    def test_concat_mixed_numeric_middle(self, env1):
        # same stale-middle pattern for plain numerics: [i64, i64, f64]
        from cylon_tpu.frame import concat
        a = _df({"x": np.asarray([1, 2], np.int64)}, env1)
        b = _df({"x": np.asarray([3, 4], np.int64)}, env1)
        c = _df({"x": np.asarray([0.5], np.float64)}, env1)
        out = concat([a, b, c]).to_pandas()
        assert sorted(out["x"].tolist()) == [0.5, 1.0, 2.0, 3.0, 4.0]


class TestDecimalPrecisionVsScale:
    """Round-4 advisor (medium): ingested tight precision can undercut the
    scale ([0.01, 0.02] -> precision 1, scale 2); to_arrow must still emit
    a valid decimal128."""

    def test_to_arrow_small_fractions(self, env1):
        import decimal
        df = _df({"m": np.asarray([decimal.Decimal("0.01"),
                                   decimal.Decimal("0.02")], object)}, env1)
        at = df.table.to_arrow()
        assert at.column("m").to_pylist() == [decimal.Decimal("0.01"),
                                              decimal.Decimal("0.02")]

    def test_parquet_roundtrip_small_fractions(self, env1, tmp_path):
        import decimal
        df = _df({"m": np.asarray([decimal.Decimal("0.01"),
                                   decimal.Decimal("0.02")], object)}, env1)
        p = str(tmp_path / "d.parquet")
        df.to_parquet(p)
        back = pd.read_parquet(p)
        assert sorted(map(float, back["m"])) == [0.01, 0.02]


class TestLocalSortGroupedBy:
    """Round-4 advisor (low): a per-shard sort alone must NOT claim
    grouped_by (it gates groupby's no-shuffle fast path, which also needs
    cross-shard co-location)."""

    def test_local_sort_does_not_set_grouped_by(self, env4, rng):
        from cylon_tpu.relational.sort import local_sort_table
        t = ct.Table.from_pandas(
            pd.DataFrame({"k": rng.integers(0, 4, 64),
                          "x": rng.random(64)}), env4)
        out = local_sort_table(t, ["k"])
        assert out.grouped_by is None

    def test_groupby_after_local_sort_still_correct(self, env4, rng):
        # the bug scenario: non-colocated but per-shard-sorted table must
        # still take the shuffling groupby path and produce global groups
        from cylon_tpu.relational.sort import local_sort_table
        from cylon_tpu.relational import groupby_aggregate
        pdf = pd.DataFrame({"k": rng.integers(0, 4, 64).astype(np.int64),
                            "x": rng.random(64)})
        t = ct.Table.from_pandas(pdf, env4)
        out = groupby_aggregate(local_sort_table(t, ["k"]), ["k"],
                                [("x", "sum")]).to_pandas()
        exp = pdf.groupby("k", as_index=False).agg(x_sum=("x", "sum"))
        got = out.sort_values("k").reset_index(drop=True)
        assert len(got) == len(exp)
        np.testing.assert_allclose(got["x_sum"], exp["x_sum"])


class TestMixedDecimalIngest:
    """Round-4 advisor (low): a column mixing Decimal with other types must
    raise the framework's CylonTypeError, not a raw decimal error."""

    def test_decimal_then_str(self, env1):
        import decimal
        from cylon_tpu.status import CylonTypeError
        with pytest.raises(CylonTypeError):
            _df({"m": np.asarray([decimal.Decimal("1.5"), "oops"], object)},
                env1)

    def test_decimal_then_list(self, env1):
        import decimal
        from cylon_tpu.status import CylonTypeError
        with pytest.raises(CylonTypeError):
            _df({"m": np.asarray([decimal.Decimal("1.5"), [1, 2]], object)},
                env1)

    def test_nonfinite_decimal(self, env1):
        import decimal
        from cylon_tpu.status import CylonTypeError
        # Decimal('NaN') is a null under pd.isna -> ingests as None
        df = _df({"m": np.asarray([decimal.Decimal("1.5"),
                                   decimal.Decimal("NaN")], object)}, env1)
        assert df.to_pandas()["m"].tolist() == [decimal.Decimal("1.5"), None]
        # Decimal('Infinity') is NOT null: framework error, not raw TypeError
        with pytest.raises(CylonTypeError):
            _df({"m": np.asarray([decimal.Decimal("1.5"),
                                  decimal.Decimal("Infinity")], object)},
                env1)


class TestMultiJoinKeyTracking:
    """Round-5 advisor: join_tables_multi's accumulated key names must
    survive suffix renaming.  The seed's fallback silently switched to the
    RIGHT table's key names when a collision renamed the left keys —
    null-valued for unmatched rows in a `how='left'` chain, fabricating
    null-key matches against any null-keyed row downstream."""

    def _frames(self):
        # t2 carries a NON-key payload column named "k" (collides with
        # t1's key -> k_x/k_y suffixes); t3 holds a null-keyed row.  The
        # buggy right-key fallback joins step 2 on "j" — null for the
        # unmatched "9" row — and nulls compare equal in this engine's
        # joins (reference comparator semantics), so it FABRICATES the
        # z=999 match (verified live against the seed logic); the fix
        # joins on the renamed left key "k_x" instead.
        t1 = pd.DataFrame({"k": ["1", "2", "3", "9"],
                           "x": [10, 20, 30, 90]})
        t2 = pd.DataFrame({"j": ["1", "2", "3"],
                           "k": ["a", "b", "c"],
                           "y": [7, 8, 9]})
        t3 = pd.DataFrame({"m": ["1", None], "z": [111, 999]})
        return t1, t2, t3

    def _expected(self, t1, t2, t3):
        p12 = t1.merge(t2, left_on="k", right_on="j", how="left",
                       suffixes=("_x", "_y"))
        return p12.merge(t3, left_on="k_x", right_on="m", how="left")

    @pytest.mark.parametrize("world", ["env1", "env4"])
    def test_colliding_left_chain_keeps_left_keys(self, world, request):
        from cylon_tpu.relational import join_tables_multi
        env = request.getfixturevalue(world)
        t1, t2, t3 = self._frames()
        out = join_tables_multi(
            [ct.Table.from_pandas(t1, env), ct.Table.from_pandas(t2, env),
             ct.Table.from_pandas(t3, env)],
            ons=["k", "j", "m"], how="left")
        exp = self._expected(t1, t2, t3)
        got = out.to_pandas()
        assert sorted(got.columns) == sorted(exp.columns)
        got = got.sort_values(["k_x", "x"]).reset_index(drop=True)
        exp = exp.sort_values(["k_x", "x"]).reset_index(drop=True)
        # the unmatched-left row must NOT pick up t3's null-keyed payload
        row9 = got[got["k_x"] == "9"]
        assert row9["z"].isna().all(), row9
        for c in exp.columns:
            assert (got[c].fillna("<null>").tolist()
                    == exp[c].fillna("<null>").tolist()), c
