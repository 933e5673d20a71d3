"""Decimal (scaled-int64) and list-passthrough columns (round-4, VERDICT
item 7).  Reference: the C++ comparators span every Arrow type including
decimal128 and list payloads (arrow_comparator.cpp; join_test.cpp:124 joins
list<float32> columns locally).  Here decimal128(p<=18) is EXACT via
unscaled int64 (TPC-H money semantics) and variable-length lists ride
host-side as passthrough payloads (carried through joins by code gathers,
never usable as keys)."""

import decimal
from decimal import Decimal

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.status import CylonTypeError, InvalidError


def _dec(vals, scale=2):
    q = Decimal(1).scaleb(-scale)
    return np.asarray([Decimal(str(v)).quantize(q) for v in vals],
                      dtype=object)


class TestDecimal:
    def test_pandas_roundtrip_exact(self, env4):
        df = pd.DataFrame({"m": _dec([1.25, -3.10, 0.07, 99999.99]),
                           "k": np.arange(4, dtype=np.int64)})
        t = ct.Table.from_pandas(df, env4)
        back = t.to_pandas()
        assert list(back["m"]) == list(df["m"])  # exact Decimal equality

    def test_arrow_roundtrip(self, env4):
        import pyarrow as pa
        arr = pa.array([Decimal("12.34"), None, Decimal("-0.01")],
                       type=pa.decimal128(10, 2))
        at = pa.table({"m": arr, "k": pa.array([1, 2, 3])})
        t = ct.Table.from_arrow(at, env4)
        out = t.to_arrow()
        assert out.column("m").type == pa.decimal128(10, 2)
        assert out.column("m").to_pylist() == arr.to_pylist()

    def test_join_on_decimal_keys(self, env4, rng):
        lv = rng.integers(0, 40, 300) / 4          # .0 .25 .5 .75 grid
        rv = rng.integers(0, 40, 200) / 4
        ldf = pd.DataFrame({"m": _dec(lv), "a": rng.random(300)})
        rdf = pd.DataFrame({"m": _dec(rv), "b": rng.random(200)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        from cylon_tpu.relational import join_tables
        j = join_tables(lt, rt, "m", "m")
        exp = ldf.merge(rdf, on="m")
        assert j.row_count == len(exp)
        got = j.to_pandas()
        assert sorted(map(float, got["m"])) == sorted(map(float, exp["m"]))

    def test_join_mixed_scales_rescale(self, env4):
        # scale-1 vs scale-2 decimals: 2.5 must match 2.50
        ldf = pd.DataFrame({"m": _dec([2.5, 3.1, 4.0], scale=1),
                            "a": [1, 2, 3]})
        rdf = pd.DataFrame({"m": _dec([2.50, 4.00, 9.99], scale=2),
                            "b": [10, 20, 30]})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        from cylon_tpu.relational import join_tables
        j = join_tables(lt, rt, "m", "m").to_pandas()
        assert sorted(j["b"].tolist()) == [10, 20]

    def test_filter_decimal_literal(self, env4):
        df = pd.DataFrame({"m": _dec([0.05, 0.06, 0.07, 0.08]),
                           "v": [1, 2, 3, 4]})
        d = ct.DataFrame(df, env=env4)
        got = d[d["m"] >= Decimal("0.06")].to_pandas()
        assert got["v"].tolist() == [2, 3, 4]
        got2 = d[d["m"] == Decimal("0.07")].to_pandas()
        assert got2["v"].tolist() == [3]
        with pytest.raises(CylonTypeError):
            d["m"] >= Decimal("0.065")   # finer than the column scale
        with pytest.raises(CylonTypeError):
            d["m"] + 1.0                 # a float operand is lossy
        assert (d["m"] + 1).to_numpy().tolist() == [
            Decimal("1.05"), Decimal("1.06"), Decimal("1.07"),
            Decimal("1.08")]             # scale-exact since PR 41

    def test_groupby_on_decimal_keys(self, env4, rng):
        df = pd.DataFrame({"m": _dec(rng.integers(0, 8, 500) / 4),
                           "v": rng.integers(0, 50, 500)})
        d = ct.DataFrame(df, env=env4)
        g = d.groupby("m").agg([("v", "sum")]).to_pandas()
        eg = (df.assign(m=df.m.map(float)).groupby("m", as_index=False)
              .agg(v_sum=("v", "sum")))
        got = sorted(zip(map(float, g["m"]), g["v_sum"]))
        exp = sorted(zip(eg["m"], eg["v_sum"]))
        assert got == exp

    def test_sort_by_decimal(self, env4):
        df = pd.DataFrame({"m": _dec([3.5, -1.25, 0.0, 2.75])})
        d = ct.DataFrame(df, env=env4)
        out = d.sort_values("m").to_pandas()
        assert list(map(float, out["m"])) == [-1.25, 0.0, 2.75, 3.5]


class TestListPassthrough:
    def _frames(self, rng, n=200):
        ldf = pd.DataFrame({"k": rng.integers(0, 30, n).astype(np.int64),
                            "payload": [[int(i), int(i) * 2]
                                        for i in range(n)]})
        rdf = pd.DataFrame({"k": np.arange(30, dtype=np.int64),
                            "b": rng.random(30)})
        return ldf, rdf

    def test_roundtrip(self, env4, rng):
        ldf, _ = self._frames(rng)
        t = ct.Table.from_pandas(ldf, env4)
        back = t.to_pandas()
        assert list(back["payload"]) == list(ldf["payload"])

    def test_survives_join_as_payload(self, env4, rng):
        ldf, rdf = self._frames(rng)
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        from cylon_tpu.relational import join_tables
        j = join_tables(lt, rt, "k", "k").to_pandas()
        exp = ldf.merge(rdf, on="k")
        assert len(j) == len(exp)
        # each row's payload must still be the payload ingested with its k
        payload_by_first = {p[0]: k for k, p in
                            zip(ldf["k"], ldf["payload"])}
        for k, p in zip(j["k"], j["payload"]):
            assert payload_by_first[p[0]] == k

    def test_survives_filter_and_concat(self, env4, rng):
        ldf, _ = self._frames(rng)
        d = ct.DataFrame(ldf, env=env4)
        f = d[d["k"] >= 15]
        exp = ldf[ldf.k >= 15]
        got = f.to_pandas()
        assert list(got["payload"]) == list(exp["payload"])
        from cylon_tpu.relational import concat_tables
        both = concat_tables([f._table, f._table]).to_pandas()
        assert len(both) == 2 * len(exp)

    def test_arrow_list_ingest(self, env4):
        import pyarrow as pa
        at = pa.table({"k": pa.array([1, 2, 3]),
                       "ls": pa.array([[1.0, 2.0], [], [3.0]],
                                      type=pa.list_(pa.float64()))})
        t = ct.Table.from_arrow(at, env4)
        back = t.to_pandas()
        assert list(back["ls"]) == [[1.0, 2.0], [], [3.0]]

    def test_list_keys_raise(self, env4, rng):
        ldf, _ = self._frames(rng)
        lt = ct.Table.from_pandas(ldf, env4)
        from cylon_tpu.relational import (groupby_aggregate, join_tables,
                                          set_operation, sort_table,
                                          unique_table)
        with pytest.raises(CylonTypeError):
            join_tables(lt, lt, "payload", "payload")
        with pytest.raises(InvalidError):
            groupby_aggregate(lt, "payload", [("k", "sum")])
        with pytest.raises(InvalidError):
            sort_table(lt, "payload")
        with pytest.raises(InvalidError):
            unique_table(lt)
        with pytest.raises(InvalidError):
            set_operation(lt, lt, "union")
        with pytest.raises(CylonTypeError):
            _ = ct.DataFrame(_table=lt)["payload"] == [1, 2]


class TestReviewRegressions:
    def test_decimal256_takes_float_fallback(self, env4):
        """decimal256 storage is 4 limbs — the int64 buffer view must NOT
        apply (it silently corrupted values); it falls back to float64."""
        import pyarrow as pa
        arr = pa.array([Decimal("1.5"), Decimal("2.5"), Decimal("3.5")],
                       type=pa.decimal256(10, 1))
        t = ct.Table.from_arrow(pa.table({"m": arr}), env4)
        from cylon_tpu.core.dtypes import LogicalType
        assert t.column("m").type == LogicalType.FLOAT64
        assert t.to_pandas()["m"].tolist() == [1.5, 2.5, 3.5]

    def test_rescale_grows_precision(self, env4):
        """Joining (5,0) with (5,3) rescales values by 10^3: the declared
        precision must grow or export crashes (ArrowInvalid)."""
        import pyarrow as pa
        a = pa.table({"m": pa.array([Decimal("99999")],
                                    type=pa.decimal128(5, 0)),
                      "x": pa.array([1])})
        b = pa.table({"m": pa.array([Decimal("99999.000")],
                                    type=pa.decimal128(8, 3)),
                      "y": pa.array([2])})
        ta, tb = ct.Table.from_arrow(a, env4), ct.Table.from_arrow(b, env4)
        from cylon_tpu.relational import join_tables
        j = join_tables(ta, tb, "m", "m")
        assert j.row_count == 1
        out = j.to_arrow()     # must not raise
        assert out.column("m").to_pylist()[0] == Decimal("99999.000")

    def test_leading_pd_na_decimal_ingest(self, env4):
        """A leading pd.NA must not defeat the decimal type probe."""
        df = pd.DataFrame({"m": pd.Series([pd.NA, Decimal("1.5"),
                                           Decimal("2.5")], dtype=object)})
        t = ct.Table.from_pandas(df, env4)
        from cylon_tpu.core.dtypes import LogicalType
        assert t.column("m").type == LogicalType.DECIMAL
        back = t.to_pandas()["m"]
        assert back[0] is None or pd.isna(back[0])
        assert list(back[1:]) == [Decimal("1.5"), Decimal("2.5")]

    def test_multi_loc_missing_after_concat_padding(self, env4):
        """Padding rows (unspecified contents post-concat) must not fake
        a presence hit in multi-index list-label loc."""
        from cylon_tpu.relational import concat_tables
        d1 = ct.DataFrame(pd.DataFrame({"a": [1, 2, 3], "b": [1, 1, 1],
                                        "v": [1., 2., 3.]}), env=env4)
        d2 = ct.DataFrame(pd.DataFrame({"a": [4, 5, 6], "b": [2, 2, 2],
                                        "v": [4., 5., 6.]}), env=env4)
        both = ct.DataFrame(_table=concat_tables([d1._table, d2._table]))
        m = both.set_index(["a", "b"])
        from cylon_tpu.status import CylonKeyError
        with pytest.raises(CylonKeyError):
            m.loc[[(0, 0)]]
