"""Scale-exact DECIMAL arithmetic, its bounds, its typed ingest and its
aggregates (PR 41; the rule is ``Series._decimal_arith``'s docstring and
``docs/decimal.md``): ``* + -`` against Python's ``decimal.Decimal``
exactly, scales and precisions as the rule says, result bounds equal to
interval arithmetic, no silent int64 overflow, float operands refused;
``sum`` / ``min`` / ``max`` of a DECIMAL keep type and scale on one device
and on four; the typed ingest equals the object path on the same values;
every elementwise op is one ``series__expr_fn`` program under stage
``expr``, counted by kind."""

from __future__ import annotations

import decimal
from decimal import Decimal as D

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu import LogicalType
from cylon_tpu.core.column import Column, DecimalScale
from cylon_tpu.obs import metrics
from cylon_tpu.series import _interval
from cylon_tpu.status import CylonTypeError, InvalidError

PRICE = [90000, 10500000, 1234, 777777, 5000000, 250000, 90001]      # cents
DISC = [0, 10, 5, 3, 7, 10, 1]                                 # hundredths
THOU = [1500, -250, 0, 999, 12345, 7, -1]                      # scale 3
QTY = [1, 50, 7, 13, 2, 49, 25]


def _frame(env):
    return ct.DataFrame({
        "p": Column.from_scaled_ints(np.array(PRICE), 2, 15),
        "d": Column.from_scaled_ints(np.array(DISC), 2, 15),
        "t": Column.from_scaled_ints(np.array(THOU), 3, 9),
        "q": np.array(QTY, np.int64),
        "f": np.array(QTY, np.float64)}, env=env)


def _dec(ints, scale):
    return [D(int(v)).scaleb(-scale) for v in ints]


P, DI, T = _dec(PRICE, 2), _dec(DISC, 2), _dec(THOU, 3)

#: name -> (expression on the frame, the same on Python Decimals, scale)
CASES = {
    "col_times_col": (lambda f: f["p"] * f["d"],
                      [a * b for a, b in zip(P, DI)], 4),
    "revenue": (lambda f: f["p"] * (1 - f["d"]),
                [a * (1 - b) for a, b in zip(P, DI)], 4),
    "col_plus_col": (lambda f: f["p"] + f["d"],
                     [a + b for a, b in zip(P, DI)], 2),
    "col_minus_col": (lambda f: f["d"] - f["p"],
                      [b - a for a, b in zip(P, DI)], 2),
    "mixed_scales_add": (lambda f: f["p"] + f["t"],
                         [a + b for a, b in zip(P, T)], 3),
    "mixed_scales_sub": (lambda f: f["t"] - f["d"],
                         [a - b for a, b in zip(T, DI)], 3),
    "times_int_literal": (lambda f: f["p"] * 3, [a * 3 for a in P], 2),
    "int_literal_times": (lambda f: 3 * f["p"], [a * 3 for a in P], 2),
    "times_decimal_literal": (lambda f: f["p"] * D("1.075"),
                              [a * D("1.075") for a in P], 5),
    "plus_decimal_literal": (lambda f: f["d"] + D("0.5"),
                             [b + D("0.5") for b in DI], 2),
    "plus_finer_literal": (lambda f: f["d"] + D("0.125"),
                           [b + D("0.125") for b in DI], 3),
    "minus_int_literal": (lambda f: f["p"] - 900, [a - 900 for a in P], 2),
    "times_int_column": (lambda f: f["p"] * f["q"],
                         [a * q for a, q in zip(P, QTY)], 2),
    "int_column_times": (lambda f: f["q"] * f["t"],
                         [a * q for a, q in zip(T, QTY)], 3),
    "plus_int_column": (lambda f: f["t"] + f["q"],
                        [a + q for a, q in zip(T, QTY)], 3),
    "negated": (lambda f: -f["t"], [-a for a in T], 3),
    "absolute": (lambda f: abs(f["t"]), [abs(a) for a in T], 3),
    "chained": (lambda f: f["p"] * (1 - f["d"]) * (1 + f["d"]),
                [a * (1 - b) * (1 + b) for a, b in zip(P, DI)], 6),
}


@pytest.mark.parametrize("world", [1, 4])
@pytest.mark.parametrize("case", sorted(CASES))
def test_arithmetic_equals_python_decimal(case, world, env1, env4):
    expr, want, scale = CASES[case]
    out = expr(_frame(env1 if world == 1 else env4))
    col = out.column
    assert col.type == LogicalType.DECIMAL
    assert col.dictionary.scale == scale
    got = list(out.to_numpy())
    assert got == want, case                    # exact Decimal equality
    assert all(-g.as_tuple().exponent == scale for g in got)


@pytest.mark.parametrize("expr,scale,precision", [
    (lambda f: f["t"] * f["t"], 6, 9),    # p1 + p2 = 18, bounds say 9
    (lambda f: f["t"] + f["t"], 3, 5),    # max(9, 9) + 1 = 10, bounds say 5
    (lambda f: f["t"] * 7, 3, 5),         # 9 + 1 = 10, bounds say 5
    (lambda f: -f["t"], 3, 5),            # keeps 9; bounds say 5
])
def test_scale_and_precision_follow_the_rule(expr, scale, precision, env1):
    sc = expr(_frame(env1)).column.dictionary
    assert (sc.scale, sc.precision) == (scale, precision)


def test_precision_is_the_rules_where_there_are_no_bounds(env1):
    a = ct.DataFrame({"x": Column(np.array(THOU, np.int64),
                                  LogicalType.DECIMAL, None,
                                  DecimalScale(6, 3))}, env=env1)["x"]
    assert a.column.bounds is None
    assert (a * a).column.dictionary == DecimalScale(12, 6)
    assert (a + a).column.dictionary == DecimalScale(7, 3)
    assert (a * 25).column.dictionary == DecimalScale(8, 3)
    assert (a * a).column.bounds is None


@pytest.mark.parametrize("op,a,b,want", [
    ("add", (1, 5), (-2, 3), (-1, 8)),
    ("sub", (1, 5), (-2, 3), (-2, 7)),
    ("rsub", (1, 5), (100, 100), (95, 99)),
    ("mul", (-3, 5), (-2, 4), (-12, 20)),
    ("mul", (2, 5), (90, 100), (180, 500)),
    ("neg", (-3, 5), None, (-5, 3)),
    ("abs", (-3, 5), None, (0, 5)),
    ("abs", (-9, -4), None, (4, 9)),
    ("mul", None, (1, 2), None),
    ("add", (1, 2), None, None),
    ("floordiv", (1, 2), (1, 2), None),
])
def test_interval_arithmetic(op, a, b, want):
    assert _interval(op, a, b) == want


def test_result_bounds_are_the_interval_of_the_operands_bounds(env1):
    f = _frame(env1)
    pb, db = f["p"].column.bounds, f["d"].column.bounds
    one_minus = (100 - db[1], 100 - db[0])
    rev = f["p"] * (1 - f["d"])
    assert (1 - f["d"]).column.bounds == one_minus
    assert rev.column.bounds == _interval("mul", pb, one_minus)
    data = np.asarray(rev.column.data)[:len(PRICE)]
    assert rev.column.bounds[0] <= data.min() \
        and data.max() <= rev.column.bounds[1]
    # the decimal's scaled 1.05e9 fits int32: the sum scans as val32
    assert rev.column.bounds[1] < 1 << 31
    tb = f["t"].column.bounds
    assert (f["p"] + f["t"]).column.bounds == (
        pb[0] * 10 + tb[0], pb[1] * 10 + tb[1])


def test_int64_arithmetic_carries_bounds_too(env1):
    q = _frame(env1)["q"]
    lo, hi = q.column.bounds
    assert (q + q).column.bounds == (2 * lo, 2 * hi)
    assert (q * 3 - 1).column.bounds == (3 * lo - 1, 3 * hi - 1)
    assert (q - q).column.bounds == (lo - hi, hi - lo)
    assert (-q).column.bounds == (-hi, -lo)
    assert (q * q).column.bounds == _interval("mul", (lo, hi), (lo, hi))
    assert (q // 2).column.bounds is None and (q % 2).column.bounds is None
    assert (q * 1.5).column.bounds is None          # a float result
    big = q * (1 << 62)                             # past int64: unknown
    assert big.column.bounds is None


def test_no_silent_overflow_without_bounds(env1):
    """decimal(15,2) x decimal(15,2) is decimal(30,4): with no bounds that
    is refused, with bounds that prove 10 digits it is decimal(10,4)."""
    wide = DecimalScale(15, 2)
    f = ct.DataFrame({
        "a": Column(np.array(PRICE, np.int64), LogicalType.DECIMAL, None,
                    wide),
        "b": Column(np.array(DISC, np.int64), LogicalType.DECIMAL, None,
                    wide)}, env=env1)
    with pytest.raises(CylonTypeError, match="precision 30 > 18.*float64"):
        f["a"] * f["b"]
    g = _frame(env1)
    assert (g["p"] * (1 - g["d"])).column.dictionary == DecimalScale(10, 4)


def test_bounds_that_pass_18_digits_raise(env1):
    big = ct.DataFrame({"x": Column.from_scaled_ints(
        np.array([10 ** 12, 3 * 10 ** 12]), 2, 15)}, env=env1)["x"]
    with pytest.raises(CylonTypeError, match="do not rule the overflow"):
        big * big
    with pytest.raises(CylonTypeError, match="> 18"):
        big * 10 ** 7
    assert (big * 10 ** 5).column.dictionary.precision == 18


@pytest.mark.parametrize("bad", [
    lambda f: f["p"] * 1.5, lambda f: 1.0 - f["d"], lambda f: f["p"] + 0.1,
    lambda f: f["p"] * f["f"], lambda f: f["f"] * f["p"],
    lambda f: f["p"] + f["f"], lambda f: f["p"] * True,
    lambda f: f["p"] * D("NaN")])
def test_float_operands_raise(bad, env1):
    with pytest.raises(CylonTypeError):
        bad(_frame(env1))


@pytest.mark.parametrize("bad", [
    lambda f: f["p"] / 2, lambda f: f["p"] / f["d"], lambda f: 1 / f["p"],
    lambda f: f["p"] // 2, lambda f: f["p"] % 2, lambda f: f["p"] ** 2])
def test_division_is_not_defined_and_says_so(bad, env1):
    with pytest.raises(CylonTypeError, match="not supported.*float64"):
        bad(_frame(env1))


def test_layouts_must_match(env1):
    longer = ct.DataFrame({"d": Column.from_scaled_ints(np.arange(40), 2)},
                          env=env1)
    with pytest.raises(InvalidError):
        _frame(env1)["p"] * longer["d"]


def test_nulls_propagate(env1):
    valid = np.array([True, False, True, True, True, False, True])
    f = ct.DataFrame({
        "p": Column.from_scaled_ints(np.array(PRICE), 2, 15, valid),
        "d": Column.from_scaled_ints(np.array(DISC), 2, 15)}, env=env1)
    got = (f["p"] * (1 - f["d"])).to_numpy()
    assert [g is None for g in got] == list(~valid)
    assert got[0] == P[0] * (1 - DI[0])


# ---- typed ingest ----------------------------------------------------------

def test_typed_ingest_equals_the_object_path(env4):
    rng = np.random.default_rng(7)
    cents = rng.integers(-99999, 10500001, 5000)
    objects = np.asarray(_dec(cents, 2), dtype=object)
    typed = Column.from_scaled_ints(cents, 2)
    walked = Column.from_numpy(objects)
    assert typed.type == walked.type == LogicalType.DECIMAL
    np.testing.assert_array_equal(typed.data, walked.data)
    assert typed.dictionary == walked.dictionary
    assert typed.bounds == walked.bounds == (int(cents.min()),
                                             int(cents.max()))
    a = ct.Table.from_pydict({"m": typed}, env4).to_pandas()
    b = ct.Table.from_pydict({"m": objects}, env4).to_pandas()
    assert list(a["m"]) == list(b["m"]) == list(objects)


def test_typed_ingest_declares_its_precision_and_holds_it():
    c = Column.from_scaled_ints(np.array([1, 99]), 2, 15)
    assert c.dictionary == DecimalScale(15, 2) and c.bounds == (1, 99)
    with pytest.raises(CylonTypeError, match="cannot hold"):
        Column.from_scaled_ints(np.array([1000]), 2, 3)
    masked = Column.from_scaled_ints(np.array([5, 10 ** 17, 7]), 2, 4,
                                     np.array([True, False, True]))
    assert masked.bounds == (0, 7)          # a null slot holds 0


@pytest.mark.parametrize("world", [1, 4])
def test_dictionary_ingest_equals_the_string_path(world, env1, env4):
    env = env1 if world == 1 else env4
    words = np.asarray(["TRUCK", "AIR", "MAIL", "SHIP", "RAIL"])   # unsorted
    codes = np.random.default_rng(3).integers(0, 5, 999)
    typed = Column.from_dictionary(codes, words)
    walked = Column.from_numpy(words[codes])
    np.testing.assert_array_equal(typed.data, walked.data)
    assert typed.data.dtype == np.int32
    assert list(typed.dictionary) == list(walked.dictionary) == sorted(words)
    cat = pd.DataFrame({"m": pd.Categorical.from_codes(codes, list(words)),
                        "k": np.arange(999)})
    t = ct.Table.from_pandas(cat, env)
    assert t.column("m").type == LogicalType.STRING
    assert list(t.to_pandas()["m"]) == list(words[codes])
    df = ct.DataFrame.from_table(t)
    assert len(df[df["m"] == "MAIL"]) == int((codes == 2).sum())


def test_categorical_nulls_are_nulls(env1):
    s = pd.DataFrame({"m": pd.Categorical(["b", None, "a", "b"])})
    got = ct.Table.from_pandas(s, env1).to_pandas()["m"]
    assert list(got[[0, 2, 3]]) == ["b", "a", "b"] and pd.isna(got[1])


# ---- aggregates ------------------------------------------------------------

@pytest.mark.parametrize("world", [1, 4])
def test_sum_min_max_of_a_decimal_keep_type_and_scale(world, env1, env4):
    env = env1 if world == 1 else env4
    rng = np.random.default_rng(11)
    n = 4000
    k = rng.integers(0, 37, n)
    cents = rng.integers(-5000, 10500001, n)
    f = ct.DataFrame({"k": k, "m": Column.from_scaled_ints(cents, 2, 15)},
                     env=env)
    f["r"] = f["m"] * (1 - f["m"] * 0)          # scale 4, the same values
    g = f.groupby("k").agg([("m", "sum"), ("m", "min"), ("m", "max"),
                            ("r", "sum")])
    t = g.table
    for name, prec in (("m_sum", 18), ("m_min", 15), ("m_max", 15)):
        c = t.column(name)
        assert c.type == LogicalType.DECIMAL
        assert c.dictionary == DecimalScale(prec, 2), name
    assert t.column("r_sum").dictionary.scale == 4
    got = g.to_pandas().sort_values("k").reset_index(drop=True)
    want = pd.DataFrame({"k": k, "m": cents}).groupby("k")["m"].agg(
        ["sum", "min", "max"]).reset_index()
    for name, col in (("m_sum", "sum"), ("m_min", "min"), ("m_max", "max")):
        assert list(got[name]) == _dec(want[col], 2), name
        assert all(isinstance(v, decimal.Decimal) for v in got[name])
    assert list(got["r_sum"]) == [v * 1 for v in _dec(want["sum"] * 100, 4)]


@pytest.mark.parametrize("op", ["mean", "var", "std", "median"])
def test_inexact_aggregates_of_a_decimal_raise(op, env1):
    f = _frame(env1)
    with pytest.raises(InvalidError, match="not scale-exact.*float64"):
        f.groupby("q").agg([("p", op)])


def test_counts_of_a_decimal_are_integers(env1):
    g = _frame(env1).groupby("q").agg([("p", "count"), ("p", "nunique")])
    assert g.table.column("p_count").type == LogicalType.INT64
    assert g.table.column("p_nunique").type == LogicalType.INT64


def test_fused_join_groupby_keeps_the_scale(env1):
    """The deferred join -> groupby pushdown builds its result through the
    same ``_result_types``."""
    from cylon_tpu.relational import groupby_aggregate, join_tables
    lt = ct.Table.from_pydict({"k": np.arange(64) % 8, "m":
                               Column.from_scaled_ints(np.arange(64), 2)},
                              env1)
    rt = ct.Table.from_pydict({"k": np.arange(8), "b": np.arange(8)}, env1)
    g = groupby_aggregate(join_tables(lt, rt, "k", "k"), "k",
                          [("m", "sum")])
    c = g.column("m_sum")
    assert c.type == LogicalType.DECIMAL and c.dictionary.scale == 2
    want = pd.Series(np.arange(64)).groupby(np.arange(64) % 8).sum()
    got = g.to_pandas().sort_values("k")["m_sum"]
    assert list(got) == _dec(want, 2)


# ---- one builder, one stage, one counter -----------------------------------

def _dispatches():
    return {k.split('"')[1]: v for k, v in metrics.snapshot().items()
            if k.startswith("series_expr_dispatches")}


def test_every_op_is_counted_by_kind(env1):
    f = _frame(env1)
    before = _dispatches()
    assert set(before) == {"decimal", "int", "float", "compare", "mask"}
    f["p"] * (1 - f["d"])                        # 2 decimal
    f["q"] * 2 + 1                               # 2 int
    f["q"] * 0.5; f["q"] / 2; f["f"] + f["q"]    # 3 float  # noqa: E702
    m = (f["p"] > D("1000")) & (f["q"] < 40) | ~(f["t"] == f["t"])
    after = _dispatches()
    delta = {k: after[k] - before[k] for k in after}
    assert delta == {"decimal": 2, "int": 2, "float": 3, "compare": 3,
                     "mask": 3}
    assert m.dtype == LogicalType.BOOL


def test_expressions_launch_the_one_builder(env1):
    """``cylon.launch.series__expr_fn`` on the host, HLO module
    ``jit_series__expr_fn`` with the ops under ``cylon.expr`` on the
    device."""
    from cylon_tpu import series
    from cylon_tpu.obs import trace
    f = _frame(env1)
    rec = trace.arm(capacity=64)
    try:
        f["p"] * (1 - f["d"])
        names = [e[3] for e in rec.events()]
    finally:
        trace.disarm()
    assert names.count("launch.series__expr_fn") == 2
    prog = series._expr_fn(env1.mesh, "mul", ("int64", "int64"), 1, 1)
    text = prog.__wrapped__.lower(f["p"].column.data,
                                  f["d"].column.data).as_text(
        debug_info=True)
    assert "jit_series__expr_fn" in text and "cylon.expr" in text


@pytest.mark.parametrize("word,present", [("MAIL", True), ("BOAT", False),
                                          ("AAA", False), ("ZZZ", False)])
@pytest.mark.parametrize("op", ["eq", "ne", "lt", "le", "gt", "ge"])
def test_string_compares_against_a_scalar(op, word, present, env1):
    """Codes are order-isomorphic to the sorted words; an absent word
    compares as its insertion point - 1/2, in doubled integers."""
    import operator
    words = np.asarray(["TRUCK", "AIR", "MAIL", "SHIP", "RAIL", "MAIL"])
    f = ct.DataFrame({"m": words}, env=env1)
    got = getattr(operator, op)(f["m"], word).to_numpy()
    want = getattr(operator, op)(words.astype(object), word)
    np.testing.assert_array_equal(got, want.astype(bool))
    assert present == (word in words)


# ---- the filter ------------------------------------------------------------

def test_filter_keeps_bounds_and_narrow_sums(env1):
    f = _frame(env1)
    kept = f[f["q"] > 5]
    pb = f["p"].column.bounds
    assert kept["p"].column.bounds == (min(pb[0], 0), pb[1])
    assert kept["p"].column.type == LogicalType.DECIMAL
    rev = kept["p"] * (1 - kept["d"])
    assert rev.column.bounds is not None and rev.column.bounds[1] < 1 << 31
    before = metrics.snapshot()['grouped_sum_scans{form="pair64"}']
    kept["r"] = rev
    kept.groupby("q")[["r"]].sum()
    assert metrics.snapshot()['grouped_sum_scans{form="pair64"}'] == before
