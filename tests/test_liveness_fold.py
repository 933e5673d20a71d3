"""Row liveness rides in the leading key operand (ISSUE 50): where that
operand has room, padding sorts last by two sentinel values INSIDE it and
no liveness operand is built - one operand fewer through every key sort of
a padded table, the same order, the same answers.

* the rule's truth table at its edges (``ops/pack.fold_room`` under
  ``relational/common.fold_liveness``);
* ``key_operands`` / ``key_operand_slots`` / ``sort_operand_nbytes`` in
  lockstep, fold on and off;
* every operator that ranks padded tables, folded against unfolded row for
  row (and against pandas), worlds 1 and 4, with a live key AT the edge the
  rule admits;
* the count programs of the benchmark cells' shapes hold a sort of 3 (join,
  set operations), 2 (``unique``) and 3 (``groupby__raw_fn``) operands, none
  of them a liveness flag; the registry says ``folded`` / ``operand``."""

import re

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

import cylon_tpu as ct
from cylon_tpu.core.column import Column
from cylon_tpu.core.dtypes import LogicalType
from cylon_tpu.exec import compiler
from cylon_tpu.obs import metrics
from cylon_tpu.ops import pack
from cylon_tpu.relational import (groupby_aggregate, join_tables,
                                  set_operation, sort_table, unique_table)
from cylon_tpu.relational import common
from cylon_tpu.relational.common import PAD_L, PAD_R, fold_liveness

from test_sort_operand_budget import _spy
from utils import assert_frames_equal

I32 = np.iinfo(np.int32)
U32 = np.iinfo(np.uint32)


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

def _col(dtype, bounds=None, nullable=False, n=4):
    data = np.zeros(n, dtype)
    lt = {"float32": LogicalType.FLOAT32, "float64": LogicalType.FLOAT64,
          "bool": LogicalType.BOOL}.get(np.dtype(dtype).name)
    lt = lt or LogicalType(np.dtype(dtype).name)
    return Column(data, lt, np.ones(n, bool) if nullable else None,
                  bounds=bounds)


def _strings(hashed=False):
    c = Column.from_numpy(np.asarray(["ant", "bee", "cat"], object))
    if hashed:
        c = common.to_hashed_strings(c)
    return c


_RULE = [
    # a 32-bit integer: its bounds must leave the two top values free
    ("int32 hi=max-2", [_col("int32", (0, I32.max - 2))], False, True),
    ("int32 hi=max-1", [_col("int32", (0, I32.max - 1))], False, False),
    ("int32 lo=min, ascending", [_col("int32", (I32.min, 7))], False, True),
    ("uint32 hi=max-2", [_col("uint32", (0, U32.max - 2))], False, True),
    ("uint32 hi=max-1", [_col("uint32", (0, U32.max - 1))], False, False),
    ("int32 no bounds", [_col("int32")], False, False),
    # descending: the operand is ~x, so the LOW end must be free
    ("int32 desc lo=min+2", [_col("int32", (I32.min + 2, I32.max))], True,
     True),
    ("int32 desc lo=min+1", [_col("int32", (I32.min + 1, 0))], True, False),
    ("uint32 desc lo=2", [_col("uint32", (2, U32.max))], True, True),
    ("uint32 desc lo=1", [_col("uint32", (1, 9))], True, False),
    # a 64-bit integer: only as the ONE operand of narrow32
    ("int64 narrow hi=max-2", [_col("int64", (-5, I32.max - 2))], False,
     True),
    ("int64 narrow hi=max-1", [_col("int64", (-5, I32.max - 1))], False,
     False),
    ("int64 narrow desc lo=min+2", [_col("int64", (I32.min + 2, 5))], True,
     True),
    ("int64 wide pair", [_col("int64", (0, 1 << 40))], False, False),
    ("int64 no bounds", [_col("int64")], False, False),
    ("uint64 narrow", [_col("uint64", (0, 1000))], False, True),
    # a null flag always has room, whatever follows it
    ("nullable wide int64", [_col("int64", (0, 1 << 40), True)], False, True),
    ("nullable f64", [_col("float64", nullable=True)], False, True),
    ("nullable on the other table only",
     [_col("int64"), _col("int64", nullable=True)], False, True),
    # operands that use a fraction of their 32 bits
    ("string codes", [_strings()], False, True),
    ("hashed strings (int64 codes)", [_strings(hashed=True)], False, False),
    ("float32", [_col("float32")], False, True),
    ("float32 desc", [_col("float32")], True, True),
    ("float64", [_col("float64")], False, False),
    ("int16", [_col("int16")], True, True),
    ("int8", [_col("int8")], False, True),
    ("bool", [_col("bool")], True, True),
    # two tables ranked together: both must prove it
    ("two tables, both bounded",
     [_col("int64", (0, 9)), _col("int64", (3, I32.max - 2))], False, True),
    ("two tables, one at max-1",
     [_col("int64", (0, 9)), _col("int64", (3, I32.max - 1))], False, False),
    ("two tables, one without bounds",
     [_col("int32", (0, 9)), _col("int32")], False, False),
]


@pytest.mark.parametrize("firsts,descending,folds",
                         [pytest.param(*c[1:], id=c[0]) for c in _RULE])
def test_fold_rule_truth_table(firsts, descending, folds):
    """Only the FIRST key column decides; a second key changes nothing."""
    second = _col("float64")
    assert fold_liveness(*[[c] for c in firsts],
                         descending=descending) is folds
    assert fold_liveness(*[[c, second] for c in firsts],
                         descending=descending) is folds


# ---------------------------------------------------------------------------
# the packer and its static mirrors
# ---------------------------------------------------------------------------

#: (first key dtype, its values at the live rows, nullable, narrow, desc)
_STRUCTS = {
    "narrow_i64": ("int64", [I32.max - 2, -7, 0, I32.min], False, True, False),
    "narrow_i64_desc": ("int64", [I32.min + 2, 9, 0, I32.max], False, True,
                        True),
    "int32": ("int32", [I32.max - 2, I32.min, 5, 5], False, False, False),
    "uint32_desc": ("uint32", [2, U32.max, 77, 2], False, False, True),
    "nullable_wide": ("int64", [1 << 40, -(1 << 50), 3, 3], True, False,
                      False),
    "float32": ("float32", [np.nan, np.inf, -np.inf, -0.0], False, False,
                False),
    "float32_desc": ("float32", [np.nan, np.inf, -np.inf, 1.5], False, False,
                     True),
    "int16": ("int16", [32767, -32768, 0, 1], False, False, False),
    "bool_desc": ("bool", [True, False, True, False], False, False, True),
}


def _ko(struct, fold, pad_key=PAD_L, with_second=True):
    dt, vals, nullable, narrow, desc = _STRUCTS[struct]
    live = len(vals)
    first = np.concatenate([np.asarray(vals, dt), np.zeros(3, dt)])
    second = np.arange(live + 3, dtype=np.int64) * (1 << 33)    # a wide pair
    valid = np.asarray([True, True, False, True, True, True, True]) \
        if nullable else None
    mask = np.arange(live + 3) < live
    datas = [jnp.asarray(first)] + ([jnp.asarray(second)] * with_second)
    valids = [None if valid is None else jnp.asarray(valid)] \
        + ([None] * with_second)
    ko = pack.key_operands(datas, valids, row_mask=jnp.asarray(mask),
                           descendings=[desc] + [False] * with_second,
                           pad_key=pad_key,
                           narrow32=[narrow] + [False] * with_second,
                           fold=fold)
    dtypes = (dt,) + ("int64",) * with_second
    need_nf = (nullable,) + (False,) * with_second
    narrows = (narrow,) + (False,) * with_second
    return ko, mask, (dtypes, need_nf, narrows)


@pytest.mark.parametrize("struct", sorted(_STRUCTS))
@pytest.mark.parametrize("fold", [False, True], ids=["operand", "folded"])
def test_packer_and_static_mirrors_in_lockstep(struct, fold):
    """``key_operand_slots`` / ``_kinds`` / ``sort_operand_nbytes`` say what
    ``key_operands`` builds; folded, there is one operand fewer, every
    other operand is bit-equal at the live rows, and no operand is the
    0 / pad-key liveness flag."""
    ko, mask, (dtypes, need_nf, narrows) = _ko(struct, fold)
    kinds, slots = pack.key_operand_slots(dtypes, need_nf, narrows, fold=fold)
    assert kinds == ko.kinds
    assert pack.key_operand_kinds(dtypes, need_nf, narrows, fold=fold) == kinds
    rows = mask.size
    assert pack.sort_operand_nbytes(dtypes, need_nf, narrows, rows,
                                    fold=fold) == sum(
        np.asarray(o).nbytes for o in ko.ops)
    # the value operands sit where the slots say, in both forms
    plain, _m, _s = _ko(struct, False)
    _k0, slots0 = pack.key_operand_slots(dtypes, need_nf, narrows)
    assert len(plain.ops) == len(ko.ops) + fold
    for col, col0 in zip(slots, slots0):
        for s, s0 in zip(col, col0):
            np.testing.assert_array_equal(np.asarray(ko.ops[s])[mask],
                                          np.asarray(plain.ops[s0])[mask])
    flag = np.where(mask, 0, PAD_L)
    assert any(np.array_equal(np.asarray(o), flag) for o in ko.ops) != fold


@pytest.mark.parametrize("struct", sorted(_STRUCTS))
def test_folded_padding_sorts_last_and_differs_by_table(struct):
    """The sort order of the live rows is the unfolded one, padding fills
    ``[n_live, N)``, and the two tables' padding never compares equal."""
    plain, mask, _ = _ko(struct, False)
    left, _m, _s = _ko(struct, True, PAD_L)
    right, _m, _s = _ko(struct, True, PAD_R)
    n = mask.size
    idx = jnp.arange(n, dtype=jnp.int32)

    def order(ko):
        return np.asarray(jax.lax.sort(ko.ops + (idx,),
                                       num_keys=len(ko.ops))[-1])
    n_live = int(mask.sum())
    np.testing.assert_array_equal(order(left)[:n_live],
                                  order(plain)[:n_live])
    assert set(order(left)[n_live:]) == set(range(n_live, n))
    lead_l, lead_r = np.asarray(left.ops[0]), np.asarray(right.ops[0])
    assert (lead_l[~mask] != lead_r[~mask]).all()
    assert (lead_l[~mask] < lead_r[~mask]).all()        # left's stay first
    live_top = lead_l[mask].max()
    assert (lead_l[~mask] > live_top).all()
    assert lead_l.dtype == np.asarray(plain.ops[1]).dtype


def test_the_packer_refuses_a_fold_with_no_room():
    """A caller that skips the rule cannot corrupt an order silently where
    the operand's shape already says there is no room."""
    mask = jnp.arange(4) < 2
    for data in (jnp.zeros(4, jnp.int64), jnp.zeros(4, jnp.float64)):
        with pytest.raises(ValueError, match="fold"):
            pack.key_operands([data], [None], row_mask=mask, fold=True)
    # no row mask, nothing to fold: today's operands
    ko = pack.key_operands([jnp.zeros(4, jnp.int32)], [None], fold=True)
    assert len(ko.ops) == 1


# ---------------------------------------------------------------------------
# every operator that ranks padded tables: folded == unfolded == pandas
# ---------------------------------------------------------------------------

def _liveness_counts() -> dict:
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith("key_sort_liveness")}


def _count(before: dict, after: dict, form: str, site: str) -> int:
    key = f'key_sort_liveness{{form="{form}",site="{site}"}}'
    return after[key] - before[key]


def _both(monkeypatch, run, site):
    """``run()`` with the rule as it stands and with a rule that never
    folds; each as a pandas frame, and the counter's word for each."""
    before = _liveness_counts()
    folded = run()
    mid = _liveness_counts()
    assert _count(before, mid, "folded", site) >= 1
    assert _count(before, mid, "operand", site) == 0
    monkeypatch.setattr(pack, "fold_room", lambda *a, **k: False)
    plain = run()
    monkeypatch.undo()
    after = _liveness_counts()
    assert _count(mid, after, "operand", site) >= 1
    assert _count(mid, after, "folded", site) == 0
    return folded, plain


def _edge_keys(rng, n, kind):
    """Keys that repeat, with live rows AT the edge the rule admits: the
    operand type's ``max - 2``, which is also the column's ``hi``."""
    if kind == "string":
        pool = np.asarray(["ant", "bee", "cat", "dog", "elk"], object)
        return pool[rng.integers(0, len(pool), n)]
    if kind == "float32":
        k = rng.integers(-4, 4, n).astype(np.float32)
        k[::7] = np.nan
        k[1::11] = np.inf
        return k
    dt = {"int64": np.int64, "int32": np.int32, "nullable": np.int64}[kind]
    k = rng.integers(0, 6, n).astype(dt)
    k[::5] = I32.max - 2
    k[1::9] = I32.min + 2           # the descending sort's edge
    if kind == "nullable":
        return pd.array([None if i % 6 == 3 else int(v)
                         for i, v in enumerate(k)], dtype="Int64")
    return k


_KINDS = ("int64", "int32", "nullable", "string", "float32")


@pytest.mark.parametrize("how", ["inner", "left", "right", "outer", "semi",
                                 "anti"])
@pytest.mark.parametrize("kind", _KINDS)
def test_join_folded_equals_unfolded(env, rng, monkeypatch, how, kind):
    """Every ``how``, on tables under capacity; the inner join feeds its
    fused groupby (the benchmark's query) and the others are compared row
    for row as they come out."""
    n_l, n_r = 45, 29
    ldf = pd.DataFrame({"k": _edge_keys(rng, n_l, kind),
                        "a": rng.integers(-9, 9, n_l).astype(np.int64)})
    rdf = pd.DataFrame({"k": _edge_keys(rng, n_r, kind),
                        "b": rng.integers(-9, 9, n_r).astype(np.int64)})
    lt, rt = ct.Table.from_pandas(ldf, env), ct.Table.from_pandas(rdf, env)
    assert (lt.valid_counts < lt.capacity).any()

    def run():
        j = join_tables(lt, rt, "k", "k", how=how)
        if how == "inner" and kind != "float32":
            j = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
        return j.to_pandas()
    folded, plain = _both(monkeypatch, run, "join")
    pd.testing.assert_frame_equal(folded, plain)
    if how in ("semi", "anti"):
        # a null key matches a null key, NaN matches NaN (pandas' merge)
        hit = ldf["k"].isin(rdf["k"].dropna()) | (
            ldf["k"].isna() & bool(rdf["k"].isna().any()))
        exp = ldf[hit if how == "semi" else ~hit]
    else:
        exp = ldf.merge(rdf, on="k", how=how)
        if how == "inner" and kind != "float32":
            exp = exp.groupby("k", dropna=False, as_index=False).agg(
                a_sum=("a", "sum"), b_sum=("b", "sum"))
    assert_frames_equal(folded, exp[list(folded.columns)])


@pytest.mark.parametrize("kind", _KINDS)
def test_groupby_folded_equals_unfolded(env, rng, monkeypatch, kind):
    """Route ``raw`` on one device; ``combine`` -> exchange -> ``final`` on
    the mesh of four (both phases ask the rule)."""
    n = 83
    df = pd.DataFrame({"k": _edge_keys(rng, n, kind),
                       "a": rng.integers(-9, 9, n).astype(np.int64)})
    t = ct.Table.from_pandas(df, env)
    folded, plain = _both(
        monkeypatch, lambda: groupby_aggregate(
            t, "k", [("a", "sum"), ("a", "max")]).to_pandas(), "groupby")
    pd.testing.assert_frame_equal(folded, plain)
    exp = df.groupby("k", dropna=False, as_index=False).agg(
        a_sum=("a", "sum"), a_max=("a", "max"))
    assert_frames_equal(folded, exp)


def test_groupby_nunique_route_folded_equals_unfolded(env, rng, monkeypatch):
    """A non-associative op takes ``_group_keys`` (the dense rank), which
    folds like the sort path."""
    n = 61
    df = pd.DataFrame({"k": _edge_keys(rng, n, "int64"),
                       "a": rng.integers(0, 4, n).astype(np.int64)})
    t = ct.Table.from_pandas(df, env)
    folded, plain = _both(
        monkeypatch, lambda: groupby_aggregate(
            t, "k", [("a", "nunique")]).to_pandas(), "groupby")
    pd.testing.assert_frame_equal(folded, plain)
    exp = df.groupby("k", as_index=False).agg(a_nunique=("a", "nunique"))
    assert_frames_equal(folded, exp)


@pytest.mark.parametrize("ascending", [True, False, [False, True]],
                         ids=["asc", "desc", "desc_asc"])
@pytest.mark.parametrize("kind", _KINDS)
def test_sort_folded_equals_unfolded(env, rng, monkeypatch, kind, ascending):
    n = 77
    df = pd.DataFrame({"k": _edge_keys(rng, n, kind),
                       "v": rng.integers(0, 3, n).astype(np.int64),
                       "w": np.arange(n, dtype=np.int64)})
    t = ct.Table.from_pandas(df, env)
    by = ["k", "v"] if isinstance(ascending, list) else ["k"]
    folded, plain = _both(
        monkeypatch,
        lambda: sort_table(t, by, ascending=ascending).to_pandas(), "sort")
    pd.testing.assert_frame_equal(folded, plain)
    exp = df.sort_values(by, ascending=ascending, kind="stable")
    if env.world_size == 1:     # a stable sort: pandas' rows in pandas' order
        pd.testing.assert_frame_equal(
            folded.reset_index(drop=True), exp.reset_index(drop=True),
            check_dtype=False)
    else:
        pd.testing.assert_frame_equal(
            folded[by].reset_index(drop=True),
            exp[by].reset_index(drop=True), check_dtype=False)
        assert_frames_equal(folded, exp)


@pytest.mark.parametrize("keep", ["first", "last"])
@pytest.mark.parametrize("kind", _KINDS)
def test_unique_folded_equals_unfolded(env, rng, monkeypatch, kind, keep):
    n = 90
    df = pd.DataFrame({"k": _edge_keys(rng, n, kind),
                       "w": np.arange(n, dtype=np.int64)})
    t = ct.Table.from_pandas(df, env)
    folded, plain = _both(
        monkeypatch,
        lambda: unique_table(t, subset=["k"], keep=keep).to_pandas(),
        "setops")
    pd.testing.assert_frame_equal(folded, plain)
    assert_frames_equal(folded, df.drop_duplicates(subset=["k"], keep=keep))


@pytest.mark.parametrize("op", ["union", "intersect", "subtract"])
@pytest.mark.parametrize("kind", _KINDS)
def test_set_operation_folded_equals_unfolded(env, rng, monkeypatch, kind,
                                              op):
    """The three set operations (``unique`` is the family's fourth
    operator, above)."""
    n_a, n_b = 58, 41
    adf = pd.DataFrame({"k": _edge_keys(rng, n_a, kind),
                        "v": rng.integers(0, 2, n_a).astype(np.int64)})
    bdf = pd.DataFrame({"k": _edge_keys(rng, n_b, kind),
                        "v": rng.integers(0, 2, n_b).astype(np.int64)})
    a, b = ct.Table.from_pandas(adf, env), ct.Table.from_pandas(bdf, env)
    folded, plain = _both(
        monkeypatch, lambda: set_operation(a, b, op).to_pandas(), "setops")
    pd.testing.assert_frame_equal(folded, plain)
    ua, ub = adf.drop_duplicates(), bdf.drop_duplicates()
    both = ua.merge(ub, on=["k", "v"], how="left", indicator=True)
    exp = {"union": pd.concat([adf, bdf]).drop_duplicates(),
           "intersect": both[both["_merge"] == "both"][["k", "v"]],
           "subtract": both[both["_merge"] == "left_only"][["k", "v"]]}[op]
    assert_frames_equal(folded, exp)


def test_a_key_with_no_room_keeps_its_operand(env1, rng):
    """A wide int64 key and a key whose bounds a groupby dropped (the
    benchmark's ``sort_table`` of an aggregate) sort under a liveness
    operand, as before; tables at capacity are ``all_live``."""
    n = 50
    wide = pd.DataFrame({"k": rng.integers(0, 5, n).astype(np.int64) << 40,
                         "a": np.arange(n, dtype=np.int64)})
    t = ct.Table.from_pandas(wide, env1)
    before = _liveness_counts()
    g = groupby_aggregate(t, "k", [("a", "sum")])
    assert g.column("a_sum").bounds is None
    sort_table(g, "a_sum").to_pandas()
    full = pd.DataFrame({"k": np.arange(64, dtype=np.int64),
                         "a": np.arange(64, dtype=np.int64)})
    ft = ct.Table.from_pandas(full, env1)
    assert (ft.valid_counts == ft.capacity).all()
    join_tables(ft, ft, "k", "k").to_pandas()
    after = _liveness_counts()
    assert _count(before, after, "operand", "groupby") == 1
    assert _count(before, after, "operand", "sort") == 1
    assert _count(before, after, "all_live", "join") == 1
    assert _count(before, after, "folded", "join") == 0


# ---------------------------------------------------------------------------
# the benchmark cells' count programs
# ---------------------------------------------------------------------------

def _lowered(program, args) -> str:
    fn = compiler._unwrap_program(program)
    target = fn._fn if isinstance(fn, compiler._Program) else fn
    return target.lower(*args).as_text()


def _sorts(text: str) -> list:
    """Operand count of each ``stablehlo.sort`` of a lowered program."""
    return [len(m.group(1).split(","))
            for m in re.finditer(r'"stablehlo\.sort"\(([^)]*)\)', text)]


def _cell_tables(env1, rng, n=1000):
    """The cells' schema: int64 columns in [0, 0.9 n), tables under
    capacity."""
    def mk():
        return rng.integers(0, int(n * 0.9), n).astype(np.int64)
    a = ct.Table.from_pydict({"k": mk(), "v": mk() % 4}, env1)
    b = ct.Table.from_pydict({"k": mk(), "v": mk() % 4}, env1)
    assert (a.valid_counts < a.capacity).all()
    return a, b


@pytest.mark.parametrize("cell,module,builder,rank_operands", [
    ("join", "join", "_count_fn", 3),           # key, idx, a over b
    ("unique", "setops", "_unique_count_fn", 2),        # k, idx
    ("union", "setops", "_setop_count_fn", 3),          # k, v, idx
    ("subtract", "setops", "_setop_count_fn", 3),
    ("groupby", "groupby", "_raw_fn", 3),       # k and its two lanes
])
def test_cell_count_programs_sort_one_operand_fewer(env1, rng, monkeypatch,
                                                    cell, module, builder,
                                                    rank_operands):
    """The widest sort of the program a cell's count step launches, and no
    operand that is a select of two constants over the row mask (what the
    liveness flag was: ``where(row_mask, 0, pad_key)``)."""
    import importlib
    mod = importlib.import_module(f"cylon_tpu.relational.{module}")
    a, b = _cell_tables(env1, rng)
    log = []
    _spy(monkeypatch, mod, builder, log)
    if cell == "join":
        lt = a.rename({"v": "a"})
        rt = b.rename({"v": "b"})
        before = metrics.snapshot()
        groupby_aggregate(join_tables(lt, rt, "k", "k"), "k",
                          [("a", "sum"), ("b", "sum")]).to_pandas()
        after = metrics.snapshot()
        ops = after["join_sort_operands"] - before["join_sort_operands"]
        n = after["join_sort_dispatches"] - before["join_sort_dispatches"]
        assert n >= 1 and ops == 3 * n      # join_sort_operands_per_join 3.0
    elif cell == "unique":
        unique_table(a, subset=["k"]).to_pandas()
    elif cell == "groupby":
        groupby_aggregate(a, "k", [("v", "sum")]).to_pandas()
    else:
        set_operation(a, b, cell).to_pandas()
    monkeypatch.undo()
    program, args = log[-1]
    text = _lowered(program, args)
    assert max(_sorts(text)) == rank_operands
    # the flag's own text: a select between the constants 0 and 4 / 5
    assert not re.search(r"dense<[45]> : tensor<\d+xi32>", text)
