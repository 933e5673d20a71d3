"""``ops/pack.SORT_OPERAND_BUDGET``: no sort of the sort, groupby or eager
join paths is handed more operands than the budget where its payload can
move another way (XLA:TPU's compile time of a sort grows with its operand
count, hardly with its rows: PERF.md, PR 41) - and the results do not
change.  The eager join's side of the rule is in tests/test_join.py."""

import re

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.exec import compiler
from cylon_tpu.ops import pack
from cylon_tpu.relational import groupby_aggregate, sort_table

WIDE = 1 << 40          # past int32: a wide column is two u32 lanes


def _spy(monkeypatch, module, name, log):
    """Every program the cached builder ``module.name`` hands out, with the
    arguments it was called with."""
    orig = getattr(module, name)

    def builder(mesh, *static, **kw):
        fn = orig(mesh, *static, **kw)

        def call(*args):
            log.append((fn, args))
            return fn(*args)
        return call
    monkeypatch.setattr(module, name, builder)


def _sort_operands(program, args) -> list:
    """Operand count of each ``stablehlo.sort`` in the program's text."""
    fn = compiler._unwrap_program(program)
    target = fn._fn if isinstance(fn, compiler._Program) else fn
    text = target.lower(*args).as_text()
    return [len(m.group(1).split(","))
            for m in re.finditer(r'"stablehlo\.sort"\(([^)]*)\)', text)]


def test_the_budget_is_the_accepted_cells_widest_sort():
    """``groupby_sort_25m``'s ``sort_table`` (a wide key, a narrow column)
    has 7 operands; a lower budget would change an accepted program."""
    assert pack.SORT_OPERAND_BUDGET == 7


@pytest.mark.parametrize("n_wide,n_keys,rides", [
    (0, 1, True),       # a narrow key: 2 keys + 3 lanes
    (1, 1, True),       # the accepted cell's shape: 3 keys + 4 lanes = 7
    (2, 1, False),      # 3 + 5 lanes
    (1, 2, False),      # 5 keys + 4 lanes: TPC-H Q3's ORDER BY
    (4, 2, False),
])
def test_sort_table_rides_within_the_budget(env1, rng, monkeypatch, n_wide,
                                            n_keys, rides):
    from cylon_tpu.relational import sort as rs
    n = 300
    data = {"k": np.arange(n, dtype=np.int64)}
    for i in range(max(n_wide, n_keys)):
        data[f"w{i}"] = rng.integers(-5, 5, n).astype(np.int64) * WIDE
    df = pd.DataFrame(data)
    by = [f"w{i}" for i in range(n_keys)] if n_wide else ["k"]
    asc = [i % 2 == 1 for i in range(len(by))]
    log = []
    _spy(monkeypatch, rs, "_local_sort_fn", log)
    got = sort_table(ct.Table.from_pandas(df, env1), by,
                     ascending=asc).to_pandas()
    monkeypatch.undo()
    exp = df.sort_values(by, ascending=asc, kind="stable")
    pd.testing.assert_frame_equal(got.reset_index(drop=True),
                                  exp.reset_index(drop=True),
                                  check_dtype=False)
    (program, args), = log
    (operands,) = _sort_operands(program, args)
    assert operands <= pack.SORT_OPERAND_BUDGET
    lanes = 1 + 2 * max(n_wide, n_keys)
    # a wide pair leads with a liveness operand; the narrow key carries
    # padding inside itself (ISSUE 50) - and still counts against the
    # budget as the operand it was, so ``rides`` is what it was
    keys = 2 * n_keys + 1 if n_wide else 1
    # riding: keys + every lane; past the budget: keys + the row index
    assert operands == (keys + lanes if rides else keys + 1)


@pytest.mark.parametrize("envname", ["env1", "env4"])
@pytest.mark.parametrize("n_keys,n_vals,rides", [
    (1, 1, True),       # key, value + key lanes: 3 (4 until ISSUE 50)
    (2, 1, True),       # 2 + 3
    (3, 1, False),      # Q3's GROUP BY: a wide key among three
    (2, 4, False),
])
def test_groupby_sort_rides_within_the_budget(request, rng, monkeypatch,
                                              envname, n_keys, n_vals,
                                              rides):
    from cylon_tpu.relational import groupby as rg
    env = request.getfixturevalue(envname)
    n = 400
    data = {}
    for i in range(n_keys):
        scale = WIDE if i == 2 else 1
        data[f"k{i}"] = rng.integers(0, 4, n).astype(np.int64) * scale
    for i in range(n_vals):
        data[f"v{i}"] = rng.integers(-99, 99, n).astype(np.int64)
    df = pd.DataFrame(data)
    keys = [f"k{i}" for i in range(n_keys)]
    aggs = [(f"v{i}", "sum") for i in range(n_vals)]
    log = []
    for name in ("_raw_fn", "_combine_fn"):
        _spy(monkeypatch, rg, name, log)
    got = groupby_aggregate(ct.Table.from_pandas(df, env), keys,
                            aggs).to_pandas()
    monkeypatch.undo()
    exp = df.groupby(keys, as_index=False).agg(
        **{f"v{i}_sum": (f"v{i}", "sum") for i in range(n_vals)})
    got = got.sort_values(keys).reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp.sort_values(keys).reset_index(
        drop=True), check_dtype=False)
    widest = max(max(_sort_operands(p, a), default=0) for p, a in log)
    assert 0 < widest <= pack.SORT_OPERAND_BUDGET
    # no liveness operand: it rides in the narrow first key (ISSUE 50); the
    # ``wide`` decision counts it as the operand it was
    key_ops = n_keys + (n_keys == 3)
    lanes = n_vals + n_keys + (n_keys == 3)
    if env.world_size == 1:
        assert widest == (key_ops + lanes if rides else key_ops + 1)
