"""The set-operation deployment of benchmark cell ``setops_dedup_32m`` (ISSUE
48) at a small size on the CPU rig: ``unique_table`` / ``set_operation`` on
the configuration's own schema against the benchmark's plain reference
(``benchmark/queries/setops_dedup.py``: numpy, nothing of the program), the
routes on the plan nodes, the spans' arguments, the registry's counters and
the stage the flags carry."""

from __future__ import annotations

import contextlib
import json
import os
import sys

import numpy as np
import pytest

import cylon_tpu as ct
from cylon_tpu import obs
from cylon_tpu.obs import metrics
from cylon_tpu.relational import (common, set_operation, setops,
                                  unique_table)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
ROWS = 200_000
OPS = ("union", "intersect", "subtract")


def _lib() -> None:
    """The benchmark's ``lib`` package importable, as ``run.py`` has it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)


@pytest.fixture(scope="module")
def qm():
    _lib()
    from lib import files
    return files.load_module(BENCH, "queries", "setops_dedup")


@pytest.fixture(scope="module")
def cfg():
    with open(os.path.join(BENCH, "configs",
                           "cylon_setops_dedup_32m.json")) as f:
        cfg = json.load(f)
    for t in cfg["tables"].values():
        t["rows"] = ROWS
    return cfg


@pytest.fixture(scope="module")
def host(cfg):
    """The configuration's tables at 200k rows, from a large seed."""
    _lib()
    from lib import generate
    return generate.host_tables(BENCH, cfg, 2**31 + 48)


def _device(env, host):
    return {n: ct.Table.from_pydict(c, env) for n, c in host.items()}


def _rows(table, m: int = 4) -> np.ndarray:
    """A result's rows packed ``k * m + v`` and sorted."""
    h = {n: d for n, (d, _v) in table.host_columns().items()}
    assert h["k"].dtype == h["v"].dtype == np.int64
    return np.sort(h["k"] * m + h["v"])


@pytest.mark.parametrize("envname", ["env1", "env4"])
def test_three_operators_equal_the_reference(request, qm, cfg, host, envname):
    """One iteration of the cell's query through the query module, every
    row of the three results equal to the reference's, and ``intersect`` -
    subtract's program with the flag inverted - held beside them."""
    env = request.getfixturevalue(envname)
    q = cfg["query"]
    tables = _device(env, host)
    res = qm.query(tables, q, lambda name: contextlib.nullcontext())
    got = qm.canonical({n: d for n, (d, _v) in res.host_columns().items()},
                       q, 0)
    want = qm.reference(host, q, 0)
    assert set(got) == set(want) == {
        f"{r}.{c}" for r in qm.RESULTS for c in ("k", "v")}
    for name, w in want.items():
        assert got[name].dtype == np.int64
        assert np.array_equal(got[name], w), name
    assert res.row_count == sum(len(want[f"{r}.k"]) for r in qm.RESULTS)
    assert all(v == 0 for _n, v, _lim in qm.extra_numbers(
        host, {n: d for n, (d, _v) in res.host_columns().items()}, q))
    # intersect: the rows of distinct(a) that b holds
    pa, pb = (np.unique(host[t]["k"] * 4 + host[t]["v"]) for t in "ab")
    both = _rows(set_operation(tables["a"], tables["b"], "intersect"))
    assert np.array_equal(both, np.intersect1d(pa, pb))
    # |a - b| = |distinct(a)| - |distinct(a) n distinct(b)|, and the union's
    assert len(want["subtract.k"]) == len(pa) - len(both)
    assert len(want["union.k"]) == len(np.union1d(pa, pb))


@pytest.mark.parametrize("envname", ["env1", "env4"])
@pytest.mark.parametrize("keep", ["first", "last"])
def test_keep_is_by_source_row_position(request, host, envname, keep):
    """For every key the kept ``v`` is that of the smallest (``last``: the
    largest) row position in the source table - across shards too."""
    env = request.getfixturevalue(envname)
    k, v = host["a"]["k"], host["a"]["v"]
    out = unique_table(ct.Table.from_pydict(host["a"], env), subset=["k"],
                       keep=keep)
    h = {n: d for n, (d, _v) in out.host_columns().items()}
    want = np.full(int(k.max()) + 1, -1, np.int64)
    order = np.arange(len(k))[::-1] if keep == "first" else np.arange(len(k))
    want[k[order]] = v[order]          # the last write wins
    assert len(h["k"]) == len(np.unique(k)) == np.count_nonzero(want >= 0)
    assert np.array_equal(want[h["k"]], h["v"])
    # ~40% of the rows repeat an earlier key: the test has something to keep
    assert len(h["k"]) < 0.65 * len(k)


def _routes(plan) -> list:
    """``(op, route)`` of every plan node, pre-order: the harness's own
    reading (``benchmark/lib/checks.plan_routes``)."""
    _lib()
    from lib import checks
    return [tuple(r) for r in checks.plan_routes(plan)]


@pytest.mark.parametrize("envname,route", [("env1", "local"),
                                           ("env4", "hash")])
def test_plan_nodes_name_their_route(request, host, envname, route):
    env = request.getfixturevalue(envname)
    t = _device(env, host)

    def q():
        unique_table(t["a"], subset=["k"])
        for op in OPS:
            set_operation(t["a"], t["b"], op)
        set_operation(t["a"], t["b"], "union", assume_colocated=True)
    plan = obs.explain_analyze(q, profile_keys=False)
    seen = [r for r in _routes(plan) if r[0] in ("unique", "set_op")]
    assert seen == [("unique", route)] + [("set_op", route)] * 3 \
        + [("set_op", "colocated")]
    assert setops.plan_route(env) == route
    # two runs of one query say the same tree
    assert obs.explain(q).static_dict() == obs.explain(q).static_dict()
    nodes = plan.to_dict()["roots"]
    assert nodes[0]["rows_in"] == ROWS and nodes[1]["rows_in"] == 2 * ROWS
    assert all(n["rows_out"] > 0 for n in nodes)


def test_fallback_names_no_route_on_the_operator_node(env1, host, monkeypatch):
    """Where the recovery ladder's rung answers, the ``set_op`` node names
    no route (``expect.routes`` then misses it) and no dispatch is counted:
    the fallback's own nodes say what ran."""
    t = _device(env1, {n: {c: v[:4096] for c, v in cols.items()}
                       for n, cols in host.items()})
    monkeypatch.setattr(common, "run_with_oom_fallback",
                        lambda primary, can_fallback, fallback, label,
                        env=None: fallback(4))
    before = metrics.snapshot()['setop_dispatches{op="union"}']
    plan = obs.explain_analyze(
        lambda: set_operation(t["a"], t["b"], "union"), profile_keys=False)
    top = plan.to_dict()["roots"][0]
    assert top["op"] == "set_op" and "route" not in top["attrs"]
    assert [c["op"] for c in top["children"]] == ["pipelined_set_op"]
    assert [r for r in _routes(plan)[1:] if r[0] == "set_op"] == []
    assert metrics.snapshot()['setop_dispatches{op="union"}'] == before


def test_counters_and_span_arguments(env1, host):
    """``setop_dispatches{op=…}`` / ``setop_rows_out{op=…}`` move by one
    call's worth, and the ``cylon.op.*`` span carries ``rows_in``,
    ``rows_out``, ``out_cap`` and ``kind`` / ``keep``."""
    from cylon_tpu import config
    from cylon_tpu.utils import timing
    t = _device(env1, host)

    def snap():
        return {k: v for k, v in metrics.snapshot().items()
                if k.startswith("setop_")}
    said = []
    set_args = timing.set_args

    def spy(live, **args):
        said.append(args)
        return set_args(live, **args)
    before = snap()
    timing.set_args = spy
    try:
        u = unique_table(t["a"], subset=["k"], keep="last")
        s = set_operation(t["a"], t["b"], "subtract")
    finally:
        timing.set_args = set_args
    after = snap()
    moved = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    assert moved == {'setop_dispatches{op="unique"}': 1,
                     'setop_dispatches{op="subtract"}': 1,
                     'setop_rows_out{op="unique"}': u.row_count,
                     'setop_rows_out{op="subtract"}': s.row_count}
    assert said == [
        {"rows_out": u.row_count, "out_cap": config.pow2ceil(u.row_count),
         "keep": "last", "rows_in": ROWS},
        {"rows_out": s.row_count, "out_cap": config.pow2ceil(s.row_count),
         "kind": "subtract", "rows_in": 2 * ROWS}]


@pytest.mark.parametrize("op", OPS)
def test_flag_kernels_equal_their_definition(op):
    """``ops/setops.set_op_flags`` on a small concat against the three
    definitions written out, masked rows never flagged."""
    import jax.numpy as jnp
    from cylon_tpu.ops import setops as setk
    rng = np.random.default_rng(48)
    n_a, n_b = 300, 200
    gids = rng.integers(0, 120, n_a + n_b).astype(np.int32)
    side_b = np.arange(n_a + n_b) >= n_a
    mask = rng.random(n_a + n_b) < 0.9
    got = np.asarray(setk.set_op_flags(jnp.asarray(gids), jnp.asarray(side_b),
                                       op, jnp.asarray(mask)))
    in_b = set(gids[side_b & mask])
    want = np.zeros(n_a + n_b, bool)
    seen = set()
    for i in np.flatnonzero(mask):
        g = gids[i]
        if op == "union":
            want[i] = g not in seen
            seen.add(g)
        elif not side_b[i]:
            want[i] = g not in seen and ((g in in_b) == (op == "intersect"))
            seen.add(g)
    assert np.array_equal(got, want)
    with pytest.raises(ValueError):
        setk.set_op_flags(jnp.asarray(gids), jnp.asarray(side_b), "xor")


def test_flags_and_row_gathers_carry_their_stage(env1):
    """The segment min / max and their gathers lower under
    ``cylon.setop_flags`` in all six programs, the set operations'
    materialize gathers under ``cylon.gather_rows``; ``dense_rank``'s
    un-sort scatter stays where ``ops/pack.py`` names it (``gather_rows``:
    a stage opened there would change other cells' programs)."""
    import jax
    from cylon_tpu.analysis.registry import unwrap
    from cylon_tpu.ops import lanes
    from cylon_tpu.utils import stages
    assert "setop_flags" in stages.STAGES
    S = jax.ShapeDtypeStruct
    cap = 4096
    vc, col = S((1,), np.int32), S((cap,), np.int64)
    two, none2 = (col, col), (None, None)
    spec = lanes.plan_lanes(("int64", "int64"), (False, False), (True, True))
    narrow = (True, True)
    programs = {
        "unique_count": (setops._unique_count_fn(env1.mesh, "first", (True,)),
                         (vc, (col,), (None,))),
        "unique_mat": (setops._unique_mat_fn(env1.mesh, "first", (True,),
                                             2048, spec),
                       (vc, (col,), (None,), two, none2)),
    }
    for op in ("union", "subtract"):
        programs[f"{op}_count"] = (setops._setop_count_fn(env1.mesh, op,
                                                          narrow),
                                   (vc, vc, two, none2, two, none2))
        programs[f"{op}_mat"] = (setops._setop_mat_fn(env1.mesh, op, narrow,
                                                      4096),
                                 (vc, vc, two, none2, two, none2))
    for name, (prog, args) in programs.items():
        text = unwrap(prog).lower(*args).as_text(debug_info=True)
        flagged = [ln for ln in text.splitlines()
                   if "cylon.setop_flags" in ln]
        assert any("scatter" in ln for ln in flagged), name
        assert any("gather" in ln for ln in flagged), name
        if name.endswith("_mat") and not name.startswith("unique"):
            assert any("gather" in ln and "cylon.setops/cylon.gather_rows"
                       in ln for ln in text.splitlines()), name


# ---- the repair: a key whose bounds fit int32 is ONE sort operand ----------
# (XLA:TPU compiles a sort in time that grows with its operands: at the
# cell's size the six programs' sorts of 4 / 6 operands compiled cold past
# the check's stop; PERF.md §6, PR 48)

def _sort_operands(prog, *args) -> int:
    """Operands of the program's one multi-operand key sort."""
    import re
    from cylon_tpu.analysis.registry import unwrap
    text = unwrap(prog).lower(*args).as_text()
    sorts = re.findall(r'"stablehlo.sort"\(([^)]*)\)', text)
    assert len(sorts) == 1, sorts
    return len(sorts[0].split(","))


@pytest.mark.parametrize("narrow,unique_ops,setop_ops", [
    ((True, True), 3, 4),       # liveness, k, [v,] idx
    ((False, True), 4, 5),      # k as (hi, lo)
    ((False, False), 4, 6)])
def test_sort_operands_follow_the_bounds(env1, narrow, unique_ops, setop_ops):
    import jax
    S = jax.ShapeDtypeStruct
    vc, col = S((1,), np.int32), S((4096,), np.int64)
    two, none2 = (col, col), (None, None)
    assert _sort_operands(
        setops._unique_count_fn(env1.mesh, "first", narrow[:1]),
        vc, (col,), (None,)) == unique_ops
    for op in ("union", "subtract"):
        assert _sort_operands(
            setops._setop_count_fn(env1.mesh, op, narrow),
            vc, vc, two, none2, two, none2) == setop_ops
        assert _sort_operands(
            setops._setop_mat_fn(env1.mesh, op, narrow, 4096),
            vc, vc, two, none2, two, none2) == setop_ops


@pytest.mark.parametrize("envname", ["env1", "env4"])
def test_narrow_and_wide_keys_give_the_same_rows(request, envname):
    """The same rows as int32-bounded keys (one operand a column), shifted
    past int32 (a pair a column) and with no bounds at all: the same three
    results, at the edges of int32 too."""
    env = request.getfixturevalue(envname)
    rng = np.random.default_rng(2**31 + 5)
    n, lo, hi = 20_000, -(1 << 31), (1 << 31) - 1
    edge = np.array([lo, lo + 1, -1, 0, 1, hi - 1, hi], np.int64)

    def draw():
        k = np.concatenate([rng.integers(-3000, 3000, n), rng.choice(edge, 64)])
        return {"k": k.astype(np.int64), "v": rng.integers(0, 4, len(k))}
    a, b = draw(), draw()

    def run(shift: int, drop_bounds: bool):
        ta, tb = (ct.Table.from_pydict({"k": t["k"] + shift, "v": t["v"]},
                                       env) for t in (a, b))
        if drop_bounds:
            for t in (ta, tb):
                for c in t.columns.values():
                    c.bounds = None
        flags = common.narrow32_flags([ta.column("k"), ta.column("v")],
                                      [tb.column("k"), tb.column("v")])
        out = [unique_table(ta, subset=["k"])] + [
            set_operation(ta, tb, op) for op in OPS]
        rows = []
        for t in out:
            h = {c: d for c, (d, _v) in t.host_columns().items()}
            rows.append(np.sort((h["k"] - shift) * 4 + h["v"]))
        return flags, rows
    f_narrow, narrow = run(0, False)
    f_wide, wide = run(1 << 40, False)
    f_none, unbounded = run(0, True)
    assert f_narrow == (True, True) and f_wide == (False, True) \
        and f_none == (False, False)
    pa, pb = (np.unique(t["k"] * 4 + t["v"]) for t in (a, b))
    want = [None, np.union1d(pa, pb), np.intersect1d(pa, pb),
            np.setdiff1d(pa, pb)]
    for got_n, got_w, got_u, w in zip(narrow, wide, unbounded, want):
        assert np.array_equal(got_n, got_w) and np.array_equal(got_n, got_u)
        if w is not None:
            assert np.array_equal(got_n, w)
    assert len(narrow[0]) == len(np.unique(a["k"]))
