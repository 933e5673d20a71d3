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
                     'setop_mat_dispatches{path="plain",reason="not_tpu"}': 2,
                     'setop_rows_out{op="unique"}': u.row_count,
                     'setop_rows_out{op="subtract"}': s.row_count}
    assert said == [
        {"rows_out": u.row_count, "out_cap": config.pow2ceil(u.row_count),
         "keep": "last", "rows_in": ROWS},
        {"rows_out": s.row_count, "out_cap": config.pow2ceil(s.row_count),
         "kind": "subtract", "rows_in": 2 * ROWS}]


def _rank_sorted_numpy(gids, live):
    """The rank sort on the host: ``(first, live, sidx)`` of the rows in
    (liveness, gid, row index) order - what ``setops._rank_sorted`` hands
    the flag kernels."""
    sidx = np.lexsort((np.arange(len(gids)), gids, ~live))
    g, lv = gids[sidx], live[sidx]
    first = np.ones(len(gids), bool)
    first[1:] = (g[1:] != g[:-1]) | (lv[1:] != lv[:-1])
    return first, lv, sidx


@pytest.mark.parametrize("op", OPS + ("unique_first", "unique_last"))
def test_flag_kernels_equal_their_definition(op):
    """``ops/setops.set_op_flags`` / ``unique_flags`` in the rank sort's
    order, brought back to row order, against the definitions written out;
    masked rows never flagged."""
    import jax.numpy as jnp
    from cylon_tpu.ops import setops as setk
    rng = np.random.default_rng(48)
    n_a, n_b = 300, 200
    g_a, g_b = (rng.integers(0, 120, n).astype(np.int32) for n in (n_a, n_b))
    m_a, m_b = (rng.random(n) < 0.9 for n in (n_a, n_b))
    if op.startswith("unique"):
        keep = op[len("unique_"):]
        first, live, sidx = _rank_sorted_numpy(g_a, m_a)
        got = np.zeros(n_a, bool)
        got[sidx] = np.asarray(setk.unique_flags(
            jnp.asarray(first), jnp.asarray(live), keep))
        want = np.zeros(n_a, bool)
        order = np.flatnonzero(m_a)
        seen = set()
        for i in (order if keep == "first" else order[::-1]):
            want[i] = g_a[i] not in seen
            seen.add(g_a[i])
        assert np.array_equal(got, want) and want.sum() < m_a.sum()
        return
    # union ranks [a; b]; subtract / intersect rank [b; a]
    a_first = op == "union"
    gids = np.concatenate([g_a, g_b] if a_first else [g_b, g_a])
    live = np.concatenate([m_a, m_b] if a_first else [m_b, m_a])
    first, lv, sidx = _rank_sorted_numpy(gids, live)
    is_b = (sidx >= n_a) if a_first else (sidx < n_b)
    flags = np.asarray(setk.set_op_flags(
        jnp.asarray(first), jnp.asarray(lv), jnp.asarray(is_b), op))
    got = np.zeros(n_a + n_b, bool)         # in [a; b] row order
    got[sidx if a_first else np.where(is_b, sidx + n_a, sidx - n_b)] = flags
    in_b = set(g_b[m_b])
    want = np.zeros(n_a + n_b, bool)
    seen = set()
    for i in np.flatnonzero(np.concatenate([m_a, m_b])):
        g = np.concatenate([g_a, g_b])[i]
        if op == "union":
            want[i] = g not in seen
            seen.add(g)
        elif i < n_a:
            want[i] = g not in seen and ((g in in_b) == (op == "intersect"))
            seen.add(g)
    assert np.array_equal(got, want) and want.any()
    with pytest.raises(ValueError):
        setk.set_op_flags(jnp.asarray(first), jnp.asarray(lv),
                          jnp.asarray(is_b), "xor")


def _cell_programs(mesh, narrow=(True, True), cap=4096, out_cap=2048,
                   window=0) -> dict:
    """``name -> (program, argument shapes)`` of the cell's six programs
    (and ``intersect``'s pair) at a small size: two int64 columns."""
    import jax
    from cylon_tpu.ops import lanes
    S = jax.ShapeDtypeStruct
    vc, col = S((1,), np.int32), S((cap,), np.int64)
    two, none2 = (col, col), (None, None)
    spec = lanes.plan_lanes(("int64", "int64"), (False, False), narrow)
    programs = {
        "unique_count": (setops._unique_count_fn(mesh, "first", narrow[:1]),
                         (vc, (col,), (None,))),
        "unique_mat": (setops._unique_mat_fn(mesh, spec, out_cap, window),
                       (vc, S((cap,), np.int32), two, none2)),
    }
    for op in OPS:
        programs[f"{op}_count"] = (setops._setop_count_fn(mesh, op, narrow),
                                   (vc, vc, two, none2, two, none2))
        srt = S((2 * cap,), np.int32)
        programs[f"{op}_mat"] = (
            setops._setop_mat_fn(mesh, op, spec, out_cap, window),
            (vc, srt, vc, two, none2, two, none2) if op == "union"
            else (vc, srt, two, none2))
    return programs


def _sorts(text: str) -> list:
    """Operand count of every ``stablehlo.sort`` of a lowered program."""
    import re
    return sorted(len(s.split(",")) for s in re.findall(
        r'"stablehlo.sort"\(([^)]*)\)', text))


def test_flags_and_row_gathers_carry_their_stage(env1):
    """A count program lowers NO scatter and no scan, the stable rank sort
    under ``cylon.sort_keys`` and the one-operand sort of the kept
    positions under ``cylon.compact``, its flags under
    ``cylon.setop_flags``; a materialize program lowers no sort and no
    scatter either, and its row gather under ``cylon.gather_rows``."""
    from cylon_tpu.analysis.registry import unwrap
    from cylon_tpu.utils import stages
    assert "setop_flags" in stages.STAGES
    for name, (prog, args) in _cell_programs(env1.mesh).items():
        text = unwrap(prog).lower(*args).as_text(debug_info=True)
        locs = [ln for ln in text.splitlines() if ln.startswith("#loc")]
        for word in ("scatter", "cumsum", "reduce_window", "segment_"):
            assert word not in text, (name, word)
        if name.endswith("_count"):
            assert _sorts(text) == [1, 3 if name.startswith("unique") else 4]
            assert any("cylon.setops/cylon.sort_keys/sort" in ln
                       for ln in locs), name
            assert any("cylon.setops/cylon.compact/sort" in ln
                       for ln in locs), name
            assert any("cylon.setop_flags" in ln for ln in locs), name
            assert not any("gather_rows" in ln for ln in locs), name
        else:
            assert _sorts(text) == [], name
            assert any("cylon.gather_rows" in ln and "gather" in
                       ln.rpartition("cylon.gather_rows")[2]
                       for ln in locs), name
            assert not any("cylon.setop_flags" in ln or "cylon.sort_keys"
                           in ln for ln in locs), name


# ---- a key whose bounds fit int32 is ONE sort operand (PR 48) --------------
# (XLA:TPU compiles a sort in time that grows with its operands: at the
# cell's size the parent's sorts of 4 / 6 operands compiled cold past the
# check's stop; PERF.md §6, PR 48)

@pytest.mark.parametrize("narrow,unique_ops,setop_ops", [
    ((True, True), 3, 4),       # liveness, k, [v,] idx
    ((False, True), 4, 5),      # k as (hi, lo)
    ((False, False), 4, 6)])
def test_sort_operands_follow_the_bounds(env1, narrow, unique_ops, setop_ops):
    """The rank sort's operands in the count programs, beside the
    one-operand sort of the kept positions; no sort at all in the
    materialize programs, whose lanes follow the same bounds."""
    from cylon_tpu.analysis.registry import unwrap
    for name, (prog, args) in _cell_programs(env1.mesh, narrow).items():
        sorts = _sorts(unwrap(prog).lower(*args).as_text())
        if name.endswith("_mat"):
            assert sorts == [], name
        else:
            assert sorts == [1, unique_ops if name.startswith("unique")
                             else setop_ops], name


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


# ---- PR 49: the flags are read off the sorted order, the rows move by the
# filter's take: the same rows in the same ORDER as before ---------------------

TYPED = ("k", "n", "f", "s", "d")


def _typed(env, seed: int, n: int):
    """Every kind of column the operators compare and move: a narrow int64,
    a nullable int64, a float32 with ``-0.0`` / ``NaN``, a dictionary
    string, a nullable float64 (laneless: a side gather) - few enough
    distinct rows that most repeat.  The host rows come back with it."""
    from cylon_tpu.core.column import Column
    from cylon_tpu.core.dtypes import LogicalType
    rng = np.random.default_rng(seed)
    host = {
        "k": rng.integers(0, 6, n).astype(np.int64),
        "n": rng.integers(0, 2, n).astype(np.int64),
        "nv": rng.random(n) < 0.7,
        "f": rng.choice(np.array([-0.0, 0.0, np.nan, 1.5], np.float32), n),
        "s": rng.choice(np.array(["x", "y", "zz"], object), n),
        "d": rng.choice(np.array([-0.0, 0.0, np.nan, 2.5]), n),
        "dv": rng.random(n) < 0.8,
    }
    table = ct.Table.from_pydict({
        "k": host["k"],
        "n": Column(host["n"], LogicalType.INT64, host["nv"], bounds=(0, 1)),
        "f": host["f"], "s": host["s"],
        "d": Column(host["d"], LogicalType.FLOAT64, host["dv"]),
    }, env)
    return table


def _shard_rows(table) -> list:
    """A list a shard of the table's live rows, in order: ``(identity,
    bits)`` - what two rows are compared by (nulls equal whatever they
    hold, ``-0.0 == 0.0``, ``NaN == NaN``) and what a moved row must still
    hold, bit for bit."""
    h = table.host_columns()
    n = table.row_count
    words = np.asarray(table.column("s").dictionary)

    def valid(c):
        return np.ones(n, bool) if h[c][1] is None else np.asarray(h[c][1])

    def canon(x):
        return "nan" if np.isnan(x) else float(x) + 0.0
    rows = []
    for i in range(n):
        nv, dv = bool(valid("n")[i]), bool(valid("d")[i])
        f, d = h["f"][0][i], h["d"][0][i]
        s = str(words[h["s"][0][i]])
        ident = (int(h["k"][0][i]), int(h["n"][0][i]) if nv else None,
                 canon(f), s, canon(d) if dv else None)
        bits = (int(h["k"][0][i]), int(h["n"][0][i]) if nv else None,
                f.tobytes(), s, d.tobytes() if dv else None)
        rows.append((ident, bits))
    ends = np.cumsum(np.asarray(table.valid_counts))
    return [rows[e - c:e] for e, c in zip(ends, table.valid_counts)]


def _reference(op: str, a_rows: list, b_rows: list, subset=None) -> list:
    """One shard's output rows, in order, by the operators' definitions
    written out (``a``'s kept rows in ``a``'s order, then ``b``'s)."""
    def ident(row):
        return row[0] if subset is None else tuple(
            row[0][TYPED.index(c)] for c in subset)
    if op in ("first", "last"):
        order = range(len(a_rows)) if op == "first" \
            else range(len(a_rows) - 1, -1, -1)
        seen, kept = set(), []
        for i in order:
            if ident(a_rows[i]) not in seen:
                seen.add(ident(a_rows[i]))
                kept.append(i)
        return [a_rows[i][1] for i in sorted(kept)]
    in_b = {ident(r) for r in b_rows}
    seen, out = set(), []
    for r in a_rows + (b_rows if op == "union" else []):
        if ident(r) in seen:
            continue
        seen.add(ident(r))
        if op == "union" or (ident(r) in in_b) == (op == "intersect"):
            out.append(r[1])
    return out


@pytest.mark.parametrize("envname", ["env1", "env4"])
@pytest.mark.parametrize("op", OPS + ("first", "last", "first_by_k_s"))
def test_every_operator_keeps_the_rows_and_their_order(request, envname, op):
    """All four operators and both ``keep`` on every column kind, a's
    padding non-empty: the output IS the reference's list, row for row in
    order and bit for bit.  On four chips the reference reads the shards
    ``shuffle_table`` made (the exchange is not what is under test)."""
    from cylon_tpu.relational import repart
    env = request.getfixturevalue(envname)
    a, b = _typed(env, 49, 1500), _typed(env, 50, 1100)
    subset = ["k", "s"] if op == "first_by_k_s" else None
    if op in OPS:
        got = set_operation(a, b, op)
        sa, sb = setops._align_schemas(a, b)
        if env.world_size > 1:
            sa, sb = (repart.shuffle_table(t, list(TYPED)) for t in (sa, sb))
    else:
        got = unique_table(a, subset=subset, keep=op.split("_")[0])
        sa = repart.shuffle_table(a, subset or list(TYPED)) \
            if env.world_size > 1 else a
        sb = sa
    assert int(sa.valid_counts.max()) < sa.capacity       # a has padding
    want = [_reference(op.split("_")[0], ra, rb, subset)
            for ra, rb in zip(_shard_rows(sa), _shard_rows(sb))]
    assert [len(w) for w in want] == list(got.valid_counts)
    assert [r[1] for part in _shard_rows(got) for r in part] \
        == [r for w in want for r in w]
    assert 0 < got.row_count < a.row_count + b.row_count


def _lifted_rule(mesh, out_cap, density):
    """``fused.window_rule`` without its platform and size tests
    (tests/test_filter_windowed.py)."""
    from cylon_tpu.ops import pallas_gather as pg
    if density < pg.MIN_DENSITY:
        return 0, "density_below_floor"
    return pg.pick_window(density), ""


def _mat_dispatches() -> dict:
    return {k: v for k, v in metrics.snapshot().items()
            if k.startswith(("setop_mat_dispatches", "filter_dispatches"))}


#: case -> (operator, key domain)
WINDOWED = {
    "union": ("union", 12_000),
    "subtract": ("subtract", 12_000),
    # b a sample of a's rows, so that most of a's first occurrences stay
    "intersect": ("intersect", 60_000),
    "unique": ("unique", 60_000),
    # late rows mostly repeat an earlier key: the first occurrences thin
    # out down the table and the last tiles span more than the window the
    # whole table's density would ask for (1024)
    "unique_late_repeats": ("unique", 12_000),
}


@pytest.mark.parametrize("case", list(WINDOWED))
def test_rows_move_by_the_windowed_take_where_the_rule_allows(
        env1, monkeypatch, case):
    """The kernel in interpret mode, the rule's platform and size tests
    lifted: with a's padding four windows wide the union still measures a
    tile span inside the window - b's rows are addressed directly behind
    a's LIVE rows, not behind its capacity - and the dispatch counts
    ``windowed`` and returns the plain path's rows in their order.  The
    rule is asked with the sparsest tile's density (first occurrences thin
    out: ``repart.materialize_kept(thinning=True)``), so the window holds
    the widest tile whatever the table's own density."""
    from functools import partial
    import jax
    from cylon_tpu.ops import pallas_gather as pg
    from cylon_tpu.relational import fused, repart
    op, domain = WINDOWED[case]
    rng = np.random.default_rng(49)
    n_a, n_b, cap = 17_000, 16_000, 32_768
    rows_a = {"k": rng.integers(0, domain, n_a).astype(np.int64),
              "v": rng.integers(0, 4, n_a).astype(np.int64)}
    pick = rng.permutation(n_a)[:n_b]
    rows_b = {c: x[pick] for c, x in rows_a.items()} if op == "intersect" \
        else {"k": rng.integers(0, domain, n_b).astype(np.int64),
              "v": rng.integers(0, 4, n_b).astype(np.int64)}
    a, b = (repart.repad_table(ct.Table.from_pydict(rows, env1), cap)
            for rows in (rows_a, rows_b))

    def run():
        return obs.explain_analyze(
            lambda: unique_table(a, subset=["k"]) if op == "unique"
            else set_operation(a, b, op), profile_keys=False)
    plain = run()
    assert plain.roots[0].attrs["path"] == "plain"
    monkeypatch.setattr(fused, "window_rule", _lifted_rule)
    for mod in (repart, setops):
        monkeypatch.setattr(mod, "shard_map",
                            partial(jax.shard_map, check_vma=False))
    before = _mat_dispatches()
    win = run()
    after = _mat_dispatches()
    assert {k: after[k] - before[k] for k in after if after[k] != before[k]} \
        == {'setop_mat_dispatches{path="windowed"}': 1}
    attrs = win.roots[0].attrs
    span, window = attrs["max_tile_span"], attrs["window"]
    assert attrs["path"] == "windowed" and cap - n_a >= 4 * window
    assert 0 < span <= window == pg.pick_window(
        min(attrs["density"], pg.TILE / span))
    assert pg.pick_window(attrs["density"]) == 1024     # the table's own
    assert (span > 1024) == (case == "unique_late_repeats")
    assert attrs["density"] == round(win.result.row_count / (
        n_a + n_b if op == "union" else n_a), 6)
    for name, (d, v) in win.result.host_columns().items():
        assert v is None
        assert np.array_equal(d, plain.result.host_columns()[name][0]), name
    if op == "union":       # b's kept rows came from behind a's padding
        assert win.result.row_count > n_a


@pytest.mark.parametrize("envname", ["env1", "env4"])
def test_plan_nodes_say_the_materialize_dispatch(request, host, envname):
    """``unique`` / ``set_op`` nodes carry the filter node's ``path`` /
    ``window`` / ``density`` / ``max_tile_span``; ``setop_mat_dispatches``
    counts one a call and ``filter_dispatches`` none (the TPC-H cell's
    share reads that family over the whole process)."""
    env = request.getfixturevalue(envname)
    t = _device(env, host)
    before = _mat_dispatches()
    plan = obs.explain_analyze(lambda: (
        unique_table(t["a"], subset=["k"]),
        set_operation(t["a"], t["b"], "union"),
        set_operation(t["a"], t["b"], "intersect")), profile_keys=False)
    after = _mat_dispatches()
    assert {k: after[k] - before[k] for k in after if after[k] != before[k]} \
        == {'setop_mat_dispatches{path="plain",reason="not_tpu"}': 3}
    assert len(before) == 14        # both families whole, from import on
    nodes = [n for n in plan.roots if n.op in ("unique", "set_op")]
    assert len(nodes) == 3
    for node, res in zip(nodes, plan.result):
        assert node.attrs["path"] == "plain" and node.attrs["window"] == 0
        assert 0 < node.attrs["density"] <= 1
        assert node.attrs["max_tile_span"] > 0
        assert node.rows_out == res.row_count
    # the kept share of the fullest shard's live SOURCE rows
    if env.world_size == 1:
        assert [n.attrs["density"] for n in nodes] == [
            round(r.row_count / live, 6) for r, live in
            zip(plan.result, (ROWS, 2 * ROWS, ROWS))]
