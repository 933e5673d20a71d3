"""Fused join→groupby pushdown (relational/fused.py) and deferred join
materialization (core.table.DeferredTable).

Reference analog: the streaming operator DAG (cpp/src/cylon/ops/ — DisJoinOP
composing into downstream ops without materialized intermediates, SURVEY §2
C9).  The fused result must be EXACTLY what materialize-then-groupby
produces; the join must stay unmaterialized when (and only when) every
aggregation reduces to multiplicity algebra over the sorted state.
"""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.core.table import DeferredTable
from cylon_tpu.relational import groupby_aggregate, join_tables

from utils import assert_table_matches


def _tables(env, rng, n=6000, nkey=700, nulls=False):
    a = rng.integers(0, 100, n).astype(np.int64)
    ldf = pd.DataFrame({"k": rng.integers(0, nkey, n).astype(np.int64),
                        "a": a})
    rdf = pd.DataFrame({"k": rng.integers(0, nkey, n).astype(np.int64),
                        "b": rng.integers(0, 100, n).astype(np.int64)})
    if nulls:
        ldf["a"] = ldf["a"].astype("Int64")
        ldf.loc[::7, "a"] = pd.NA
    return ldf, rdf


def _join(env, ldf, rdf):
    lt = ct.Table.from_pandas(ldf, env)
    rt = ct.Table.from_pandas(rdf, env)
    return join_tables(lt, rt, "k", "k", how="inner")


@pytest.mark.parametrize("world", ["env1", "env4", "env8"])
def test_fused_matches_pandas_all_pushdown_ops(world, request, rng):
    env = request.getfixturevalue(world)
    ldf, rdf = _tables(env, rng)
    j = _join(env, ldf, rdf)
    assert isinstance(j, DeferredTable) and not j.materialized
    g = groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum"),
                                   ("a", "mean"), ("b", "count"),
                                   ("a", "var"), ("b", "std")])
    assert not j.materialized, "pushdown must not materialize the join"
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(a_sum=("a", "sum"), b_sum=("b", "sum"), a_mean=("a", "mean"),
               b_count=("b", "count"), a_var=("a", "var"),
               b_std=("b", "std")))
    assert_table_matches(g, eg)


def test_fused_equals_unfused(env4, rng):
    """The fused answer must equal the materialize-then-groupby answer."""
    ldf, rdf = _tables(env4, rng)
    aggs = [("a", "sum"), ("b", "mean"), ("a", "count")]
    j1 = _join(env4, ldf, rdf)
    fused = groupby_aggregate(j1, "k", aggs)
    assert not j1.materialized
    j2 = _join(env4, ldf, rdf)
    j2.columns  # force materialization -> normal grouped fast path
    assert j2.materialized
    normal = groupby_aggregate(j2, "k", aggs)
    fp = fused.to_pandas().sort_values("k").reset_index(drop=True)
    np_ = normal.to_pandas().sort_values("k").reset_index(drop=True)
    pd.testing.assert_frame_equal(fp, np_, check_dtype=False, rtol=1e-12)


def test_null_values_in_aggregated_column(env4, rng):
    ldf, rdf = _tables(env4, rng, nulls=True)
    j = _join(env4, ldf, rdf)
    g = groupby_aggregate(j, "k", [("a", "sum"), ("a", "count"),
                                   ("a", "mean")])
    assert not j.materialized
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(a_sum=("a", "sum"), a_count=("a", "count"),
               a_mean=("a", "mean")))
    eg["a_sum"] = eg["a_sum"].astype(np.int64)
    # Float64 extension NA -> float64 NaN (the framework's null-float
    # rendering; the fused and materialize paths agree exactly)
    eg["a_mean"] = eg["a_mean"].astype(np.float64)
    assert_table_matches(g, eg)


def test_non_pushdown_op_materializes_and_matches(env4, rng):
    """min/max are not multiplicity-algebraic: the groupby must fall back
    to the materialize path and still be correct."""
    ldf, rdf = _tables(env4, rng)
    j = _join(env4, ldf, rdf)
    g = groupby_aggregate(j, "k", [("a", "sum"), ("a", "min"),
                                   ("b", "max")])
    assert j.materialized
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(a_sum=("a", "sum"), a_min=("a", "min"), b_max=("b", "max")))
    assert_table_matches(g, eg)


def test_groupby_on_non_key_column_materializes(env4, rng):
    ldf, rdf = _tables(env4, rng)
    j = _join(env4, ldf, rdf)
    g = groupby_aggregate(j, "a", [("b", "sum")])
    assert j.materialized
    ej = ldf.merge(rdf, on="k")
    eg = ej.groupby("a", as_index=False).agg(b_sum=("b", "sum"))
    assert_table_matches(g, eg)


def test_agg_on_key_column_itself(env4, rng):
    ldf, rdf = _tables(env4, rng)
    j = _join(env4, ldf, rdf)
    g = groupby_aggregate(j, "k", [("k", "count"), ("a", "sum")])
    assert not j.materialized
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(k_count=("k", "count"), a_sum=("a", "sum")))
    assert_table_matches(g, eg)


def test_deferred_schema_queries_do_not_materialize(env4, rng):
    ldf, rdf = _tables(env4, rng)
    j = _join(env4, ldf, rdf)
    assert j.column_names == ["k", "a", "b"]
    assert j.column_count == 3
    assert "a" in j and "zzz" not in j
    assert len(j.schema) == 3
    assert j.row_count == len(ldf.merge(rdf, on="k"))
    assert j.capacity > 0
    assert not j.materialized
    # data access materializes
    _ = j.column("a")
    assert j.materialized


def test_deferred_via_dataframe_api(env4, rng):
    """DataFrame.merge -> .groupby on the join keys rides the fused path
    end-to-end through the public API."""
    ldf, rdf = _tables(env4, rng)
    lf = ct.DataFrame(ldf, env=env4)
    rf = ct.DataFrame(rdf, env=env4)
    m = lf.merge(rf, on="k", env=env4)
    g = (m.groupby("k", env=env4)[["a", "b"]].sum()).to_pandas()
    assert not m._table.materialized, \
        "DataFrame terminal agg must ride the fused path, not materialize"
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(a_sum=("a", "sum"), b_sum=("b", "sum")))
    g = g.sort_values("k").reset_index(drop=True)
    eg.columns = g.columns
    pd.testing.assert_frame_equal(g, eg.sort_values("k").reset_index(drop=True),
                                  check_dtype=False)


def test_defer_flag_off_restores_eager_join(env4, rng):
    """The eager join as its real callers ask for it
    (``exec/pipeline.py``: a sink-less chunk join): ``allow_defer=False``."""
    ldf, rdf = _tables(env4, rng)
    j = join_tables(ct.Table.from_pandas(ldf, env4),
                    ct.Table.from_pandas(rdf, env4), "k", "k", how="inner",
                    allow_defer=False)
    assert not isinstance(j, DeferredTable)
    g = groupby_aggregate(j, "k", [("a", "sum")])
    ej = ldf.merge(rdf, on="k")
    assert_table_matches(g, ej.groupby("k", as_index=False)
                         .agg(a_sum=("a", "sum")))


def test_fused_ddof(env4, rng):
    ldf, rdf = _tables(env4, rng)
    j = _join(env4, ldf, rdf)
    g = groupby_aggregate(j, "k", [("a", "var"), ("a", "std")], ddof=0)
    assert not j.materialized
    ej = ldf.merge(rdf, on="k")
    eg = (ej.groupby("k", as_index=False)
          .agg(a_var=("a", lambda x: x.var(ddof=0)),
               a_std=("a", lambda x: x.std(ddof=0))))
    eg.columns = ["k", "a_var", "a_std"]
    assert_table_matches(g, eg)


def test_f64_columns_carry_lite(env4, rng):
    """Carry-LITE: f64 output columns no longer disqualify the join's lane
    carriage — the join defers, laneable columns ride the sort, f64
    columns gather by take index.  A pushdown over an f64 value column is
    gated (not in the sorted lanes) and falls back to materialization."""
    n = 5000
    ldf = pd.DataFrame({"k": rng.integers(0, 600, n).astype(np.int64),
                        "a": rng.integers(0, 50, n).astype(np.int64),
                        "x": rng.random(n)})
    rdf = pd.DataFrame({"k": rng.integers(0, 600, n).astype(np.int64),
                        "b": rng.integers(0, 50, n).astype(np.int64),
                        "y": rng.random(n)})
    lt = ct.Table.from_pandas(ldf, env4)
    rt = ct.Table.from_pandas(rdf, env4)
    j = join_tables(lt, rt, "k", "k", how="inner")
    assert isinstance(j, DeferredTable) and not j.materialized
    ej = ldf.merge(rdf, on="k")
    # pushdown over the laneable column only: stays deferred
    g1 = groupby_aggregate(j, "k", [("a", "sum")])
    assert not j.materialized
    e1 = ej.groupby("k", as_index=False).agg(a_sum=("a", "sum"))
    assert_table_matches(g1, e1)
    # f64 value column: gated out of the pushdown, materializes, correct
    g2 = groupby_aggregate(j, "k", [("x", "sum"), ("y", "mean")])
    assert j.materialized
    e2 = ej.groupby("k", as_index=False).agg(x_sum=("x", "sum"),
                                             y_mean=("y", "mean"))
    assert_table_matches(g2, e2)
    # full materialized join equals pandas (f64 columns via carry-lite)
    keycols = ["k", "a", "x", "b", "y"]
    got = j.to_pandas().sort_values(keycols).reset_index(drop=True)
    exp = ej.sort_values(keycols).reset_index(drop=True)
    pd.testing.assert_frame_equal(got[exp.columns], exp, check_dtype=False,
                                  rtol=1e-12)


# ---- ISSUE 28: the numpy reference, first sight, counts as int32 scans ------

def _np_join_groupby_sum(lk, a, rk, b):
    """Plain numpy: inner join on the key, sum(a) and sum(b) per key over
    the joined rows, rows by key.  A side's row appears once per row of the
    other side with its key."""
    n_keys = int(max(lk.max(), rk.max())) + 1
    lc = np.bincount(lk, minlength=n_keys)
    rc = np.bincount(rk, minlength=n_keys)
    sa = np.zeros(n_keys, np.int64)
    sb = np.zeros(n_keys, np.int64)
    np.add.at(sa, lk, a)
    np.add.at(sb, rk, b)
    keys = np.flatnonzero((lc > 0) & (rc > 0))
    return keys.astype(np.int64), (sa * rc)[keys], (sb * lc)[keys]


def _draw_keys(rng, dist: str, n: int):
    if dist == "uniform":          # the benchmark's: density ~0.2
        return rng.integers(0, int(0.9 * n), n).astype(np.int64)
    # Zipf s = 1.1: a few huge groups, group density far under the
    # windowed gather's floor (ops/pallas_gather.MIN_DENSITY)
    return np.minimum(rng.zipf(1.1, n) - 1, 10 * n).astype(np.int64)


@pytest.mark.parametrize("world", ["env1", "env4"])
@pytest.mark.parametrize("dist", ["uniform", "zipf"])
@pytest.mark.parametrize("n", [5000, 4096])
def test_fused_sums_equal_numpy_reference(world, dist, n, request):
    """join -> groupby-sum through the public entry points, cell by cell
    against numpy: uniform keys and a low-density key set, shards padded
    (5000 rows pad to the shape family) and exactly full, one device and
    the 4-device mesh (shuffled first), first sight and the cached
    segment space (the second call)."""
    env = request.getfixturevalue(world)
    rng = np.random.default_rng([28, n, dist == "zipf"])
    lk, rk = _draw_keys(rng, dist, n), _draw_keys(rng, dist, n)
    a = rng.integers(-1000, 1000, n).astype(np.int64)
    b = rng.integers(0, 1 << 40, n).astype(np.int64)   # needs both lanes
    lt = ct.Table.from_pydict({"k": lk, "a": a}, env)
    rt = ct.Table.from_pydict({"k": rk, "b": b}, env)
    wk, wa, wb = _np_join_groupby_sum(lk, a, rk, b)
    for _call in range(2):
        g = groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                              "k", [("a", "sum"), ("b", "sum")])
        got = g.to_pandas().sort_values("k", kind="stable")
        assert np.array_equal(np.asarray(got["k"]), wk)
        assert np.array_equal(np.asarray(got["a_sum"]), wa)
        assert np.array_equal(np.asarray(got["b_sum"]), wb)


@pytest.mark.parametrize("world", ["env1", "env4"])
def test_first_sight_counts_what_the_settled_program_counts(world, request):
    """The first dispatch of a callsite runs at the 512-slot segment space
    only to learn n_groups; the redispatch at the space chosen from that
    count reports, shard by shard, the same count."""
    from cylon_tpu.relational import fused
    from cylon_tpu.relational.groupby import _FIRST_SEG_CAP
    env = request.getfixturevalue(world)
    rng = np.random.default_rng(281)
    n = 20011     # a callsite signature no other test of this file has;
                  # over 512 groups a shard at world 4 too
    lt = ct.Table.from_pydict({"k": _draw_keys(rng, "uniform", n),
                               "a": rng.integers(0, 99, n)}, env)
    rt = ct.Table.from_pydict({"k": _draw_keys(rng, "uniform", n),
                               "b": rng.integers(0, 99, n)}, env)
    seen = []
    real = fused._fused_fn

    def builder(mesh, *static, **kw):
        prog = real(mesh, *static, **kw)

        def call(*args):
            out = prog(*args)
            seen.append((static[8], np.asarray(out[4]).reshape(-1, 2)[:, 0]))
            return out
        return call

    try:
        fused._fused_fn = builder
        g = groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"),
                              "k", [("a", "sum"), ("b", "sum")])
    finally:
        fused._fused_fn = real
    (first_seg, first_n), (seg, n_groups) = seen
    assert first_seg == _FIRST_SEG_CAP < int(first_n.max()) <= seg
    assert np.array_equal(first_n, n_groups)
    assert int(n_groups.sum()) == g.row_count > 0


def _cumsums(traced):
    """(accumulator dtype, scan length) of every cumsum equation."""
    from cylon_tpu.analysis.jaxpr_check import iter_eqns
    return [(str(e.outvars[0].aval.dtype),
             e.invars[0].aval.shape[e.params["axis"]])
            for e, _ in iter_eqns(traced) if e.primitive.name == "cumsum"]


@pytest.mark.parametrize("world", ["env1", "env4"])
def test_fused_program_keeps_wide_scans_from_the_rewriter(world, request):
    """Two rules about the fused program's prefix sums, from the death of
    XLA:TPU's scan rewriter on a 4-device mesh (PERF.md, PR 28: it dies
    rewriting the (hi, lo) variadic reduce-windows that long 64-bit scans
    lower to, about four of them in one program).  A count never passes the
    row count, so its prefix is an int32 scan on every mesh (also 112 ms
    of a 1.57 s query on one chip).  And on a mesh of more than one device
    every 64-bit prefix sum is a ``blocked_cumsum``: no 64-bit scan is
    longer than the 128 elements the rewriter leaves alone."""
    import jax
    from cylon_tpu.analysis import registry
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import fused
    env = request.getfixturevalue(world)
    w, cap = env.world_size, 1024
    lspec = lanes.plan_lanes(("int64", "int64"), (False, False),
                             (True, False))
    rspec = lanes.plan_lanes(("int64",), (False,), (False,))
    from cylon_tpu.ops import join as joink
    # key k narrow -> the sorted key operand; a's (hi, lo) share two
    # operands with b's
    layout = joink.payload_layout(lspec, rspec, (0,), ("int64",), (False,),
                                  (True,), False)
    assert layout.n_arrays == 3 and layout.sort_operands == 5
    fn = fused._fused_fn(env.mesh, cap, False, lspec, rspec, layout,
                         (("l", 1, "sum"), ("r", 0, "sum"), ("l", 1, "mean")),
                         (0,), (True,), 512, 1)
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int64)
    row = S((w * 2 * cap,), np.int32)
    pl = (row,) + tuple(S((w * 2 * cap,), np.uint32) for _ in range(2))
    traced = jax.make_jaxpr(registry.unwrap(fn))(vc, vc, row, row, pl)
    scans = _cumsums(traced)
    long_ = sorted(dt for dt, n in scans if n > 128)
    # int32, over all 2 * cap rows: the geometry's three cumsums and three
    # counts (the two multiplicities, the mean's)
    assert long_.count("int32") == 6, scans
    wide = [dt for dt in long_ if dt != "int32"]
    if w == 1:
        # as XLA's rewriter gets them: two int64 sums, the mean's f64 sum
        assert wide == ["float64", "int64", "int64"], scans
    else:
        assert wide == [], scans
        assert {dt for dt, n in scans if n <= 128} == {"int64", "float64"}


def test_blocked_cumsum_equals_cumsum(rng):
    """Bit for bit on int64 (sums past 2^32, negative values, a length that
    is no multiple of 128 and one that needs three levels), to rounding on
    float64."""
    import jax.numpy as jnp
    from cylon_tpu.ops.groupby import blocked_cumsum
    for n in (1, 128, 129, 5000, 128 * 128 + 7, 128 ** 3 + 1):
        x = rng.integers(-(1 << 40), 1 << 40, n).astype(np.int64)
        assert np.array_equal(np.asarray(blocked_cumsum(jnp.asarray(x))),
                              np.cumsum(x)), n
    f = rng.normal(size=70001)
    np.testing.assert_allclose(np.asarray(blocked_cumsum(jnp.asarray(f))),
                               np.cumsum(f), rtol=1e-12, atol=1e-9)


@pytest.mark.parametrize("world", ["env1", "env4"])
def test_every_grouped_reduce_is_told_the_mesh(world, request, monkeypatch):
    """The rule is one statement (relational/common.multi_shard) and every
    caller of ``grouped_reduce`` passes it: the fused program, the
    standalone groupby's per-shard reduce and its distributed final
    step."""
    from cylon_tpu.ops import groupby as gbk
    env = request.getfixturevalue(world)
    told = []
    real = gbk.grouped_reduce

    def spy(*args, **kwargs):
        told.append(kwargs.get("blocked_scans"))
        return real(*args, **kwargs)

    monkeypatch.setattr(gbk, "grouped_reduce", spy)
    rng = np.random.default_rng(283)
    n = 7001      # shapes no other test has: the builders trace here
    lt = ct.Table.from_pydict({"k": rng.integers(0, n // 3, n),
                               "a": rng.integers(-(1 << 40), 1 << 40, n)},
                              env)
    rt = ct.Table.from_pydict({"k": rng.integers(0, n // 3, n),
                               "b": rng.integers(0, 99, n)}, env)
    groupby_aggregate(join_tables(lt, rt, "k", "k", how="inner"), "k",
                      [("a", "sum"), ("b", "mean")]).to_pandas()
    fused_calls = len(told)
    got = groupby_aggregate(lt, "k", [("a", "sum")]).to_pandas()
    assert fused_calls >= 1 and len(told) > fused_calls
    assert told == [env.world_size > 1] * len(told)
    want = lt.to_pandas().groupby("k")["a"].sum()
    assert np.array_equal(got.sort_values("k")["a_sum"], want.sort_index())
