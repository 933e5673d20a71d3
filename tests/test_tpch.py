"""TPC-H Q3/Q5 against the pandas oracle (BASELINE.md config 4; reference
validated on TPC-xBB subsets, docs/docs/release/cylon_release_0.4.0.md)."""

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import tpch


@pytest.fixture(params=["env1", "env4"])
def env(request):
    return request.getfixturevalue(request.param)


def test_q3_matches_pandas(env):
    pdfs = tpch.generate_pandas(scale=0.002, seed=3)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = tpch.q3(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q3_pandas(pdfs)
    assert len(got) == len(exp)
    # revenue descending with date tiebreak; float revenue ties are
    # possible in theory but measure-zero with these distributions
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q5_matches_pandas(env):
    pdfs = tpch.generate_pandas(scale=0.002, seed=4)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = tpch.q5(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q5_pandas(pdfs)
    assert len(got) == len(exp)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_generator_cardinalities():
    pdfs = tpch.generate_pandas(scale=0.01, seed=0)
    assert len(pdfs["customer"]) == 1500
    assert len(pdfs["orders"]) == 15000
    assert len(pdfs["nation"]) == 25 and len(pdfs["region"]) == 5
    assert pdfs["lineitem"].l_discount.between(0, 0.1).all()
    # shipdate strictly after orderdate
    li = pdfs["lineitem"]
    od = pdfs["orders"].set_index("o_orderkey").o_orderdate
    assert (li.l_shipdate.to_numpy()
            > od.loc[li.l_orderkey].to_numpy()).all()


def test_q1_matches_pandas(env):
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.002, seed=3)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q1(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q1_pandas(pdfs)
    pd.testing.assert_frame_equal(got, exp[got.columns], check_dtype=False,
                                  check_exact=False, rtol=1e-6)


def test_q6_matches_pandas(env):
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.002, seed=4)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q6(dfs, env=env)
    exp = tpch.q6_pandas(pdfs)
    assert abs(got - exp) <= 1e-6 * max(abs(exp), 1.0), (got, exp)


def test_q4_matches_pandas(env):
    pdfs = tpch.generate_pandas(scale=0.005, seed=7)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = tpch.q4(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q4_pandas(pdfs)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_q10_matches_pandas(env):
    pdfs = tpch.generate_pandas(scale=0.01, seed=8)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = tpch.q10(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q10_pandas(pdfs)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q12_matches_pandas(env):
    pdfs = tpch.generate_pandas(scale=0.01, seed=9)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = tpch.q12(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q12_pandas(pdfs)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_q14_matches_pandas(env):
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.004, seed=14)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q14(dfs, env=env)
    exp = tpch.q14_pandas(pdfs)
    assert got == pytest.approx(exp, rel=1e-9)


def test_q9_matches_pandas(env):
    """Q9 (round 13, the out-of-core tier's wide-join exerciser): six
    tables, five joins incl. the two-key partsupp edge, year-grouped
    profit — bit-checked against the pandas oracle at env1/env4."""
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.002, seed=9)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q9(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q9_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp[got.columns], check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q9_generator_year_column_is_derived():
    """o_orderyear consumes no RNG draws: every pre-round-13 column
    stays byte-identical (the regression-baseline rule)."""
    pdfs = tpch.generate_pandas(scale=0.002, seed=9)
    o = pdfs["orders"]
    assert (o.o_orderyear.to_numpy()
            == o.o_orderdate.dt.year.to_numpy()).all()


def test_q18_matches_pandas(env):
    import cylon_tpu as ct
    # lower HAVING threshold so the tiny scale keeps qualifying orders
    pdfs = tpch.generate_pandas(scale=0.004, seed=18)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q18(dfs, env=env, quantity=150).to_pandas() \
        .reset_index(drop=True)
    exp = tpch.q18_pandas(pdfs, quantity=150)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q19_matches_pandas(env):
    import cylon_tpu as ct
    # Q19's conjunctions select ~1e-5 of lineitem; this scale keeps a
    # handful of qualifying rows so the assertion is non-vacuous
    pdfs = tpch.generate_pandas(scale=0.05, seed=19)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q19(dfs, env=env)
    exp = tpch.q19_pandas(pdfs)
    assert exp != 0.0
    assert got == pytest.approx(exp, rel=1e-9)


@pytest.mark.parametrize("qname", ["q16", "q21", "q22"])
def test_round5_queries_match_pandas(env, qname):
    """Q16/Q21/Q22 — the semi/anti-join query family (round 5)."""
    pdfs = tpch.generate_pandas(scale=0.004, seed=16)
    dfs = {k: __import__("cylon_tpu").DataFrame(v, env=env)
           for k, v in pdfs.items()}
    got = getattr(tpch, qname)(dfs, env=env).to_pandas() \
        .reset_index(drop=True)
    exp = getattr(tpch, f"{qname}_pandas")(pdfs)
    assert len(got) == len(exp)
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q11_matches_pandas(env):
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.004, seed=11)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q11(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q11_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q15_matches_pandas(env):
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.01, seed=15)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q15(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q15_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp, check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q17_matches_pandas(env):
    import cylon_tpu as ct
    # brand x container selects ~1/1000 of parts; this scale keeps a
    # handful of qualifying parts so the assertion is non-vacuous
    pdfs = tpch.generate_pandas(scale=0.02, seed=17)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q17(dfs, env=env)
    exp = tpch.q17_pandas(pdfs)
    assert exp != 0.0
    assert got == pytest.approx(exp, rel=1e-9)


def test_q20_matches_pandas(env):
    import cylon_tpu as ct
    # ~1/6 of parts are forest-named; this scale keeps a non-vacuous
    # supplier set through the nested INs + correlated half-sum
    pdfs = tpch.generate_pandas(scale=0.01, seed=20)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q20(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q20_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_q13_matches_pandas(env):
    """Q13 (round 12) — the LEFT-join count-distribution, bit-checked:
    integer counts compare exactly, including the c_count = 0 bucket the
    left join's null extension produces."""
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.004, seed=13)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q13(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q13_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp, check_dtype=False)


def test_q7_matches_pandas(env):
    """Q7 (round 14, the adaptive skew-split route's TPC-H exerciser):
    lineitem ⋈ supplier/customer ⋈ nation×2 on a 25-value nation key —
    every key a heavy hitter — bit-checked against the pandas oracle at
    env1/env4 with the skew route armed (its default)."""
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.004, seed=7)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q7(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q7_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp[got.columns], check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q8_matches_pandas(env):
    """Q8 (round 15, the multi-slice topology tier's TPC-H exerciser):
    national market share — seven tables chained through six
    shuffle-backed joins, the suite's widest cross-slice working set —
    bit-checked against the pandas oracle at env1/env4 (docs/
    topology.md; the two-tier-route equality legs live in
    tests/test_topo.py)."""
    import cylon_tpu as ct
    pdfs = tpch.generate_pandas(scale=0.004, seed=8)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    got = tpch.q8(dfs, env=env).to_pandas().reset_index(drop=True)
    exp = tpch.q8_pandas(pdfs)
    assert len(got) == len(exp) > 0
    pd.testing.assert_frame_equal(got, exp[got.columns], check_dtype=False,
                                  check_exact=False, rtol=1e-9)


def test_q7_generator_year_column_is_derived():
    """l_shipyear consumes no RNG draws: every pre-round-14 column
    stays byte-identical (the regression-baseline rule)."""
    pdfs = tpch.generate_pandas(scale=0.002, seed=7)
    li = pdfs["lineitem"]
    assert (li.l_shipyear.to_numpy()
            == li.l_shipdate.dt.year.to_numpy()).all()


def test_q18_explain_analyze_records_plan(env):
    """Round 14: the naturally skew-shaped Q18's ANALYZE tree (recorded
    as q18_plan in the tpch bench detail) carries its join route
    decisions — with the skew route armed, every distributed join node
    names a route and any skew_split node carries the voted plan
    summary."""
    import cylon_tpu as ct
    from cylon_tpu import obs
    pdfs = tpch.generate_pandas(scale=0.004, seed=18)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    qp = obs.explain_analyze(
        lambda: tpch.q18(dfs, env=env, quantity=150).to_pandas())
    d = qp.to_dict()
    assert d["roots"], "no plan nodes recorded"
    joins = []

    def walk(n):
        if n["op"] == "join":
            joins.append(n)
        for c in n.get("children", ()):
            walk(c)
    for r in d["roots"]:
        walk(r)
    assert joins, "Q18 recorded no join nodes"
    for n in joins:
        attrs = n.get("attrs", {})
        if attrs.get("route") == "skew_split":
            plan = attrs.get("skew_plan")
            assert plan and plan.get("plan_hash") and plan.get("fanout")


def test_q13_explain_analyze_records_plan(env):
    """The profiler's acceptance workload: EXPLAIN ANALYZE of Q13 at
    SF0.01 produces a plan tree whose per-node seconds reconcile with
    the global phase table (per-region equality up to fp summation) and
    whose exchange bytes equal the always-on exchange counters."""
    import cylon_tpu as ct
    from cylon_tpu import obs
    from cylon_tpu.obs import metrics
    pdfs = tpch.generate_pandas(scale=0.01, seed=13)
    dfs = {k: ct.DataFrame(v, env=env) for k, v in pdfs.items()}
    rows0 = metrics.counter("exchange_rows_total").value
    bytes0 = metrics.counter("exchange_bytes_total").value
    qp = obs.explain_analyze(lambda: tpch.q13(dfs, env=env).to_pandas())
    d = qp.to_dict()
    assert d["roots"], "no plan nodes recorded"
    ops = set()

    def walk(n):
        ops.add(n["op"])
        for c in n.get("children", ()):
            walk(c)
    for r in d["roots"]:
        walk(r)
    assert "join" in ops and "groupby" in ops and "sort" in ops
    rec = d["reconcile"]
    # per-node seconds reconcile with the global phase table: every
    # region second landed in exactly one node's self table
    assert rec["node_s"] <= rec["phase_s"] + 1e-6
    assert abs(rec["unattributed_s"]) <= max(0.05 * rec["phase_s"], 0.02)
    for name, s in rec["per_phase_node_s"].items():
        assert s == pytest.approx(d["global_phases"][name]["s"],
                                  rel=1e-4, abs=2e-3), name
    # exchange bytes attributed to nodes == the counter deltas
    def sum_xchg(n):
        return (n.get("bytes_exchanged", 0)
                + sum(sum_xchg(c) for c in n.get("children", ())))
    node_bytes = sum(sum_xchg(r) for r in d["roots"])
    assert node_bytes == metrics.counter("exchange_bytes_total").value \
        - bytes0
    if env.world_size == 1:
        assert metrics.counter("exchange_rows_total").value == rows0


def test_round12_generator_addition():
    pdfs = tpch.generate_pandas(scale=0.01, seed=0)
    o = pdfs["orders"]
    assert "o_comment" in o.columns
    assert set(o.o_comment.unique()) <= {"special requests", "ok"}
    assert (o.o_comment == "special requests").any()
    # the new column rides an independent stream: the previously
    # generated columns stay byte-identical (regression-baseline rule)
    assert o.o_totalprice.sum() == tpch.generate_pandas(
        scale=0.01, seed=0)["orders"].o_totalprice.sum()


def test_round9_generator_addition():
    pdfs = tpch.generate_pandas(scale=0.01, seed=0)
    p = pdfs["part"]
    assert "p_name" in p.columns
    assert p.p_name.str.startswith("forest").any()
    assert set(p.p_name.unique()) <= set(tpch.PNAMES.tolist())
    # the new column rides an independent stream: the previously
    # generated columns stay byte-identical (regression-baseline rule)
    assert p.p_size.sum() == tpch.generate_pandas(
        scale=0.01, seed=0)["part"].p_size.sum()


def test_round7_generator_addition():
    pdfs = tpch.generate_pandas(scale=0.01, seed=0)
    ps = pdfs["partsupp"]
    assert "ps_supplycost" in ps.columns
    assert ps.ps_supplycost.between(1.0, 1000.0).all()
    # the new column rides an independent stream: the previously
    # generated columns stay byte-identical (regression-baseline rule)
    assert ps.ps_availqty.sum() == tpch.generate_pandas(
        scale=0.01, seed=0)["partsupp"].ps_availqty.sum()


def test_round5_generator_additions():
    pdfs = tpch.generate_pandas(scale=0.01, seed=0)
    assert len(pdfs["partsupp"]) == 4 * len(pdfs["part"])
    assert set(pdfs["orders"].o_orderstatus) <= {"F", "O", "P"}
    s = pdfs["supplier"]
    assert {"s_name", "s_comment"} <= set(s.columns)
    c = pdfs["customer"]
    assert (c.c_cntrycode == c.c_nationkey + 10).all()
    assert (c.c_phone.str.split("-").str[0].astype(int)
            == c.c_nationkey + 10).all()


def test_tpch_out_of_core_disk_tier_bit_equal(env4, monkeypatch, tmp_path):
    """The ISSUE-13 acceptance shape at CI scale: a TPC-H-shaped
    pipelined join+groupby (lineitem ⋈ orders, the Q3/Q9 spine) under
    CYLON_TPU_HBM_BUDGET + CYLON_TPU_HOST_BUDGET caps sized below its
    working set completes BIT-EQUAL to the uncapped run, with
    disk_events > 0 and bytes_to_disk > 0 — the whole residency ladder
    (device → host → spill files → mmap windows) under a real TPC-H
    data distribution.  The full-scale run is `bench.py --tpch` under
    the same env caps; the subprocess legs live in
    `scripts/chaos_soak.py --oocore`."""
    import cylon_tpu as ct
    from cylon_tpu import config
    from cylon_tpu.exec import GroupBySink, memory, pipelined_join, recovery
    pdfs = tpch.generate_pandas(scale=0.002, seed=13)
    li = ct.Table.from_pandas(
        pdfs["lineitem"][["l_orderkey", "l_quantity"]], env4)
    o = ct.Table.from_pandas(
        pdfs["orders"][["o_orderkey", "o_orderyear"]], env4)

    def run():
        sink = GroupBySink("o_orderyear", [("l_quantity", "sum")])
        pipelined_join(li, o, "l_orderkey", "o_orderkey", how="inner",
                       n_chunks=4, sink=sink)
        return (sink.finalize().to_pandas().sort_values("o_orderyear")
                .reset_index(drop=True))

    base = run()
    import gc
    gc.collect()
    memory.reset_stats()
    recovery.reset_events()
    monkeypatch.setattr(config, "HBM_BUDGET_BYTES", 4096)
    monkeypatch.setattr(config, "HOST_BUDGET_BYTES", 4096)
    monkeypatch.setattr(config, "SPILL_DIR", str(tmp_path / "spill"))
    capped = run()
    st = memory.stats()
    assert st["disk_events"] > 0 and st["bytes_to_disk"] > 0, st
    assert recovery.recovery_events() == []   # degraded, not escalated
    pd.testing.assert_frame_equal(capped, base)   # bit-equal


# ---------------------------------------------------------------------------
# PR 41: the spec's DECIMAL money, exact
# ---------------------------------------------------------------------------

def _plain_q3_q5(pdfs, limit=10):
    """Q3 and Q5 written out on integer cents with direct indexing (keys
    are dense): nothing of the engine, nothing of ``q*_pandas``."""
    c, o, l = pdfs["customer"], pdfs["orders"], pdfs["lineitem"]
    cents = np.rint(l.l_extendedprice.to_numpy() * 100).astype(np.int64)
    disc = np.rint(l.l_discount.to_numpy() * 100).astype(np.int64)
    rev = cents * (100 - disc)                                  # scale 4
    okey = l.l_orderkey.to_numpy()
    odate = o.o_orderdate.to_numpy()
    ocust = o.o_custkey.to_numpy()
    d = np.datetime64("1995-03-15")
    seg = (c.c_mktsegment.to_numpy() == "BUILDING")[ocust]
    rows = np.flatnonzero((l.l_shipdate.to_numpy() > d)
                          & ((odate < d) & seg)[okey])
    sums = np.zeros(len(o), np.int64)
    np.add.at(sums, okey[rows], rev[rows])
    keys = np.unique(okey[rows])
    order = np.lexsort((keys, odate[keys], -sums[keys]))[:limit]
    q3 = (keys[order], sums[keys][order], odate[keys][order])
    lo, hi = np.datetime64("1994-01-01"), np.datetime64("1995-01-01")
    asia = (tpch.NATION_REGION == list(tpch.REGIONS).index("ASIA"))
    cnat = np.where((odate >= lo) & (odate < hi),
                    c.c_nationkey.to_numpy()[ocust], -1)[okey]
    snat = pdfs["supplier"].s_nationkey.to_numpy()[l.l_suppkey.to_numpy()]
    rows = np.flatnonzero((cnat == snat) & asia[np.maximum(cnat, 0)])
    by_nation = np.zeros(25, np.int64)
    np.add.at(by_nation, cnat[rows], rev[rows])
    nations = np.flatnonzero(np.bincount(cnat[rows], minlength=25))
    order = np.argsort(-by_nation[nations], kind="stable")
    return q3, (tpch.NATIONS[nations][order], by_nation[nations][order])


def _scaled(series, scale=4):
    return [int(v.scaleb(scale)) for v in series]


@pytest.mark.parametrize("seed", [1, 5])
def test_q3_q5_on_decimal_tables_are_exact(env, seed):
    """``money="decimal"`` at SF 0.01: every cell equals the plain
    reference written out above (exact scaled integers at scale 4), and
    the float path's answer to 1e-9 relative; ``revenue`` is DECIMAL at
    scale 4 and its sums scan as ``val32`` (the derived bounds hold)."""
    from cylon_tpu import LogicalType
    from cylon_tpu.obs import metrics
    pdfs = tpch.generate_pandas(scale=0.01, seed=seed)
    dd = tpch.generate_tables(0.01, env, seed=seed, money="decimal")
    for name, cols in tpch.MONEY.items():
        for c in cols:
            col = dd[name].table.column(c)
            assert col.type == LogicalType.DECIMAL
            assert (col.dictionary.precision, col.dictionary.scale) == (15, 2)
    scans = 'grouped_sum_scans{form="%s"}'
    before = metrics.snapshot()
    r3, r5 = tpch.q3(dd, env=env), tpch.q5(dd, env=env)
    after = metrics.snapshot()
    assert after[scans % "val32"] - before[scans % "val32"] == 2
    # across shards the second phase sums partial sums, whose bounds
    # nobody knows: one pair64 scan a query there, none on one device
    assert after[scans % "pair64"] - before[scans % "pair64"] == (
        0 if env.world_size == 1 else 2)
    for r in (r3, r5):
        col = r.table.column("revenue")
        assert col.type == LogicalType.DECIMAL and col.dictionary.scale == 4
    g3, g5 = r3.to_pandas(), r5.to_pandas()
    (k3, s3, d3), (n5, s5) = _plain_q3_q5(pdfs)
    assert list(g3.l_orderkey) == list(k3)
    assert _scaled(g3.revenue) == list(s3)
    assert list(g3.o_orderdate.to_numpy().astype("datetime64[ns]")) \
        == list(d3.astype("datetime64[ns]"))
    assert list(g5.n_name) == list(n5) and _scaled(g5.revenue) == list(s5)
    f3, f5 = tpch.q3_pandas(pdfs), tpch.q5_pandas(pdfs)
    np.testing.assert_allclose(g3.revenue.astype(float), f3.revenue,
                               rtol=1e-9)
    np.testing.assert_allclose(g5.revenue.astype(float), f5.revenue,
                               rtol=1e-9)
    assert list(g3.l_orderkey) == list(f3.l_orderkey)
    assert list(g5.n_name) == list(f5.n_name)


def test_q3_limit(env1):
    """``limit=None`` is every group, in Q3's order; the default is 10."""
    pdfs = tpch.generate_pandas(scale=0.01, seed=2)
    dd = tpch.generate_tables(0.01, env1, seed=2, money="decimal")
    every = tpch.q3(dd, limit=None).to_pandas()
    (k, s, _d), _ = _plain_q3_q5(pdfs, limit=None)
    assert len(every) == len(k) > 10
    assert list(every.l_orderkey) == list(k)
    assert _scaled(every.revenue) == list(s)
    assert len(tpch.q3(dd)) == 10 and len(tpch.q3(dd, limit=3)) == 3


def test_money_argument():
    with pytest.raises(ValueError):
        tpch.generate_tables(0.001, money="cents")
