"""TPC-H Q3/Q5 against the pandas oracle (BASELINE.md config 4; reference
validated on TPC-xBB subsets, docs/docs/release/cylon_release_0.4.0.md),
the generator, and the disk tier under a TPC-H distribution.  The other
queries: test_tpch_q1_q9.py, test_tpch_q10_q15.py, test_tpch_q16_q22.py."""

import numpy as np
import pandas as pd
import pytest

from cylon_tpu import tpch


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
    data distribution.  The subprocess legs live in
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
