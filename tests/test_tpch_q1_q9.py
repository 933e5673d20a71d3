"""TPC-H Q1, Q4, Q6, Q7, Q8 and Q9 against the pandas oracle (the other
queries: test_tpch.py, test_tpch_q10_q15.py, test_tpch_q16_q22.py)."""

import pandas as pd

from cylon_tpu import tpch


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
