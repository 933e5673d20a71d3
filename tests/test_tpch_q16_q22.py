"""TPC-H Q16 to Q22 against the pandas oracle, and Q18's recorded plan (the
other queries: test_tpch.py, test_tpch_q1_q9.py, test_tpch_q10_q15.py)."""

import pandas as pd
import pytest

from cylon_tpu import tpch


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
