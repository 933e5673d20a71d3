"""TPC-H Q10 to Q15 against the pandas oracle, and Q13's recorded plan (the
other queries: test_tpch.py, test_tpch_q1_q9.py, test_tpch_q16_q22.py)."""

import pandas as pd
import pytest

from cylon_tpu import tpch


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
