"""The TPC-H cell (PR 41) on the CPU: the new distributions, the derived
columns, the numpy reference against a pandas merge, the float32 control,
and the cell's tiny twin (SF 0.01) through ``run.py`` on the CPU rig,
traced, with the new metrics on its line."""

from __future__ import annotations

import copy
import json
import os

import numpy as np
import pytest

import helpers
from lib import compare, files, generate

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, CELL = "tpch_sf5", "tpch_sf5_q3q5"
TINY = {"region": 5, "nation": 25, "supplier": 100, "customer": 1500,
        "orders": 15000, "lineitem": 60000}


def tiny_config() -> dict:
    """The configuration at SF 0.01: the rows, and the key ranges that
    follow from them."""
    cfg = copy.deepcopy(files.load_json(BENCH_DIR, "configs", CONFIG))
    cfg["name"] = "tiny_tpch"
    for t, rows in TINY.items():
        cfg["tables"][t]["rows"] = rows
    for t, col, hi in (("orders", "o_custkey", TINY["customer"] - 1),
                       ("lineitem", "l_suppkey", TINY["supplier"] - 1)):
        dict(cfg["tables"][t]["columns"])[col]["hi"] = hi
    dict(cfg["tables"]["lineitem"]["columns"])["l_orderkey"]["groups"] = \
        TINY["orders"]
    return cfg


@pytest.fixture(scope="module")
def cfg():
    return tiny_config()


@pytest.fixture(scope="module")
def qm():
    return files.load_module(BENCH_DIR, "queries", "tpch_q3q5")


def _dist(name):
    return files.load_module(BENCH_DIR, "dists", name)


# ---- the distributions ----------------------------------------------------

@pytest.mark.parametrize("rows,groups", [(60000, 15000), (15000, 15000),
                                         (105000, 15000), (40, 10)])
def test_run_length_key_is_exactly_rows_long(rows, groups):
    spec = {"groups": groups, "min": 1, "max": 7, "dtype": "int64"}
    for seed in (3, 2**31 + 9):
        k = _dist("run_length_key").draw(np.random.default_rng(seed), rows,
                                         spec)
        assert k.shape == (rows,) and k.dtype == np.int64
        assert (np.diff(k) >= 0).all()
        runs = np.bincount(k, minlength=groups)
        assert len(runs) == groups and runs.min() >= 1 and runs.max() <= 7
    with pytest.raises(ValueError):
        _dist("run_length_key").draw(np.random.default_rng(1), rows,
                                     dict(spec, groups=rows + 1))


def test_closed_range_dists():
    rng = np.random.default_rng(2**31 + 1)
    cents = _dist("uniform_int").draw(rng, 50000, {
        "lo": 90000, "hi": 10500000, "dtype": "int64"})
    assert cents.dtype == np.int64 and 90000 <= cents.min() \
        and cents.max() <= 10500000
    qty = _dist("uniform_int").draw(rng, 50000, {
        "lo": 100, "hi": 5000, "step": 100, "dtype": "int64"})
    assert set(np.unique(qty)) == set(range(100, 5001, 100))
    zero = _dist("uniform_int").draw(rng, 10, {"lo": 0, "hi": 0,
                                               "dtype": "int64"})
    assert not zero.any()
    with pytest.raises(ValueError):
        _dist("uniform_int").draw(rng, 10, {"lo": 0, "hi": 5, "step": 2,
                                            "dtype": "int64"})
    days = _dist("uniform_days").draw(rng, 50000, {"lo": "1992-01-01",
                                                   "hi": "1998-08-02"})
    assert days.dtype == np.dtype("datetime64[ns]")
    assert days.min() == np.datetime64("1992-01-01") \
        and days.max() == np.datetime64("1998-08-01")
    assert not (days.astype(np.int64) % 86_400_000_000_000).any()
    codes = _dist("choice").draw(rng, 50000, {"n": 5})
    assert codes.dtype == np.int32 and set(np.unique(codes)) == set(range(5))
    key = _dist("dense_key").draw(rng, 7, {"dtype": "int64"})
    np.testing.assert_array_equal(key, np.arange(7))


def test_same_seed_same_tables_every_seed_the_same_sizes(cfg):
    a = generate.host_tables(BENCH_DIR, cfg, 2**31 + 5)
    b = generate.host_tables(BENCH_DIR, cfg, 2**31 + 5)
    c = generate.host_tables(BENCH_DIR, cfg, 2**31 + 6)
    for t in a:
        for col in a[t]:
            np.testing.assert_array_equal(a[t][col], b[t][col])
            assert a[t][col].shape == c[t][col].shape == (TINY[t],)
    assert not np.array_equal(a["lineitem"]["l_orderkey"],
                              c["lineitem"]["l_orderkey"])
    assert generate.input_rows(cfg) == sum(TINY.values())


def test_the_configuration_is_the_issues(cfg):
    """The full-size file: the spec's cardinalities x 5, its columns by
    table, and a vocabulary of every choice's size."""
    full = files.load_json(BENCH_DIR, "configs", CONFIG)
    assert {t: v["rows"] for t, v in full["tables"].items()} == {
        "region": 5, "nation": 25, "supplier": 50000, "customer": 750000,
        "orders": 7500000, "lineitem": 30000000}
    assert generate.input_rows(full) == 38_300_030
    q = full["query"]
    for t in full["tables"].values():
        for name, spec in t["columns"]:
            if spec["dist"] == "choice":
                assert len(q["vocabulary"][name]) == spec["n"], name
    assert len(q["nations"]) == len(q["nation_region"]) == 25
    assert sorted(full["reduced"]) == ["columns", "scale_factor", "tables",
                                       "world_size"]


# ---- derived columns, reference, control -----------------------------------

def test_derived_columns(cfg, qm):
    host = generate.host_tables(BENCH_DIR, cfg, 11)
    t = qm.derive(host, cfg["query"])
    assert set(host["lineitem"]) - set(t["lineitem"]) == {
        "l_shipdelay", "l_commitdelay", "l_receiptdelay"}    # not ingested
    assert "l_shipdelay" in host["lineitem"]                 # pure
    assert {n: len(c) for n, c in t.items()} == {
        "region": 2, "nation": 3, "supplier": 3, "customer": 4, "orders": 7,
        "lineitem": 15}
    line, orders = t["lineitem"], t["orders"]
    delay = (line["l_shipdate"] - orders["o_orderdate"][line["l_orderkey"]]
             ).astype("timedelta64[D]").astype(np.int64)
    np.testing.assert_array_equal(delay, host["lineitem"]["l_shipdelay"])
    first = np.r_[True, np.diff(line["l_orderkey"]) != 0]
    assert (line["l_linenumber"][first] == 1).all()
    assert line["l_linenumber"].max() <= 7
    np.testing.assert_array_equal(
        t["nation"]["n_regionkey"], cfg["query"]["nation_region"])


def _pandas_answers(t: dict, q: dict):
    """Q3 and Q5 by pandas merges on the derived tables (integer cents)."""
    import pandas as pd
    f = {n: pd.DataFrame(c) for n, c in t.items()}
    seg = q["vocabulary"]["c_mktsegment"].index(q["q3"]["segment"])
    d = pd.Timestamp(q["q3"]["date"])
    c = f["customer"][f["customer"].c_mktsegment == seg]
    o = f["orders"][f["orders"].o_orderdate < d]
    l = f["lineitem"][f["lineitem"].l_shipdate > d]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey").merge(
        l, left_on="o_orderkey", right_on="l_orderkey")
    j["revenue"] = j.l_extendedprice * (100 - j.l_discount)
    g3 = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                   as_index=False)["revenue"].sum().sort_values(
        ["revenue", "o_orderdate", "l_orderkey"],
        ascending=[False, True, True])
    lo, hi = pd.Timestamp(q["q5"]["date_lo"]), pd.Timestamp(q["q5"]["date_hi"])
    region = q["regions"].index(q["q5"]["region"])
    nat = f["nation"][f["nation"].n_regionkey == region]
    sup = f["supplier"].merge(nat, left_on="s_nationkey",
                              right_on="n_nationkey")
    o = f["orders"][(f["orders"].o_orderdate >= lo)
                    & (f["orders"].o_orderdate < hi)]
    j = f["customer"].merge(o, left_on="c_custkey", right_on="o_custkey") \
        .merge(f["lineitem"], left_on="o_orderkey", right_on="l_orderkey") \
        .merge(sup, left_on=["l_suppkey", "c_nationkey"],
               right_on=["s_suppkey", "s_nationkey"])
    j["revenue"] = j.l_extendedprice * (100 - j.l_discount)
    g5 = j.groupby("n_nationkey", as_index=False)["revenue"].sum() \
        .sort_values("revenue", ascending=False)
    return g3, g5, len(j)


@pytest.mark.parametrize("seed", [7, 2**31 + 13])
def test_reference_equals_a_pandas_merge(cfg, qm, seed):
    q = cfg["query"]
    host = generate.host_tables(BENCH_DIR, cfg, seed)
    want = qm.reference(host, q, seed)
    t = qm.derive(host, q)
    g3, g5, joined = _pandas_answers(t, q)
    assert len(g3) > 50 and len(g5) == 5 and joined > 30
    top = g3.head(q["q3"]["limit"])
    np.testing.assert_array_equal(want["q3.l_orderkey"], top.l_orderkey)
    np.testing.assert_array_equal(want["q3.revenue"], top.revenue)
    np.testing.assert_array_equal(
        want["q3.o_orderdate"], top.o_orderdate.to_numpy().astype(
            "datetime64[ns]").astype(np.int64))
    np.testing.assert_array_equal(want["q5.n_nationkey"], g5.n_nationkey)
    np.testing.assert_array_equal(want["q5.revenue"], g5.revenue)
    assert all(v.dtype == np.int64 for v in want.values())
    every = qm.q3_groups(t, q)
    np.testing.assert_array_equal(every["l_orderkey"], g3.l_orderkey)
    np.testing.assert_array_equal(every["revenue"], g3.revenue)
    assert len(qm.q5_rows(t, q)[0]) == joined


def test_control_is_caught(cfg, qm):
    q = cfg["query"]
    host = generate.host_tables(BENCH_DIR, cfg, 5)
    numbers = compare.columns(qm.control(host, q, 5),
                              qm.reference(host, q, 5))
    assert not compare.verdict(numbers)
    over = {n for n, v, lim in numbers if v > lim}
    assert {"cells_differ.q3.revenue", "cells_differ.q5.revenue"} <= over


def test_ties_are_ordered_by_key_and_nothing_else(qm):
    cols = {"q3.revenue": np.array([9, 7, 7, 7, 3]),
            "q3.o_orderdate": np.array([1, 2, 2, 5, 0]),
            "q3.l_orderkey": np.array([4, 8, 6, 1, 2]),
            "q3.o_shippriority": np.array([0, 1, 2, 3, 4])}
    out = qm._ties_by_key(cols, "q3.")
    np.testing.assert_array_equal(out["l_orderkey"], [4, 6, 8, 1, 2])
    np.testing.assert_array_equal(out["o_shippriority"], [0, 2, 1, 3, 4])
    np.testing.assert_array_equal(out["revenue"], cols["q3.revenue"])
    assert qm._inversions(np.array([9, 7, 7]), np.array([1, 3, 2])) == 1
    assert qm._inversions(np.array([7, 9]), np.array([1, 1])) == 1


# ---- the tiny twin through run.py ------------------------------------------

@pytest.fixture()
def bench(tmp_path, monkeypatch, cfg):
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    cell = files.load_json(BENCH_DIR, "workloads", CELL)
    cell.update(name="tiny_tpch_q3q5", config=cfg["name"])
    for kind, obj in (("configs", cfg), ("workloads", cell)):
        with open(os.path.join(bench_dir, kind, obj["name"] + ".json"),
                  "w") as f:
            json.dump(obj, f)
    for name in os.listdir(os.path.join(bench_dir, "metrics")):
        path = os.path.join(bench_dir, "metrics", name)
        with open(path) as f:
            m = json.load(f)
        if CELL in m.get("workloads", ()):   # the copy's own files
            m["workloads"] = ["tiny_tpch_q3q5"]
            with open(path, "w") as f:
                json.dump(m, f)
    run = helpers.load_run(bench_dir)
    helpers.steer_to_cpu(run, monkeypatch)
    return run


def _run(bench, capfd, seed, trace=0):
    capfd.readouterr()
    rc = bench.main(["--workload", "tiny_tpch_q3q5", "--seed", str(seed),
                     "--seconds", "0.5", "--trace", str(trace)])
    return rc, capfd.readouterr()


def test_tiny_twin_equals_its_reference(bench, capfd):
    rc, out = _run(bench, capfd, seed=2**31 + 17)
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rows_per_s", "query_s_p95", "setup_s"}
    c = line["compared"]
    for name in ("cells_differ.q3.revenue", "cells_differ.q5.revenue",
                 "q3_all_cells_differ.revenue", "q3_all_groups_diff",
                 "q5_joined_rows_diff", "money_columns_not_decimal",
                 "revenue_not_decimal_scale_4", "float_columns_in_results",
                 "route_mismatches", "window_compiles", "recovery_events"):
        assert c[name] == {"value": 0, "limit": 0}, name
    assert "own checks: Q3 without LIMIT" in out.err


def test_tiny_twin_traced_has_the_new_metrics(bench, monkeypatch, capfd):
    """The CPU has no device plane: the trace reduction is stood in for, as
    ``test_rehearsal`` does; the host-plane and counter metrics are real."""
    monkeypatch.setattr(bench, "_traced_queries", lambda one, n, spans, d: (
        [one() for _ in range(n)],
        {"n_queries": n, "n_chips": 1, "busy_s": 0.9, "window_s": 1.0,
         "idle_share": 0.1, "op_seconds": [("fusion.1", 0.5)],
         "gap_seconds": [("q3_call", 0.1)]})[1])
    rc, out = _run(bench, capfd, seed=23, trace=1)
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    assert line["correct"] is True, line["compared"]
    m = line["metrics"]
    assert m["tpch_q3_ms"]["value"] > 0 and m["tpch_q5_ms"]["value"] > 0
    assert m["sum_scans_32bit_share"]["value"] == 1.0
    assert 0 < m["tpch_decimal_expr_share"]["value"] < 0.5
    # a metric that lists other cells is not read here
    assert not {"join_call_ms", "fused_program_ms", "setops_unique_ms"} & set(m)


def test_broken_revenue_is_not_correct(bench, capfd):
    qm = bench.files.load_module(bench.BENCH_DIR, "queries", "tpch_q3q5")
    sound = qm.query

    def broken(tables, q, span):
        res = sound(tables, q, span)
        col = res.q5.columns["revenue"]
        col.data = col.data.at[2].add(1)
        return res
    qm.query = broken
    rc, out = _run(bench, capfd, seed=3)
    line = helpers.last_json_line(out.out)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["cells_differ.q5.revenue"]["value"] == 1
