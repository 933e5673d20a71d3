"""The set-operation cell (PR 48) on the CPU: the configuration as the issue
states it, the numpy reference against pandas, the key-alone control, and the
cell's tiny twin (65,536 rows a table) through ``run.py``, untraced and
traced, a broken timed path, and a tree without the routes."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import helpers
from lib import compare, files, generate

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, CELL = "cylon_setops_dedup_32m", "setops_dedup_32m"

# run.py of a throw-away copy on the CPU, in a process of its own
from test_zipf_fixed_hot import _DRIVER  # noqa: E402


@pytest.fixture(scope="module")
def qm():
    return files.load_module(BENCH_DIR, "queries", "setops_dedup")


def _small(rows: int, seed: int):
    cfg = copy.deepcopy(files.load_json(BENCH_DIR, "configs", CONFIG))
    for t in cfg["tables"].values():
        t["rows"] = rows
    return cfg["query"], generate.host_tables(BENCH_DIR, cfg, seed)


def test_configuration_is_the_issue_s():
    cfg = files.load_json(BENCH_DIR, "configs", CONFIG)
    assert cfg["world_size"] == 1 and len(cfg["source"]) <= 200
    for word in ("table.cpp:925,997,1152-1166", "Unique :1306,1376",
                 "frame.py:2079", "cylon_scaling.py:31-37"):
        assert word in cfg["source"]
    assert list(cfg["tables"]) == ["a", "b"]
    for t in cfg["tables"].values():
        assert t["rows"] == 32_000_000
        assert t["columns"] == [
            ["k", {"dist": "uniform_fraction", "fraction": 0.9,
                   "dtype": "int64"}],
            ["v", {"dist": "uniform_int", "lo": 0, "hi": 3,
                   "dtype": "int64"}]]
    q = cfg["query"]
    assert q["unique"] == {"subset": ["k"], "keep": "first"}
    assert q["set_ops"] == ["union", "subtract"]
    assert list(cfg["reduced"]) == ["world_size"]
    assert {"rows", "v", "order", "on_device"} <= set(cfg["assumed"])
    assert len(cfg["guarantees"]) == 5
    assert any("BOTH columns" in g for g in cfg["guarantees"])
    cell = files.load_json(BENCH_DIR, "workloads", CELL)
    assert cell["chips"] == 1 and cell["query"] == "setops_dedup"
    assert cell["loop"] == {**cell["loop"], "mode": "closed", "clients": 1,
                            "warmups": 2, "traced_queries": 3}
    assert cell["expect"]["routes"] == [["unique", "local"],
                                        ["set_op", "local"]]
    # every per-layer metric of the layer lists this cell alone (PR 51: the
    # readings other cells have too live under their shared names, in
    # their own layers)
    mine = [m for m in files.metric_files(BENCH_DIR)
            if m["layer"] == "set ops"]
    assert len(mine) == 8
    assert all(m["workloads"] == [CELL] and m["name"].startswith("setops_")
               and m["moves"] == "rows_per_s" for m in mine)
    shared = {m["name"] for m in files.metric_files(BENCH_DIR)
              if CELL in m.get("workloads", [CELL])
              and not m["name"].startswith("setops_")}
    assert {"host_pull_wait_ms", "programs_per_query",
            "unscoped_device_share", "gather_rows_device_ms"} <= shared


def _frame(cols: dict, result: str) -> pd.DataFrame:
    return pd.DataFrame({c: cols[f"{result}.{c}"] for c in ("k", "v")})


@pytest.mark.parametrize("seed", [5, 2**31 + 48])
def test_reference_equals_pandas(qm, seed):
    """200,000 rows a table: ``drop_duplicates``, ``concat`` +
    ``drop_duplicates``, an anti-join - sorted by ``(k, v)``."""
    q, host = _small(200_000, seed)
    a, b = pd.DataFrame(host["a"]), pd.DataFrame(host["b"])
    ref = qm.reference(host, q, seed)
    merged = a.drop_duplicates().merge(b.drop_duplicates(), how="left",
                                       on=["k", "v"], indicator=True)
    want = {
        "unique": a.drop_duplicates("k", keep="first"),
        "union": pd.concat([a, b]).drop_duplicates(),
        "subtract": merged[merged["_merge"] == "left_only"][["k", "v"]]}
    for r, w in want.items():
        w = w.sort_values(["k", "v"]).reset_index(drop=True)
        got = _frame(ref, r)
        assert got.dtypes.tolist() == [np.int64, np.int64]
        assert got.equals(w), r
    # the sizes the configuration reckons with: 60.4% / 76.7% / 33.1% of
    # the rows a table (x 2 for the union's input)
    n = 200_000
    assert abs(len(ref["unique.k"]) / n - 0.6037) < 0.01
    assert abs(len(ref["union.k"]) / (2 * n) - 0.767) < 0.01
    assert abs(len(ref["subtract.k"]) / n - 0.6625) < 0.01


def test_control_by_key_alone_is_caught_and_the_reference_is_not(qm):
    q, host = _small(200_000, 11)
    ref = qm.reference(host, q, 11)
    sound = compare.columns(qm.canonical(ref, q, 11), ref) \
        + qm.extra_numbers(host, ref, q)
    assert compare.verdict(sound), sound
    numbers = compare.columns(qm.control(host, q, 11), ref)
    assert not compare.verdict(numbers)
    over = {n: v for n, v, lim in numbers if v > lim}
    # the unique IS by key: the control and the reference agree there; the
    # union loses a key's other versions, the subtract every key of b
    assert not any(n.endswith(("unique.k", "unique.v")) for n in over)
    assert over["cells_differ.union.k"] > 10_000
    assert over["cells_differ.subtract.k"] > 10_000 and over["rows_diff"] > 0
    # ... and as a run's result it fails the membership counts too
    extra = dict((n, v) for n, v, _ in qm.extra_numbers(
        host, qm.control(host, q, 11), q))
    assert extra["union_rows_missing"] > 10_000
    assert extra["subtract_rows_missing"] > 10_000
    assert extra["unique_key_twice"] == extra["union_row_twice"] == 0


def _twin(tmp_path, seed: int, trace: int):
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    helpers.twin_metrics_of(bench_dir, CELL)
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER, bench_dir,
         os.path.dirname(os.path.abspath(__file__)),
         os.path.dirname(BENCH_DIR), "tiny_" + CELL, str(seed), str(trace)],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    return helpers.last_json_line(proc.stdout), proc.stderr, bench_dir


_OWN = ("setop_dispatches_off.unique", "setop_dispatches_off.union",
        "setop_dispatches_off.subtract", "setop_dispatches_off.intersect",
        "setop_rows_out_off", "unique_key_twice", "union_row_twice",
        "union_rows_missing", "subtract_rows_of_b", "subtract_rows_missing",
        "route_mismatches", "window_compiles", "recovery_events")


def test_tiny_twin(tmp_path):
    """65,536 rows a table, ``--trace 0``, a seed past 2^31: every row of
    the three results equal to the reference's, the workload file's routes,
    three dispatches an iteration."""
    line, err, _ = _twin(tmp_path, 2**31 + 48, trace=0)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rows_per_s", "query_s_p95", "setup_s"}
    for name in _OWN + ("rows_diff",) + tuple(
            f"cells_differ.{r}.{c}" for r in ("unique", "union", "subtract")
            for c in "kv"):
        assert line["compared"][name] == {"value": 0, "limit": 0}, name
    assert 'routes: [["unique", "local"], ["set_op", "local"], ' \
        '["set_op", "local"]]' in err
    assert "setop_dispatches unique=" in err


def test_tiny_twin_traced_reports_what_a_host_plane_can_give(tmp_path):
    """``--trace 1``: the spans' means and ``rows_out`` read off the
    operator spans equal the result's own rows (the cell's other metrics read
    the device plane: the chip's)."""
    line, _err, _ = _twin(tmp_path, 7, trace=1)
    assert line["correct"] is True, line["compared"]
    m = {k[5:]: v["value"] for k, v in line["metrics"].items()
         if k.startswith("tiny_")}
    assert set(m) == {"setops_unique_ms", "setops_union_ms",
                      "setops_subtract_ms",
                      "setops_rows_out_mrows_per_query",
                      "key_sort_folded_share"}, sorted(m)
    assert min(m.values()) > 0


def _main_on_cpu(tmp_path, monkeypatch, capfd, breaker):
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    run = helpers.load_run(bench_dir)
    helpers.steer_to_cpu(run, monkeypatch)
    breaker(run.files.load_module(run.BENCH_DIR, "queries", "setops_dedup"))
    capfd.readouterr()
    rc = run.main(["--workload", "tiny_" + CELL, "--seed", "3",
                   "--seconds", "0.3", "--trace", "0"])
    return rc, capfd.readouterr()


@pytest.mark.parametrize("result,column", [("unique", "v"), ("union", "k"),
                                           ("subtract", "v")])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, capfd,
                                          result, column):
    """One cell altered where the query produces it."""
    def breaker(qm):
        sound = qm.query

        def broken(tables, q, span):
            res = sound(tables, q, span)
            col = res.tables[result].columns[column]
            col.data = col.data.at[res.tables[result].row_count // 2].add(1)
            return res
        qm.query = broken
    rc, out = _main_on_cpu(tmp_path, monkeypatch, capfd, breaker)
    line = helpers.last_json_line(out.out)
    assert rc == 0 and line["correct"] is False
    over = [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    assert f"cells_differ.{result}.{column}" in over, over


def test_tree_without_the_routes_fails_cleanly(tmp_path, monkeypatch, capfd):
    """A tree whose plan nodes name no route (the parent of PR 48) cannot
    be held to the normal path: non-zero exit, soon, and no result line."""
    from cylon_tpu.relational import setops
    monkeypatch.delattr(setops, "plan_route")
    rc, out = _main_on_cpu(tmp_path, monkeypatch, capfd, lambda qm: None)
    assert rc != 0 and out.out.strip() == ""
    assert "NotImplementedError" in out.err and "name no route" in out.err
