"""Both cells rehearsed at 65,536 rows on the CPU, through ``run.py``'s own
``main``: the query modules equal their references, a file-only addition is
found with no edit, a broken timed path comes out ``correct: false``, and a
machine with no TPU gets no result line.  A CPU run proves results, control
flow and counts; its times are not device metrics and are not looked at."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import helpers


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    run = helpers.load_run(bench_dir)
    helpers.steer_to_cpu(run, monkeypatch)
    return run


def _run(bench, capfd, cell: str, seed: int, trace: int = 0):
    capfd.readouterr()
    rc = bench.main(["--workload", cell, "--seed", str(seed),
                     "--seconds", "0.5", "--trace", str(trace)])
    out = capfd.readouterr()
    return rc, out


@pytest.mark.parametrize("cell", ["tiny_join_groupby_32m",
                                  "tiny_groupby_sort_25m"])
def test_cell_on_cpu_equals_its_reference(bench, capfd, cell):
    """The tiny twins exist only as files written into the copy."""
    rc, out = _run(bench, capfd, cell, seed=2**31 + 11)
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"rows_per_s", "query_s_p95", "setup_s"}
    assert list(line)[-1] == "compared"
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    # the numbers compared are standard error's last lines, and the file's
    tail = out.err.strip().splitlines()[-len(line["compared"]):]
    assert all(ln.startswith("compared ") for ln in tail)
    err_file = os.path.join(bench.OUT_DIR, f"{cell}.{2**31 + 11}.stderr")
    with open(err_file) as f:
        assert "compared rows_diff = 0 (limit 0)" in f.read()


def test_added_metric_file_is_found(bench, monkeypatch, capfd):
    """``--trace 1`` reads every metric file that lists the cell - among
    them the one the copy added.  The CPU has no device plane, so the trace
    reduction is stood in for here; on the chip it is the real one."""
    monkeypatch.setattr(bench, "_traced_queries", lambda one, n, spans, d: (
        [one() for _ in range(n)],
        {"n_queries": n, "n_chips": 1, "busy_s": 0.9, "window_s": 1.0,
         "idle_share": 0.1, "op_seconds": [("fusion.1", 0.5)],
         "gap_seconds": [("groupby_call", 0.1)]})[1])
    rc, out = _run(bench, capfd, "tiny_join_groupby_32m", seed=5, trace=1)
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    m = line["metrics"]
    assert m["query_mean_ms"]["value"] > 0          # the added file
    # a metric that lists its cells is theirs alone; one that lists none is
    # every cell's, this throw-away one's too
    assert not {"sort_call_ms", "join_call_ms", "groupby_call_ms"} & set(m)
    assert set(m) == {"ingest_s", "compile_s", "window_compiles",
                      "peak_hbm_gib", "device_idle_share", "query_mean_ms"}
    assert line["device"]["busy_s"] == 0.9 and "breakdown" in line


@pytest.mark.parametrize("cell,query,column", [
    ("tiny_join_groupby_32m", "join_groupby", "b_sum"),
    ("tiny_groupby_sort_25m", "groupby_sort", "a_sum")])
def test_broken_timed_path_is_not_correct(bench, capfd, cell, query, column):
    """One sum altered where the query produces it: ``correct`` is false."""
    qm = bench.files.load_module(bench.BENCH_DIR, "queries", query)
    sound = qm.query

    def broken(tables, q, span):
        res = sound(tables, q, span)
        col = res.columns[column]
        col.data = col.data.at[res.row_count // 2].add(1)
        return res
    qm.query = broken
    rc, out = _run(bench, capfd, cell, seed=3)
    line = helpers.last_json_line(out.out)
    assert rc == 0 and line["correct"] is False
    over = [k for k, v in line["compared"].items() if v["value"] > v["limit"]]
    assert any(k.endswith("." + column) for k in over), over


def test_wrong_route_is_not_correct(bench, capfd):
    """A plan that does not show the route the workload file names."""
    path = os.path.join(bench.BENCH_DIR, "workloads",
                        "tiny_groupby_sort_25m.json")
    with open(path) as f:
        cell = json.load(f)
    cell["expect"]["routes"] = [["groupby", "fused_pushdown"]]
    with open(path, "w") as f:
        json.dump(cell, f)
    rc, out = _run(bench, capfd, "tiny_groupby_sort_25m", seed=4)
    line = helpers.last_json_line(out.out)
    assert line["correct"] is False
    assert line["compared"]["route_mismatches"]["value"] == 1


def test_no_tpu_no_result(tmp_path, capfd):
    """Unsteered, on this machine: non-zero exit and no result line."""
    run = helpers.load_run(helpers.copy_with_tiny_cells(tmp_path))
    capfd.readouterr()
    rc = run.main(["--workload", "tiny_join_groupby_32m", "--seed", "1",
                   "--seconds", "0.5", "--trace", "0"])
    out = capfd.readouterr()
    assert rc != 0 and out.out.strip() == ""
    assert "no TPU found" in out.err
