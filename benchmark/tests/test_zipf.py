"""The skewed-key cell (PR 32), on the CPU: the distribution file, the
cell rehearsed at 65,536 rows through ``run.py``'s own ``main`` with its
new per-layer metric read from the program's registry, the reader alone,
and the float32 control at the cell's own size."""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import control
import helpers
from lib import compare, files, generate

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = {"dist": "zipf", "s": 1.1, "fraction": 0.9, "dtype": "int64"}
ROWS = 400_000


@pytest.fixture(scope="module")
def zipf():
    return files.load_module(BENCH_DIR, "dists", "zipf")


def _draw(zipf, seed, rows=ROWS, spec=SPEC):
    return zipf.draw(np.random.default_rng(seed), rows, spec)


def test_same_seed_same_array_in_bounds(zipf):
    a, b = _draw(zipf, 2**31 + 5), _draw(zipf, 2**31 + 5)
    assert a.dtype == np.int64 and a.shape == (ROWS,)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < int(ROWS * 0.9)


def test_hottest_keys_share_is_one_over_h(zipf):
    """P(rank 1) = 1 / H(N, s); P(rank r) = r**-s / H: the three hottest
    keys hold their shares to within 1% (2% for the third, which is
    rarer), and the tail is there (exact inverse CDF, no truncation)."""
    n_keys, s = int(ROWS * 0.9), SPEC["s"]
    h = float(np.sum(np.arange(1, n_keys + 1, dtype=np.float64) ** -s))
    rows = 4_000_000                   # 0.44M rows on the hottest key
    counts = np.sort(np.bincount(_draw(zipf, 17, rows=rows, spec=dict(
        SPEC, fraction=0.9 * ROWS / rows))))[::-1]
    assert len(counts) <= n_keys
    for rank, tol in ((1, 0.01), (2, 0.01), (3, 0.02)):
        want = rank ** -s / h
        assert abs(counts[rank - 1] / rows - want) < tol * want, rank
    assert np.count_nonzero(counts) > 0.5 * n_keys     # the tail is drawn
    np.testing.assert_allclose(zipf.cdf(n_keys, s)[[0, -1]], [1 / h, 1.0],
                               rtol=1e-12)


def test_hot_set_is_fixed_by_the_seed_and_differs_between_seeds(zipf):
    def hot(seed):
        c = np.bincount(_draw(zipf, seed), minlength=int(ROWS * 0.9))
        return set(np.argsort(c)[-10:].tolist())
    assert hot(1) == hot(1)
    assert len(hot(1) & hot(2)) <= 1           # not the keys 0, 1, 2, ...
    assert hot(1) != set(range(10))


def test_configuration_draws_the_probe_key_alone_from_it():
    cfg = files.load_json(BENCH_DIR, "configs", "cylon_join_zipf_32m")
    sibling = files.load_json(BENCH_DIR, "configs", "cylon_join_uniform_32m")
    dists = {(t, c): spec["dist"] for t, tab in cfg["tables"].items()
             for c, spec in tab["columns"]}
    assert dists == {("left", "k"): "zipf", ("left", "a"): "uniform_fraction",
                     ("right", "k"): "uniform_fraction",
                     ("right", "b"): "uniform_fraction"}
    assert cfg["tables"]["left"]["columns"][0][1] == SPEC
    # everything but the keys is the uniform sibling's
    for key in ("world_size", "query", "guarantees"):
        assert cfg[key] == sibling[key]
    assert {t: tab["rows"] for t, tab in cfg["tables"].items()} \
        == {t: tab["rows"] for t, tab in sibling["tables"].items()}
    assert sorted(cfg["reduced"]) == ["rows", "world_size"]
    cell = files.load_json(BENCH_DIR, "workloads", "join_groupby_32m_zipf")
    uniform_cell = files.load_json(BENCH_DIR, "workloads", "join_groupby_32m")
    for key in ("query", "loop", "expect", "chips", "traffic"):
        assert cell[key] == uniform_cell[key]


@pytest.fixture()
def bench(tmp_path, monkeypatch):
    run = helpers.load_run(helpers.copy_with_tiny_cells(tmp_path))
    helpers.steer_to_cpu(run, monkeypatch)
    return run


def _main(bench, capfd, seed, trace):
    capfd.readouterr()
    rc = bench.main(["--workload", "tiny_join_groupby_32m_zipf", "--seed",
                     str(seed), "--seconds", "0.5", "--trace", str(trace)])
    out = capfd.readouterr()
    assert rc == 0, out.err[-3000:]
    return helpers.last_json_line(out.out), out.err


def test_cell_on_cpu_equals_its_reference(bench, capfd):
    """The tiny twin (65,536 rows a side) through ``run.py``: correct
    against the plain reference, the two routes, one fused callsite that
    is not eligible for the window."""
    line, err = _main(bench, capfd, seed=2**31 + 32, trace=0)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rows_per_s", "query_s_p95", "setup_s"}
    compared = line["compared"]
    for name in ("route_mismatches", "fused_callsites_not_1",
                 "windowed_gather_missed", "keys_wrong_multiplicity",
                 "cells_differ.a_sum", "all_groups_differ.b_sum"):
        assert compared[name] == {"value": 0, "limit": 0}
    assert 'routes: [["join", "colocated"], ["groupby", "fused_pushdown"]]' \
        in err
    assert "window=0 eligible=False" in err
    # skew: far fewer groups than the uniform twin's ~0.2 of the state
    gather = next(ln for ln in err.splitlines() if "gather: segment_space=" in ln)
    assert int(gather.split("segment_space=")[1].split()[0]) < 0.1 * 2 * 65536


def test_traced_line_carries_the_registry_metric(bench, monkeypatch, capfd):
    """``--trace 1`` finds ``zipf_plain_dispatches_per_query`` through the
    new reader: every query of the process settled one plain dispatch.
    The CPU has no device plane, so the trace reduction is stood in for,
    as in ``test_rehearsal.py``, and the ``trace_*`` readers find nothing."""
    with open(os.path.join(bench.BENCH_DIR, "metrics",
                           "zipf_plain_dispatches_per_query.json")) as f:
        m = json.load(f)
    m["workloads"] = ["tiny_join_groupby_32m_zipf"]
    with open(os.path.join(bench.BENCH_DIR, "metrics",
                           "tiny_plain_dispatches_per_query.json"), "w") as f:
        json.dump(dict(m, name="tiny_plain_dispatches_per_query"), f)
    monkeypatch.setattr(bench, "_traced_queries", lambda one, n, spans, d: (
        [one() for _ in range(n)],
        {"n_queries": n, "n_chips": 1, "busy_s": 0.9, "window_s": 1.0,
         "idle_share": 0.1, "op_seconds": [], "gap_seconds": []})[1])
    line, _err = _main(bench, capfd, seed=7, trace=1)
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["tiny_plain_dispatches_per_query"] == {
        "value": 1.0, "unit": "count"}


def test_registry_counter_reader(monkeypatch):
    reader = files.load_module(BENCH_DIR, "readers", "registry_counter")
    from cylon_tpu.obs import metrics
    snap = {'t_plain{reason="a"}': 3, 't_plain{reason="b"}': 1,
            "t_windowed": 4, "t_hist": {"count": 2}}
    monkeypatch.setattr(metrics, "snapshot", lambda: dict(snap))
    plain, per = r"^t_plain\{", r"^t_(plain\{|windowed$)"
    assert reader.read({}, {"counter": plain, "per": per}) == 0.5
    assert reader.read({}, {"counter": plain, "per": plain}) == 1.0
    # a parent without the counter, a denominator of nothing, a metric
    # that is no number: no value, and the line leaves the metric out
    assert reader.read({}, {"counter": r"^absent", "per": per}) is None
    assert reader.read({}, {"counter": plain, "per": r"^absent"}) is None
    assert reader.read({}, {"counter": r"^t_hist", "per": per}) is None


def test_float32_sums_are_caught_at_the_cells_size():
    """The control at 32M rows a side (numpy only, ~1.5 min: two Zipf
    tables and two references): rounding moves the sampled sums, never
    the membership."""
    numbers = control.control_numbers(BENCH_DIR, "join_groupby_32m_zipf",
                                      seed=2**31 + 3)
    assert not compare.verdict(numbers)
    over = {n: v for n, v, lim in numbers if v > lim}
    assert min(v for n, v in over.items() if "_sum" in n) > 10_000
    assert dict((n, v) for n, v, _ in numbers)["rows_diff"] == 0
