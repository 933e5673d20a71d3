"""The distributed groupby-sum -> ``sort_values`` (BASELINE config 3, ISSUE
44) at world 4 on the virtual devices, against the benchmark's own plain
numpy reference (``benchmark/queries/groupby_sort.reference``: global table
in, global result out): route ``combine_shuffle`` (combine -> hash shuffle
-> final) then route ``sample_sort`` (sampled splitters picked on the host,
range exchange, local sort).  The sort column is compared in the order the
devices returned it; keys inside a run of equal sums in (sum, key) order on
both sides, as the configuration's ``ties`` rule says."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def qm():
    """The benchmark's query module, found as ``run.py`` finds it."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from lib import files
    return files.load_module(_BENCH, "queries", "dist_groupby_sort")


def _uniform(rng, n):
    """The cell's shape: key and value uniform in ONE global [0, 0.9 n)."""
    hi = max(int(0.9 * n), 1)
    return rng.integers(0, hi, n), rng.integers(0, hi, n)


def _tied(rng, n):
    """Nine groups in ten sum to 1000 (each key once, or twice as 400 +
    600); the rest below and above: all three splitters land in the run."""
    k = np.arange(n)
    a = np.full(n, 1000)
    lo, hi = rng.permutation(n)[:n // 10].reshape(2, -1)
    a[lo], a[hi] = rng.integers(0, 1000, len(lo)), \
        rng.integers(1001, 5000, len(hi))
    twice = rng.permutation(np.setdiff1d(k, np.concatenate([lo, hi])))[:n // 4]
    return (np.concatenate([k, twice]),
            np.concatenate([np.where(np.isin(k, twice), 400, a),
                            np.full(len(twice), 600)]))


def _constant(rng, n):
    """Every key once, every value 7: no row is above a splitter, so one
    device receives the whole range exchange and three receive nothing."""
    return rng.permutation(n), np.full(n, 7)


CASES = {
    # name: (make, rows, ascending)
    "uniform_global_range": (_uniform, 40_003, True),
    "one_run_of_ties_over_all_splitters": (_tied, 8_000, True),
    "constant_values_three_devices_empty": (_constant, 5_001, True),
    "fewer_rows_than_samples": (_uniform, 41, True),
    "descending": (_uniform, 20_002, False),
    "uniform_values_past_float32": (
        lambda rng, n: (rng.integers(0, n // 3, n),
                        rng.integers(0, 90_000_000, n)), 30_001, True),
}


def _run(env4, qm, make, rows, ascending, seed=44):
    import cylon_tpu as ct
    from cylon_tpu import obs
    from cylon_tpu.relational import groupby_aggregate, sort_table
    k, a = make(np.random.default_rng(seed), rows)
    host = {"t": {"k": k.astype(np.int64), "a": a.astype(np.int64)}}
    q = {"table": "t", "group_by": "k", "aggs": [["a", "sum"]],
         "sort_by": "a_sum", "ascending": ascending}
    t = ct.Table.from_pydict(host["t"], env4)
    out = []
    plan = obs.explain_analyze(lambda: out.append(sort_table(
        groupby_aggregate(t, "k", [("a", "sum")]), "a_sum",
        ascending=ascending)), profile_keys=False)
    from lib import checks          # the harness's own reading of a plan
    routes = [tuple(r) for r in checks.plan_routes(plan)]
    got = {n: d for n, (d, _v) in out[0].host_columns().items()}
    return host, q, out[0], got, routes


@pytest.mark.parametrize("case", sorted(CASES))
def test_groupby_then_sort_equals_the_plain_reference(env4, qm, case):
    from cylon_tpu import obs
    make, rows, ascending = CASES[case]
    taken = obs.counter("sort_sample_sorts")
    before = taken.value
    host, q, table, got, routes = _run(env4, qm, make, rows, ascending)
    assert ("groupby", "combine_shuffle") in routes, routes
    assert ("sort", "sample_sort") in routes, routes
    assert taken.value - before == 1
    want = qm.reference(host, q, 0)
    canon = qm.canonical(got, q, 0)
    assert set(canon) == set(want) == {"k", "a_sum"}
    for name in want:
        assert canon[name].dtype == np.int64
        np.testing.assert_array_equal(canon[name], want[name], err_msg=name)
    assert qm.extra_numbers(host, got, q) == [("sort_inversions", 0, 0)]
    # every group on exactly one device, the devices' results in mesh
    # order one totally ordered table
    vc = np.asarray(table.valid_counts)
    assert vc.sum() == len(np.unique(host["t"]["k"])) == len(want["k"])
    if case == "constant_values_three_devices_empty":
        assert sorted(vc.tolist()) == [0, 0, 0, rows]
    if case == "one_run_of_ties_over_all_splitters":
        # rows equal to a splitter go below it: the run and everything
        # under it on the first device, what is above on the last
        assert vc[1] == vc[2] == 0 and vc[0] > 9 * vc[3] > 0


def test_float32_control_is_refused(env4, qm):
    """The reference with float32 sums in the program's place, compared
    as a run's result is: not correct once values pass 2^24 - and the
    program's own result, compared the same way, is."""
    if _BENCH not in sys.path:
        sys.path.insert(0, _BENCH)
    from lib import compare
    make, rows, ascending = CASES["uniform_values_past_float32"]
    host, q, _table, got, _routes = _run(env4, qm, make, rows, ascending)
    want = qm.reference(host, q, 0)
    assert compare.verdict(compare.columns(qm.canonical(got, q, 0), want))
    control = compare.columns(qm.control(host, q, 0), want)
    assert not compare.verdict(control)
    over = {n for n, v, lim in control if v > lim}
    assert "cells_differ.a_sum" in over


@pytest.mark.parametrize("num_samples", [0, 8192])
def test_sort_exchange_region_says_how_even_the_samples_made_it(
        env4, num_samples):
    """The ``sort.exchange`` region's arguments, read off the flight
    recorder: ``samples`` a shard (the default is ``config.sort_samples``:
    64 at world 4), ``recv_max`` the fullest device's rows and ``recv_cap``
    its bucket - every device sorts at the capacity of the fullest, so the
    shape of the range exchange and of the local sort follows the sample."""
    import cylon_tpu as ct
    from cylon_tpu import config
    from cylon_tpu.obs import trace
    from cylon_tpu.relational import sort_table
    n = 4 * 29_500
    v = np.random.default_rng(num_samples).permutation(n).astype(np.int64)
    t = ct.Table.from_pydict({"v": v}, env4)
    trace.disarm()   # arm() hands back a recorder an earlier file left live
    rec = trace.arm(capacity=4096)
    try:
        out = sort_table(t, "v", num_samples=num_samples)
        events = rec.events()
    finally:
        trace.disarm()
    np.testing.assert_array_equal(out.host_columns()["v"][0], np.arange(n))
    region, = (e[6] for e in events if e[3] == "sort.exchange")
    exchange, = (e[6] for e in events if e[3] == "exchange.flat")
    fullest = int(np.asarray(out.valid_counts).max())
    assert region["samples"] == (num_samples or config.sort_samples(4))
    assert region["recv_max"] == exchange["recv_max"] == fullest
    assert region["recv_cap"] == exchange["recv_cap"] == out.capacity \
        == config.pow2ceil(fullest)
    assert exchange["site"] == "sort.recv"
    # 64 samples a shard leave the fullest device a tenth over its share,
    # 8,192 a hundredth (relative spread sqrt((W - 1) / samples))
    assert fullest / (n / 4) < (1.05 if num_samples else 1.4)
