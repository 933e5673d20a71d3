"""The four-chip groupby -> sort cell (PR 44) on the CPU: its tiny twin
(65,536 rows over four devices) through ``run.py``, untraced and traced,
``own_checks``' numbers at their limits, the new metrics that a host plane
can give, and the float32 control."""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import helpers
from lib import compare, files, generate

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG, CELL = "cylon_groupby_sort_100m_x4", "groupby_sort_25m_x4"

# run.py of a throw-away copy on four CPU devices, in a process of its own
from test_zipf_fixed_hot import _DRIVER  # noqa: E402


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


_OWN = ("tables_not_spread_evenly", "calls_that_moved_no_rows",
        "off_diagonal_share_outside_range", "exchanges_per_query_off",
        "sample_sorts_of_one_query_off", "sort_inversions",
        "route_mismatches", "window_compiles", "recovery_events")


def test_configuration_is_the_sibling_at_the_source_s_size():
    """Config 3 as written: the one-chip sibling's schema, query, ties
    rule and control, at 100M rows and world 4, nothing reduced."""
    cfg = files.load_json(BENCH_DIR, "configs", CONFIG)
    sib = files.load_json(BENCH_DIR, "configs",
                          "cylon_groupby_sort_100m_4ranks")
    assert cfg["world_size"] == 4 and cfg["reduced"] == {}
    assert cfg["tables"]["t"]["rows"] == 100_000_000
    # the sibling's columns, drawn once for the deployment (PR 44's check:
    # a table a seed spread 2.6%); --seed orders each chip's rows
    mine, theirs = cfg["tables"]["t"]["columns"], sib["tables"]["t"]["columns"]
    for i, ((name, spec), (sib_name, sib_spec)) in enumerate(zip(mine, theirs)):
        assert name == sib_name and spec == {
            **sib_spec, "dist": "uniform_fraction_fixed_rows",
            "data_seed": 4400000017, "column": i, "blocks": cfg["world_size"]}
    assert len(mine) == len(theirs) == 2
    for same in ("query", "ties"):
        assert cfg[same] == sib[same], same
    # the same control; the values reach 0.9 x the rows of THIS table
    assert cfg["control"] == sib["control"].replace("22.5M", "90M")
    assert set(sib["guarantees"]) <= set(cfg["guarantees"])
    assert len(cfg["guarantees"]) == len(sib["guarantees"]) + 2
    cell = files.load_json(BENCH_DIR, "workloads", CELL)
    assert cell["chips"] == 4 and cell["query"] == "dist_groupby_sort"
    assert cell["expect"]["routes"] == [["groupby", "combine_shuffle"],
                                        ["sort", "sample_sort"]]


def _tables(rows: int, seed: int, dist: str = "uniform_fraction_fixed_rows"):
    cfg = copy.deepcopy(files.load_json(BENCH_DIR, "configs", CONFIG))
    cfg["tables"]["t"]["rows"] = rows
    for _, spec in cfg["tables"]["t"]["columns"]:
        spec["dist"] = dist
    return generate.host_tables(BENCH_DIR, cfg, seed)["t"]


def _rows_a_chip(t: dict, rows: int, world: int = 4) -> list:
    """Each chip's ``(k, a)`` rows as ``from_pydict`` deals them, sorted."""
    chunk = -(-rows // world)
    out = []
    for lo in range(0, rows, chunk):
        block = np.stack([t["k"][lo:lo + chunk], t["a"][lo:lo + chunk]], 1)
        out.append(block[np.lexsort((block[:, 1], block[:, 0]))])
    return out


@pytest.mark.parametrize("rows", [65536, 100_003])
@pytest.mark.parametrize("seed", [1, 2**31 + 44])
def test_every_seed_gives_each_chip_the_same_rows_in_another_order(rows, seed):
    """The table is the deployment's: what the plain distribution draws at
    ``--seed`` = ``data_seed``; a run's seed moves rows inside a chip's
    partition only, whole rows, and the same seed gives the same table."""
    deployment = _tables(rows, 4400000017, dist="uniform_fraction")
    got, other = _tables(rows, seed), _tables(rows, seed + 1)
    assert got["k"].dtype == got["a"].dtype == np.int64
    for mine, theirs, its in zip(_rows_a_chip(got, rows),
                                 _rows_a_chip(other, rows),
                                 _rows_a_chip(deployment, rows)):
        assert np.array_equal(mine, its) and np.array_equal(theirs, its)
    assert np.mean(got["k"] != other["k"]) > 0.9
    assert np.mean(got["k"] != deployment["k"]) > 0.9
    again = _tables(rows, seed)
    assert np.array_equal(got["k"], again["k"])
    assert np.array_equal(got["a"], again["a"])


def test_tiny_twin_on_four_cpu_devices(tmp_path):
    """65,536 rows over four devices, ``--trace 0``: every group equal to
    the reference's in the order the devices returned them, the routes of
    the workload file, ``own_checks``' numbers at their limits."""
    line, err, _ = _twin(tmp_path, 2**31 + 44, trace=0)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"rows_per_s", "query_s_p95", "setup_s"}
    for name in _OWN + ("cells_differ.k", "cells_differ.a_sum", "rows_diff"):
        assert line["compared"][name] == {"value": 0, "limit": 0}, name
    assert 'routes: [["groupby", "combine_shuffle"], ["shuffle", null], ' \
        '["sort", "sample_sort"]]' in err
    assert "exchange, groupby: " in err and "exchange, sort: " in err
    assert "sort_sample_sorts: 1 in that query" in err
    assert "on 4 devices" in err


def test_tiny_twin_traced_reports_what_a_host_plane_can_give(tmp_path):
    """``--trace 1``: the new metrics that need no device plane, and
    ``recv_max`` / ``recv_cap`` of the sort's exchange equal to what the
    result's own rows a device say."""
    seed = 2**31 + 45
    line, err, bench_dir = _twin(tmp_path, seed, trace=1)
    assert line["correct"] is True, line["compared"]
    m = {k[5:]: v["value"] for k, v in line["metrics"].items()
         if k.startswith("tiny_")}
    # the readers of whole spans need the device plane's reduction: the chip's
    assert {"exchange_mb_per_query", "gsx4_sort_recv_max_mrows_per_query",
            "gsx4_sort_recv_cap_mrows_per_query", "sum_scans_32bit_share",
            "exchange_ride_share", "key_sort_folded_share",
            # the query module's own spans, on the host's clock
            "groupby_call_ms", "sort_call_ms"} == set(m), sorted(m)
    # ... but the spans they read are in this run's trace, with the
    # arguments that tell the two exchanges apart
    from lib import xspace
    events = xspace.read_events(xspace.newest_trace(
        os.path.join(bench_dir, "out")))
    host = [(n, a) for n, _s, _d, a in events["host"]]
    names = [n for n, _ in host]
    n_traced = 3
    assert names.count("cylon.host.sort_splitters") == n_traced
    for region in ("groupby.combine", "groupby.shuffle", "groupby.final",
                   "sort.sample", "sort.exchange"):
        assert names.count("cylon." + region) == n_traced, region
    sites = [a["site"] for n, a in host if n == "cylon.exchange.flat"]
    assert sites == ["groupby.recv", "sort.recv"] * n_traced
    of_sort = [a for n, a in host if n == "cylon.sort.exchange"]
    from cylon_tpu import config
    assert all(0 < int(a["samples"]) <= config.sort_samples(4)
               for a in of_sort)
    assert all(int(a["recv_max"]) <= int(a["recv_cap"]) for a in of_sort)
    # phase 1 sums bounded values (32-bit scan), phase 2 partial sums whose
    # bounds nobody knows (pair64): half and half
    assert m["sum_scans_32bit_share"] == 0.5
    said = [ln for ln in err.splitlines() if "sorted result a chip: " in ln]
    per_chip = json.loads(said[-1].split("a chip: ")[1].split(" rows")[0])
    assert m["gsx4_sort_recv_max_mrows_per_query"] == pytest.approx(
        max(per_chip) * 1e-6, rel=1e-12)
    assert m["gsx4_sort_recv_cap_mrows_per_query"] == pytest.approx(
        config.pow2ceil(max(per_chip)) * 1e-6, rel=1e-12)
    # every group leaves the sort's exchange on exactly one device
    cfg = files.load_json(bench_dir, "configs", "tiny_" + CONFIG)
    host = generate.host_tables(bench_dir, cfg, seed)
    assert sum(per_chip) == len(np.unique(host["t"]["k"]))


def test_control_is_caught_at_a_small_size():
    """float32 sums of values up to 90M: not correct (the cell's own size
    is ``control.py``'s, on the builder's machine)."""
    cfg = copy.deepcopy(files.load_json(BENCH_DIR, "configs", CONFIG))
    # the value column keeps the source's range, the rows are cut
    cfg["tables"]["t"]["rows"] = 200_000
    qm = files.load_module(BENCH_DIR, "queries", "dist_groupby_sort")
    host = generate.host_tables(BENCH_DIR, cfg, 2**31 + 46)
    host["t"]["a"] = host["t"]["a"] * 500          # [0, 90M)
    q = cfg["query"]
    assert host["t"]["a"].max() > 2**24
    numbers = compare.columns(qm.control(host, q, 1), qm.reference(host, q, 1))
    assert compare.verdict(numbers) is False
    own = compare.columns(qm.reference(host, q, 1), qm.reference(host, q, 1))
    assert compare.verdict(own) is True


def test_module_takes_the_one_chip_query_as_it_stands():
    qm = files.load_module(BENCH_DIR, "queries", "dist_groupby_sort")
    one = files.load_module(BENCH_DIR, "queries", "groupby_sort")
    for name in ("SPANS", "make_tables", "query", "reference", "control",
                 "canonical", "extra_numbers"):
        a, b = getattr(qm, name), getattr(one, name)
        assert a == b or a.__code__ == b.__code__, name
    assert qm.own_checks is not one.own_checks
