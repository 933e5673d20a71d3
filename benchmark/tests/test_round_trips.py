"""``readers/trace_round_trips.py``: the matching and the classes on events
written out by hand (two chips, one query of 100 ms; every number below is
reckoned in the comments), on the traces recorded on the chip and kept
beside this file (a parent's: no ``cylon.op.*``), and the ten metrics through
``run.py --trace 1`` on a two-device twin of the four-chip cell."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import helpers
from lib import files, xplane, xspace

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
CELL = "dist_join_groupby_8m_x4"
TEN = ("idle_in_pull_ms", "idle_in_launch_ms", "idle_in_turn_ms",
       "idle_outside_ops_ms", "turn_host_ms", "turn_named_share",
       "launch_to_start_ms", "launch_start_after_return_ms",
       "launch_chip_skew_ms", "pull_wake_ms")
MS = 1e6                                             # the trace's clock: ns


@pytest.fixture(scope="module")
def rt():
    return files.load_module(BENCH_DIR, "readers", "trace_round_trips")


def _events(ops: bool = True, drop_a_program: bool = False) -> dict:
    """One query [0, 100] ms on two chips.

    host   op.join [1, 60]: launch.A [2, 4]  launch.B [10, 11]
           pull.host_array [12, 33]  host.join_plan [34, 38]
           launch.C [40, 41]  pull.sync [45, 50]
    chip0  A [5, 20]  B [20, 30]  C [40.5, 55]
    chip1  A [7, 20]  B [20, 30]  C [40.5, 55]
    """
    def host(name, a, b):
        return ("cylon." + name, a * MS, (b - a) * MS, {})

    def chip(a_start):
        progs = [("m_A", a_start, 20), ("m_B", 20, 30), ("m_C", 40.5, 55)]
        if drop_a_program and a_start == 7:
            progs = progs[:2]
        return {"modules": [(b, s * MS, (e - s) * MS) for b, s, e in progs],
                "ops": [("fusion f", None, s * MS, (e - s) * MS)
                        for _b, s, e in progs]}

    h = [host("launch.m_A", 2, 4), host("launch.m_B", 10, 11),
         host("pull.host_array", 12, 33), host("launch.m_C", 40, 41),
         host("pull.sync", 45, 50)]
    if ops:
        h += [host("op.join", 1, 60), host("host.join_plan", 34, 38)]
    return {"device": {"/device:TPU:0": chip(5), "/device:TPU:1": chip(7)},
            "host": sorted(h, key=lambda x: x[1]),
            "spans": [("query", 0.0, 100 * MS)]}


def test_launches_matched_to_their_programs(rt):
    r = rt.round_trips(_events())
    by = {(t["place"], t["name"]): t for t in r["trips"]}
    # A: the chips are free; it starts 3 and 5 ms after the launch began
    # (mean 4), 1 and 3 ms after the call returned (2), 2 ms apart
    a = by[(0, "launch.m_A")]
    assert (a["late_ms"], a["late_after_return_ms"], a["skew_ms"]) \
        == pytest.approx((4.0, 2.0, 2.0))
    # B: enqueued behind a busy chip - it starts as A ends: nothing is late
    b = by[(1, "launch.m_B")]
    assert (b["late_ms"], b["late_after_return_ms"], b["skew_ms"]) \
        == (0.0, 0.0, 0.0)
    # C: starts 0.5 ms into the launch call, before the call returned
    c = by[(3, "launch.m_C")]
    assert (c["late_ms"], c["late_after_return_ms"]) \
        == pytest.approx((0.5, 0.0))
    assert r["late_ms"] == pytest.approx(4.5)
    assert r["late_after_return_ms"] == pytest.approx(2.0)
    assert r["skew_ms"] == pytest.approx(2.0)
    assert r["mismatch"] is None


def test_a_program_before_its_launch_says_the_clocks_are_apart(rt, capsys):
    """The profiler aligns host and device to about a millisecond: where a
    matched program starts BEFORE its launch began the reduction says by
    how much (a lower bound), and nothing where none does."""
    assert rt.round_trips(_events())["clocks_apart_ms"] == 0.0
    assert "clocks" not in capsys.readouterr().err
    ev = _events()
    for chip in ev["device"].values():     # C: 0.25 ms before launch [40, 41]
        chip["modules"][2] = ("m_C", 39.75 * MS, 15.25 * MS)
        chip["ops"][2] = ("fusion f", None, 39.75 * MS, 15.25 * MS)
    r = rt.round_trips(ev)
    assert r["clocks_apart_ms"] == pytest.approx(0.25)
    assert "0.250 ms before its launch began" in capsys.readouterr().err
    by = {(t["place"], t["name"]): t for t in r["trips"]}
    assert by[(3, "launch.m_C")]["late_ms"] == 0.0


def test_pulls_matched_to_the_devices_last_operation(rt):
    r = rt.round_trips(_events())
    by = {(t["place"], t["name"]): t for t in r["trips"]}
    # returned at 33, the last operation ended at 30 on both chips
    assert by[(2, "pull.host_array")]["wake_ms"] == pytest.approx(3.0)
    # returned at 50 while C (until 55) still runs
    assert by[(4, "pull.sync")]["wake_ms"] == 0.0
    assert r["wake_ms"] == pytest.approx(3.0)
    # a pull that begins after the device has finished waits from its own
    # start, not from the device's end
    ev = _events()
    ev["host"].append(("cylon.pull.host_array", 70 * MS, 2 * MS, {}))
    assert rt.round_trips(ev)["wake_ms"] == pytest.approx(3.0 + 2.0)


def test_idle_gaps_are_cut_where_the_class_changes(rt):
    """chip0 is idle [0, 5], [30, 40.5], [55, 100]; chip1 [0, 7] and the
    same.  [30, 40.5] straddles the pull (to 33), a turn (to 40) and launch
    C; [55, 100] the turn to 60 and what is outside the operator call."""
    ev = _events()
    r = rt.round_trips(ev)
    assert r["idle_ms"] == pytest.approx(
        {"pull": 3.0, "launch": 2.5, "turn": 15.0, "outside": 41.0})
    red = xplane.reduce({"device": {p: [(lab, s, d) for lab, _g, s, d
                                        in c["ops"]]
                                    for p, c in ev["device"].items()},
                         "spans": ev["spans"]})
    assert sum(r["idle_ms"].values()) == pytest.approx(
        1e3 * red["idle_share"] * red["window_s"] / red["n_queries"])
    # the host's side tiles the operator call: 4 + 26 + 29 = 59
    assert (r["host_ms"]["launch"], r["host_ms"]["pull"],
            r["host_ms"]["turn"]) == pytest.approx((4.0, 26.0, 29.0))
    assert r["op_ms"] == pytest.approx(59.0)
    assert r["turn_named_ms"] == pytest.approx(4.0)
    what = {k: f(r) for k, f in rt._WHAT.items()}
    assert what["turn_named_share"] == pytest.approx(100 * 4 / 29)
    assert what["idle_in_turn"] == pytest.approx(15.0)
    # by name: the turn after the first pull is [33, 40], 7 ms idle on both
    # chips, and holds the named step
    t = next(t for t in r["trips"] if t["name"] == "pull.host_array")
    assert (t["turn_host_ms"], t["turn_idle_ms"], t["turn_named"]) \
        == (pytest.approx(7.0), pytest.approx(7.0), ["host.join_plan"])
    first = next(t for t in r["trips"] if t["place"] == -1)
    assert first["name"] == "op.join begins"
    assert first["turn_host_ms"] == pytest.approx(1.0)


def test_a_boundary_nested_in_another_goes_to_the_innermost(rt):
    segs = rt.tile([("op.x", 0, 20)],
                   [("launch", "l", 0, 10), ("pull", "p", 2, 4)], 0, 30)
    assert [(a, b, cls) for a, b, cls, _i in segs] == [
        (0, 2, "launch"), (2, 4, "pull"), (4, 10, "launch"),
        (10, 20, "turn"), (20, 30, "outside")]
    assert segs[3][3] == 0                 # the turn follows the launch
    assert rt.outermost([("a", 0, 10), ("b", 2, 4), ("c", 12, 14)]) \
        == [("a", 0, 10), ("c", 12, 14)]


def test_counts_that_differ_match_nothing(rt, capsys):
    r = rt.round_trips(_events(drop_a_program=True))
    err = capsys.readouterr().err
    assert "1 cylon.launch.m_C spans but 0 jit_m_C programs on " \
        "/device:TPU:1" in err
    assert r["mismatch"] and r["late_ms"] is None and r["skew_ms"] is None
    assert r["late_after_return_ms"] is None
    assert r["wake_ms"] == pytest.approx(3.0)      # pulls need no matching
    # the classes need none either: chip 1, without C, is idle through the
    # second pull as well (3 + 5 / 2)
    assert r["idle_ms"]["pull"] == pytest.approx(5.5)
    assert rt._WHAT["launch_to_start"](r) is None


def test_a_parent_without_operator_spans(rt):
    """``idle_in_pull`` / ``idle_in_launch`` and the matched numbers read;
    what needs ``cylon.op.*`` is None - written by hand, and on the traces
    PR 26 recorded on the chip (no device number is asserted of them but
    that the classes sum to the idle time)."""
    r = rt.round_trips(_events(ops=False))
    what = {k: f(r) for k, f in rt._WHAT.items()}
    assert what["idle_in_pull"] == pytest.approx(3.0)
    assert what["idle_in_launch"] == pytest.approx(2.5)
    assert what["launch_to_start"] == pytest.approx(4.5)
    assert [k for k, v in what.items() if v is None] == [
        "idle_in_turn", "idle_outside_ops", "turn_host", "turn_named_share"]
    for name in ("join_groupby_32m.pr26.xplane.pb",
                 "groupby_sort_25m.pr26.xplane.pb"):
        path = os.path.join(DATA, name)
        r = rt.of_trace(path)
        red = xplane.reduce(xplane.read_events(path))
        assert sum(r["idle_ms"].values()) == pytest.approx(
            1e3 * red["idle_share"] * red["window_s"] / red["n_queries"],
            rel=1e-4)
        what = {k: f(r) for k, f in rt._WHAT.items()}
        assert what["idle_in_turn"] is None and what["turn_host"] is None
        assert what["idle_in_pull"] > 0 and what["pull_wake"] > 0
        assert what["launch_to_start"] > 0 and r["mismatch"] is None
        assert what["launch_chip_skew"] == 0.0            # one chip
    assert rt.round_trips({"device": {}, "host": [], "spans": []}) is None


# ---- through run.py, on two CPU devices --------------------------------------

_DRIVER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
bench_dir, tests_dir, repo_dir = sys.argv[1:4]
sys.path[:0] = [repo_dir, bench_dir, tests_dir]
import helpers
run = helpers.load_run(bench_dir)
import cylon_tpu as ct
from cylon_tpu.ctx.context import CPUMeshConfig
from lib import xspace
run.check_device = lambda chips: jax.devices("cpu")[:chips]
run.make_env = lambda chips: ct.CylonEnv(config=CPUMeshConfig(world_size=chips))
traced = run._traced_queries
def traced_on_cpu(one, n, spans, trace_dir):
    # the real profiler: the program's spans are in a trace file of this run
    try:
        return traced(one, n, spans, trace_dir)
    except RuntimeError as e:
        assert "no device operation" in str(e), e
        return {"n_queries": n, "n_chips": 2, "busy_s": 0.9, "window_s": 1.0,
                "idle_share": 0.1, "op_seconds": [], "gap_seconds": []}
run._traced_queries = traced_on_cpu
read_events = xspace.read_events
def with_stand_in_chips(path):
    # a CPU trace has no device plane: every launch's program stands in,
    # 0.1 ms (chip 0) / 0.2 ms (chip 1) after its call returned, for 0.05 ms
    ev = read_events(path)
    assert not ev["device"]
    for c in range(2):
        progs = [(n[len("cylon.launch."):], s + d + 1e5 * (c + 1), 5e4)
                 for n, s, d, _a in ev["host"]
                 if n.startswith("cylon.launch.")]
        ev["device"][f"/device:TPU:{c}"] = {
            "modules": progs,
            "ops": [("fusion f", None, s, d) for _b, s, d in progs]}
    return ev
xspace.read_events = with_stand_in_chips
sys.exit(run.main(["--workload", sys.argv[4], "--seed", sys.argv[5],
                   "--seconds", "0.5", "--trace", "1"]))
"""


def test_two_device_twin_prints_the_ten_metrics(tmp_path, rt):
    """65,536 rows a side over two CPU devices through ``run.py --trace 1``:
    the ten names are on the line (those with no list reach the twin as
    they are; ``launch_chip_skew_ms`` and the others with one through a
    twin file), the idle classes
    are cut out of the stand-in chips' gaps, and the host's side tiles the
    operator calls of a REAL run: launch + pull + turn = the outermost
    ``cylon.op.*`` spans."""
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    cell = files.load_json(bench_dir, "workloads", "tiny_" + CELL)
    cfg = files.load_json(bench_dir, "configs", cell["config"])
    cell.update(name="two_" + CELL, chips=2, config="two_" + cfg["name"])
    cell["expect"]["exchange"]["off_diagonal_share"] = [0.3, 0.7]
    cfg.update(name=cell["config"], world_size=2)
    for kind, obj in (("workloads", cell), ("configs", cfg)):
        with open(os.path.join(bench_dir, kind, obj["name"] + ".json"),
                  "w") as f:
            json.dump(obj, f)
    # one of the ten with no list (PR 51) reaches the twin under its own
    # name with no file; one that has a list gets a twin, as a file-only PR
    # would write it
    for name in TEN:
        m = files.load_json(bench_dir, "metrics", name)
        assert m["reader"] == "trace_round_trips"
        if "workloads" not in m:
            continue
        assert CELL in m["workloads"]
        m.update(name="two_" + name, workloads=[cell["name"]])
        with open(os.path.join(bench_dir, "metrics", m["name"] + ".json"),
                  "w") as f:
            json.dump(m, f)
    seed = 2**31 + 39
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER, bench_dir,
         os.path.dirname(os.path.abspath(__file__)),
         os.path.dirname(BENCH_DIR), cell["name"], str(seed)],
        capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = helpers.last_json_line(proc.stdout)
    assert line["correct"] is True, line["compared"]
    m = {k.removeprefix("two_"): v for k, v in line["metrics"].items()
         if k.removeprefix("two_") in TEN}
    assert set(m) == set(TEN)
    assert "two_launch_chip_skew_ms" in line["metrics"]   # it has a list
    assert {k: v["unit"] for k, v in m.items()} == {
        k: "%" if k == "turn_named_share" else "ms" for k in TEN}
    v = {k: x["value"] for k, x in m.items()}
    # each of the 13 launches' stand-ins starts 0.1 / 0.2 ms after the call
    assert v["launch_chip_skew_ms"] == pytest.approx(13 * 0.1, rel=1e-6)
    assert v["launch_start_after_return_ms"] > 0
    assert v["launch_to_start_ms"] >= v["launch_start_after_return_ms"]
    assert 0 < v["turn_named_share"] <= 100 and v["turn_host_ms"] > 0
    # the trace file itself: the classes sum, the host's side tiles
    path = xspace.newest_trace(os.path.join(bench_dir, "out"), since=0.0)
    ev = xspace.read_events(path)
    assert {n for n, *_ in ev["host"] if n.startswith("cylon.op.")} == {
        "cylon.op.join", "cylon.op.shuffle", "cylon.op.groupby"}
    for c in range(2):
        progs = [(n[len("cylon.launch."):], s + d + 1e5 * (c + 1), 5e4)
                 for n, s, d, _a in ev["host"]
                 if n.startswith("cylon.launch.")]
        ev["device"][f"/device:TPU:{c}"] = {
            "modules": progs,
            "ops": [("fusion f", None, s, d) for _b, s, d in progs]}
    r = rt.round_trips(ev)
    assert r["host_ms"]["launch"] + r["host_ms"]["pull"] \
        + r["host_ms"]["turn"] == pytest.approx(r["op_ms"], abs=1e-6)
    red = xplane.reduce({"device": {p: [(lab, s, d) for lab, _g, s, d
                                        in c["ops"]]
                                    for p, c in ev["device"].items()},
                         "spans": ev["spans"]})
    assert sum(r["idle_ms"].values()) == pytest.approx(
        1e3 * red["idle_share"] * red["window_s"] / red["n_queries"])
    assert v["idle_in_turn_ms"] == pytest.approx(r["idle_ms"]["turn"])
