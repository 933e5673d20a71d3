"""``lib/xspace.py``: the wire format on a trace encoded by hand, the
reduction on events written out by hand, the four readers on both, and all
of it on the traces recorded on the chip and kept beside this file (PR 25's,
from before the names, and PR 26's two, with them)."""

from __future__ import annotations

import os
import struct

import pytest

from lib import files, xplane, xspace

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH_DIR, "tests", "data")
BEFORE_NAMES = os.path.join(DATA, "join_groupby_32m.3queries.xplane.pb")
JOIN = os.path.join(DATA, "join_groupby_32m.pr26.xplane.pb")
GSORT = os.path.join(DATA, "groupby_sort_25m.pr26.xplane.pb")


# ---- a trace encoded by hand ------------------------------------------------

def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, float):
        return _varint(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _stat(meta_id: int, value) -> bytes:
    if isinstance(value, str):
        return _f(1, meta_id) + _f(5, value)
    if isinstance(value, float):
        return _f(1, meta_id) + _f(2, value)
    return _f(1, meta_id) + _f(4, value)


def _plane(name, lines, event_meta, stat_meta) -> bytes:
    body = _f(2, name)
    for lname, ts, events in lines:
        line = _f(2, lname) + _f(3, ts)
        for mid, off_ps, dur_ps, stats in events:
            ev = _f(1, mid) + _f(2, off_ps) + _f(3, dur_ps)
            for s in stats:
                ev += _f(4, s)
            line += _f(4, ev)
        body += _f(3, line)
    for mid, (mname, stats) in event_meta.items():
        md = _f(1, mid) + _f(2, mname)
        for s in stats:
            md += _f(5, s)
        body += _f(4, _f(1, mid) + _f(2, md))
    for sid, sname in stat_meta.items():
        body += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return body


SORT = ("%sort = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %a, s32[8]{0} %b), "
        "dimensions={0}")
RW = "%reduce-window.3 = s32[4,2]{1,0} reduce-window(s32[4,2]{1,0} %p)"
COPY = "%copy.1 = s32[8]{0} copy(s32[8]{0} %p)"


@pytest.fixture()
def by_hand(tmp_path):
    """One chip, one program ``jit_join__count_fn`` at 1000-1900 ns holding
    a sort under cylon.join/cylon.sort_keys (1000-1600), a reduce-window
    with NO metadata (1600-1800) and a copy with a tf_op but no stage
    (1800-1900); host: bench.query 0-2000 ns, cylon.join.sort_count 0-500
    holding cylon.launch.join__count_fn 100-200, and cylon.pull.host_array
    500-1950 with bytes=4 and a session."""
    stat_meta = {1: "tf_op", 2: "bytes", 3: "session", 4: "flops"}
    dev = _plane("/device:TPU:0", [
        ("XLA Modules", 0, [(10, 1_000_000, 900_000, [])]),
        ("XLA Ops", 0, [(11, 1_000_000, 600_000, [_stat(4, 7)]),
                        (12, 1_600_000, 200_000, []),
                        (13, 1_800_000, 100_000, [])]),
        ("Async XLA Ops", 0, [(13, 0, 5_000_000, [])]),
    ], {10: ("jit_join__count_fn(123456789)", []),
        11: (SORT, [_stat(1, "jit(join__count_fn)/cylon.join/"
                             "cylon.sort_keys/sort:")]),
        12: (RW, []),
        13: (COPY, [_stat(1, "jit(join__count_fn)/copy:")])}, stat_meta)
    host = _plane("/host:CPU", [
        ("python3", 50, [(20, 0, 2_000_000, []),
                         (21, 0, 500_000, []),
                         (22, 100_000, 100_000, []),
                         (23, 500_000, 1_450_000,
                          [_stat(2, 4), _stat(3, "tenantA")])]),
    ], {20: ("bench.query", []), 21: ("cylon.join.sort_count", []),
        22: ("cylon.launch.join__count_fn", []),
        23: ("cylon.pull.host_array", [])}, stat_meta)
    path = tmp_path / "by_hand.xplane.pb"
    path.write_bytes(_f(1, dev) + _f(1, host))
    return str(path)


def test_wire_format_by_hand(by_hand):
    ev = xspace.read_events(by_hand)
    assert list(ev["device"]) == ["/device:TPU:0"]
    chip = ev["device"]["/device:TPU:0"]
    assert chip["modules"] == [("join__count_fn", 1000.0, 900.0)]
    assert chip["ops"] == [
        ("sort sort s32[8]x2", "sort_keys", 1000.0, 600.0),
        ("reduce-window reduce-window.3 s32[4,2]", "scan", 1600.0, 200.0),
        ("copy copy.1 s32[8]", None, 1800.0, 100.0)]
    assert ev["spans"] == [("query", 50.0, 2000.0)]
    assert [(n, s, d) for n, s, d, _ in ev["host"]] == [
        ("cylon.join.sort_count", 50.0, 500.0),
        ("cylon.launch.join__count_fn", 150.0, 100.0),
        ("cylon.pull.host_array", 550.0, 1450.0)]
    assert ev["host"][2][3] == {"bytes": 4, "session": "tenantA"}


def test_reduction_by_hand(by_hand):
    r = xspace.reduce(xspace.read_events(by_hand))
    assert r["n_queries"] == 1 and r["n_chips"] == 1
    assert r["window_s"] == pytest.approx(2000e-9)
    # program seconds sum to busy seconds (the operations fill the program)
    assert sum(r["program_s"].values()) == pytest.approx(r["busy_s"])
    assert r["busy_s"] == pytest.approx(900e-9) == pytest.approx(r["ops_s"])
    # a builder's stage seconds sum to its program's
    mine = {stg: s for (b, stg), s in r["builder_stage_s"].items()
            if b == "join__count_fn"}
    assert mine == {"sort_keys": pytest.approx(600e-9),
                    "scan": pytest.approx(200e-9),
                    None: pytest.approx(100e-9)}
    assert sum(mine.values()) == pytest.approx(
        r["program_s"]["join__count_fn"])
    assert r["host_n"] == {"cylon.join.sort_count": 1,
                           "cylon.launch.join__count_fn": 1,
                           "cylon.pull.host_array": 1}
    assert r["host_s"]["cylon.pull.host_array"] == pytest.approx(1450e-9)
    # gaps (the host line starts at 50): 50-1000 has its middle (525) in
    # sort_count (50-550) and past its launch; 1900-2050 has its middle
    # (1975) in the pull (550-2000)
    assert r["gap_s"] == {"cylon.join.sort_count": pytest.approx(950e-9),
                          "cylon.pull.host_array": pytest.approx(150e-9)}


def _events():
    """Two queries 0-100 and 100-200 ns on one chip; program A 10-60 (ops:
    scan 10-40, segment_starts 40-60), program B 120-180 (one unscoped
    op).  Host: launch.A 0-5 inside join.sort_count 0-8; pull 8-100;
    launch.B 100-104; a gap 60-120 whose middle (90) is in the pull; a gap
    180-200 whose middle is in no cylon span but in bench groupby_call."""
    return {
        "device": {"/device:TPU:0": {
            "modules": [("A", 10.0, 50.0), ("B", 120.0, 60.0)],
            "ops": [("reduce-window rw.1 s32[8]", "scan", 10.0, 30.0),
                    ("fusion f.2 s32[8]", "segment_starts", 40.0, 20.0),
                    ("copy c.3 s32[8]", None, 120.0, 60.0)]}},
        "host": [("cylon.join.sort_count", 0.0, 8.0, {}),
                 ("cylon.launch.A", 0.0, 5.0, {}),
                 ("cylon.pull.host_array", 8.0, 92.0, {"bytes": 4}),
                 ("cylon.launch.B", 100.0, 4.0, {})],
        "spans": [("query", 0.0, 100.0), ("join_call", 0.0, 100.0),
                  ("query", 100.0, 100.0), ("groupby_call", 101.0, 99.0)],
    }


def test_gap_goes_to_the_innermost_program_span_else_the_benchmarks():
    r = xspace.reduce(_events())
    assert r["busy_s"] == pytest.approx(110e-9)
    assert r["gap_s"] == {
        "cylon.launch.A": pytest.approx(10e-9),         # 0-10, middle 5
        "cylon.pull.host_array": pytest.approx(60e-9),  # 60-120, middle 90
        "groupby_call": pytest.approx(20e-9)}           # 180-200: bench's
    assert sum(r["gap_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_nothing_to_read_is_none():
    ev = _events()
    assert xspace.reduce({**ev, "device": {}}) is None
    assert xspace.reduce({**ev, "spans": []}) is None


def test_stage_is_the_innermost_scope():
    assert xspace.stage_of(
        "jit(f)/cylon.groupby/cylon.scan/jit(cumsum)/add:") == "scan"
    assert xspace.stage_of("jit(f)/cylon.join/sort:") == "join"
    assert xspace.stage_of("jit(per_shard)/gather:") is None
    assert xspace.stage_of("jit(f)/my_cylon.thing/x:") is None
    assert xspace.stage_of(None) is None
    assert xspace.builder_of("jit_join__count_fn(27698916841)") \
        == "join__count_fn"
    assert xspace.builder_of("jit_per_shard") == "per_shard"


# ---- the readers ------------------------------------------------------------

def _read(reader: str, args: dict):
    return files.load_module(BENCH_DIR, "readers", reader).read({}, args)


READERS = ("trace_program_ms", "trace_stage_ms", "trace_unscoped_share",
           "trace_host_span")


@pytest.fixture()
def this_run(monkeypatch):
    """Stand ``reduced`` in for this run's trace, in the ``xspace`` each
    reader module holds (``test_rehearsal`` reloads ``lib``, so that need
    not be the module this file imported)."""
    def use(reduced):
        for r in READERS:
            mod = files.load_module(BENCH_DIR, "readers", r)
            monkeypatch.setattr(mod.xspace, "reduced_of_this_run",
                                lambda: reduced)
    return use


def test_readers_on_events_by_hand(this_run):
    this_run(xspace.reduce(_events()))
    assert _read("trace_program_ms", {"builder": "^A$"}) == \
        pytest.approx(1e3 * 50e-9 / 2)
    assert _read("trace_program_ms", {"builder": "^(A|B)$"}) == \
        pytest.approx(1e3 * 110e-9 / 2)
    assert _read("trace_program_ms", {"builder": "^C$"}) is None
    assert _read("trace_stage_ms", {"stage": "scan"}) == \
        pytest.approx(1e3 * 30e-9 / 2)
    assert _read("trace_stage_ms", {"stage": "scan", "builder": "^B$"}) \
        is None
    assert _read("trace_stage_ms", {"stage": "segment_gather"}) is None
    assert _read("trace_unscoped_share", {}) == pytest.approx(100 * 60 / 110)
    launch = {"span": r"^cylon\.launch\."}
    assert _read("trace_host_span", {**launch, "what": "count"}) == 1.0
    assert _read("trace_host_span", {**launch, "what": "ms"}) == \
        pytest.approx(1e3 * 9e-9 / 2)
    assert _read("trace_host_span", {"span": r"^cylon\.pull\.",
                                     "what": "count"}) == 0.5
    assert _read("trace_host_span", {"span": "^cylon\\.nothing",
                                     "what": "ms"}) is None
    with pytest.raises(ValueError):
        _read("trace_host_span", {**launch, "what": "seconds"})


def test_readers_find_nothing_without_a_trace(this_run):
    this_run(None)
    for reader, args in [("trace_program_ms", {"builder": "."}),
                         ("trace_stage_ms", {"stage": "scan"}),
                         ("trace_unscoped_share", {}),
                         ("trace_host_span", {"span": ".", "what": "ms"})]:
        assert _read(reader, args) is None


def test_newest_trace_of_this_process(tmp_path):
    out = tmp_path / "out"
    old = out / "trace.cell.1" / "plugins" / "profile" / "x"
    new = out / "trace.cell.2" / "plugins" / "profile" / "y"
    for d in (old, new):
        d.mkdir(parents=True)
    (old / "h.xplane.pb").write_bytes(b"")
    (new / "h.xplane.pb").write_bytes(b"")
    os.utime(old / "h.xplane.pb", (1000.0, 1000.0))
    os.utime(new / "h.xplane.pb", (2000.0, 2000.0))
    assert xspace.newest_trace(str(out), since=0.0) == \
        str(new / "h.xplane.pb")
    assert xspace.newest_trace(str(out), since=1500.0) == \
        str(new / "h.xplane.pb")
    assert xspace.newest_trace(str(out), since=3000.0) is None
    assert xspace.newest_trace(str(tmp_path / "none")) is None


# ---- the recorded traces ----------------------------------------------------

def test_trace_from_before_the_names(this_run):
    """PR 25's chip trace: both programs are ``per_shard``, no operation
    carries a stage; only the reduce-window rule finds anything.  The
    readers return None, as they must on the parent's side of a check."""
    ev = xspace.read_events(BEFORE_NAMES)
    r = xspace.reduce(ev)
    old = xplane.reduce(xplane.read_events(BEFORE_NAMES))
    assert r["busy_s"] == pytest.approx(old["busy_s"], rel=1e-6)
    assert r["window_s"] == pytest.approx(old["window_s"], rel=1e-9)
    assert set(r["program_s"]) == {"per_shard"}
    assert set(r["stage_s"]) == {None, "scan"}
    assert r["host_s"] == {}
    this_run(r)
    assert _read("trace_program_ms", {"builder": "^join__count_fn$"}) is None
    assert _read("trace_stage_ms", {"stage": "liveness"}) is None
    assert _read("trace_unscoped_share", {}) is None
    assert _read("trace_host_span", {"span": r"^cylon\.launch\.",
                                     "what": "count"}) is None
    assert _read("trace_stage_ms", {"stage": "scan"}) == \
        pytest.approx(463.0, abs=0.5)   # reduce-windows 449.6 + stitching


@pytest.mark.parametrize("path,programs", [
    (JOIN, {"join__count_fn", "fused__fused_fn"}),
    (GSORT, {"groupby__raw_fn", "sort__local_sort_fn"})])
def test_recorded_traces_with_names(path, programs, this_run):
    """My chip runs, PR 26, three traced queries a cell, one TPU v5 lite:
    the programs carry their builders' names and sum to the busy time; a
    builder's stages sum to its program; nearly nothing is unscoped."""
    r = xspace.reduce(xspace.read_events(path))
    assert r["n_queries"] == 3 and r["n_chips"] == 1
    assert programs <= set(r["program_s"])
    assert "per_shard" not in r["program_s"]
    assert sum(r["program_s"].values()) == pytest.approx(r["busy_s"],
                                                         rel=0.01)
    for b in programs:
        stages = sum(s for (bb, _), s in r["builder_stage_s"].items()
                     if bb == b)
        assert stages == pytest.approx(r["program_s"][b], rel=0.02)
    this_run(r)
    assert _read("trace_unscoped_share", {}) < 2.0
    assert _read("trace_host_span", {"span": r"^cylon\.launch\.",
                                     "what": "count"}) == 2.0
    assert _read("trace_host_span", {"span": r"^cylon\.pull\.",
                                     "what": "count"}) >= 1.0
    assert all(n.startswith("cylon.") or not n.startswith("bench.")
               for n in r["gap_s"])
    # the largest operation by (builder, stage, instruction): what PERF.md
    # §5 names - the liveness gather; XLA's segment-space gather
    top = max(r["op_s"], key=r["op_s"].get)
    assert top in {
        ("join__count_fn", "liveness", "fusion fusion pred[65011712]"),
        ("groupby__raw_fn", "segment_gather",
         "fusion fusion u32[15204352,3]")}
