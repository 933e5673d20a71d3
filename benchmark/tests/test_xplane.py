"""The reduction from trace events to numbers: on events written out by
hand (the arithmetic), and on a small trace recorded on the chip and kept
beside this file (the reading of the file: planes, lines, names, clock)."""

from __future__ import annotations

import os

import pytest

from lib import xplane

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "join_groupby_32m.3queries.xplane.pb")


def _events():
    # two queries, 0-100 and 100-200 (ns); device busy 10-40, 30-60 (overlap),
    # 120-180; a nested op inside the last
    return {
        "device": {"/device:TPU:0": [
            ("sort sort.1 s32[8]x2", 10.0, 30.0),
            ("fusion fusion.2 s32[8]", 30.0, 30.0),
            ("custom-call k.3 u32[8] target=tpu_custom_call", 120.0, 60.0),
            ("sort sort.4 s32[8]", 130.0, 10.0)]},
        "spans": [("query", 0.0, 100.0), ("join_call", 0.0, 20.0),
                  ("groupby_call", 20.0, 80.0),
                  ("query", 100.0, 100.0), ("groupby_call", 105.0, 95.0)],
    }


def test_busy_idle_and_gaps():
    r = xplane.reduce(_events())
    assert r["n_queries"] == 2 and r["n_chips"] == 1
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((50 + 60) * 1e-9)   # unions, not sums
    assert r["idle_share"] == pytest.approx(1 - 110 / 200)
    gaps = dict(r["gap_seconds"])
    # 0-10 inside join_call; 60-120 has its middle (90) in groupby_call;
    # 180-200 in the second groupby_call
    assert gaps["join_call"] == pytest.approx(10e-9)
    assert gaps["groupby_call"] == pytest.approx((60 + 20) * 1e-9)
    assert sum(gaps.values()) == pytest.approx((200 - 110) * 1e-9)


def test_op_sums_by_name():
    r = xplane.reduce(_events())
    assert xplane.op_seconds_matching(r, "^sort ") == pytest.approx(40e-9)
    assert xplane.op_seconds_matching(r, "target=tpu_custom_call$") == \
        pytest.approx(60e-9)
    assert xplane.op_seconds_matching(r, "^all-to-all ") is None   # not 0
    assert r["op_seconds"][0][0].startswith("custom-call k.3")


def test_gap_outside_any_span_and_clipping():
    ev = _events()
    ev["spans"] = [("query", 0.0, 50.0), ("query", 150.0, 50.0)]
    ev["device"]["/device:TPU:0"].append(("fusion f.9 s32[8]", 190.0, 100.0))
    r = xplane.reduce(ev)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx((50 + 60 + 10) * 1e-9)  # cut at 200
    assert dict(r["gap_seconds"])["between_queries"] == \
        pytest.approx(60e-9)       # 60-120: its middle is in no span


def test_nothing_to_read_is_none():
    assert xplane.reduce({"device": {}, "spans": [("query", 0.0, 1.0)]}) \
        is None
    assert xplane.reduce({"device": {"/device:TPU:0": [("a", 0.0, 1.0)]},
                          "spans": []}) is None


def test_two_chips_average():
    ev = _events()
    ev["device"]["/device:TPU:1"] = [("sort sort.1 s32[8]", 0.0, 200.0)]
    r = xplane.reduce(ev)
    assert r["n_chips"] == 2
    assert r["busy_s"] == pytest.approx((110 + 200) / 2 * 1e-9)


def test_label_of_an_instruction():
    text = ('%sort = (s32[65011712]{0:T(1024)}, s32[65011712]{0:T(1024)}) '
            'sort(s32[65011712]{0:T(1024)} %compare_select_fusion, '
            's32[65011712]{0:T(1024)} %iota.3), dimensions={0}, '
            'to_apply=%compare')
    assert xplane.label(text) == "sort sort s32[65011712]x2"
    text = ('%per_shard.1 = u32[8,13107200]{1,0:T(8,128)} custom-call('
            's32[51200]{0:T(1024)S(1)} %copy-done.16), '
            'custom_call_target="tpu_custom_call", operand_layout_'
            'constraints={s32[51200]{0}}')
    assert xplane.label(text) == ("custom-call per_shard.1 u32[8,13107200] "
                                  "target=tpu_custom_call")
    text = ('%fusion = pred[65011712]{0:T(1024)(128)(4,1)} fusion(pred[6501'
            '1712]{0:T(1024)(128)(4,1)S(1)} %custom-call.12), kind=kCustom')
    assert xplane.label(text) == "fusion fusion pred[65011712]"
    assert xplane.label("not an instruction") == "not an instruction"


def test_recorded_trace_of_three_queries():
    """``join_groupby_32m``, three traced queries on one TPU v5 lite (my
    chip run, PR 25, seed 101): two programs a query (the join's sort+count,
    1.178 s, and the fused join->groupby, 1.016 s), the device busy 99.7% of
    the window, the two sorts 0.64 s a query, the Pallas gather 57 ms."""
    ev = xplane.read_events(RECORDED)
    assert list(ev["device"]) == ["/device:TPU:0"]
    assert len(ev["device"]["/device:TPU:0"]) == 1200
    assert [n for n, _, _ in ev["spans"]] == \
        ["query", "join_call", "groupby_call"] * 3
    r = xplane.reduce(ev)
    assert r["n_queries"] == 3 and r["n_chips"] == 1
    assert r["window_s"] == pytest.approx(6.596876371, rel=1e-9)
    assert r["busy_s"] == pytest.approx(6.579474186, rel=1e-9)
    assert 100 * r["idle_share"] == pytest.approx(0.2638, abs=1e-3)
    assert xplane.op_seconds_matching(r, "^sort ") == \
        pytest.approx(1.920835251, rel=1e-9)
    assert xplane.op_seconds_matching(r, "target=tpu_custom_call$") == \
        pytest.approx(0.170615073, rel=1e-9)
    assert r["op_seconds"][0][0] == "fusion fusion pred[65011712]"
    gaps = dict(r["gap_seconds"])
    assert set(gaps) == {"join_call", "groupby_call"}
    assert sum(gaps.values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_trace_readers_leave_out_what_they_cannot_read():
    from lib import files
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ops = files.load_module(bench_dir, "readers", "trace_ops_ms")
    idle = files.load_module(bench_dir, "readers", "trace_idle_share")
    r = xplane.reduce(_events())
    assert ops.read({"trace": r}, {"pattern": "^sort "}) == \
        pytest.approx(1e3 * 40e-9 / 2)
    assert ops.read({"trace": r}, {"pattern": "^all-to-all "}) is None
    assert ops.read({"trace": None}, {"pattern": "^sort "}) is None
    assert idle.read({"trace": None}, {}) is None
    assert idle.read({"trace": r}, {}) == pytest.approx(45.0)
