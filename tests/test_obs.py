"""Tests for the unified observability subsystem (cylon_tpu.obs).

Fast tests (tier-1): metrics registry semantics (typed metrics, the
group/namespace migration shims, Prometheus exposition, JSON
snapshots), histogram quantiles bit-consistent with np.percentile (the
serving SLO acceptance), the shared bench_detail collector's key-schema
stability, flight-recorder ring wrap + postmortem content + session
tagging, the obs.export injection site surfacing typed, the
zero-overhead/zero-write unarmed contract, and the utils/timing edge
cases (reset clears the last-region breadcrumb, baton-park netting in
BOTH tables across nesting, sync_region/split_snapshot round-trip).

Slow tests: scripts/bench_smoke.py driven in a subprocess with
``CYLON_TPU_TRACE`` armed, validating the emitted Chrome-trace JSON
schema (pid/tid presence, ts monotonicity, per-piece dispatch spans,
balanced async in-flight pairs).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from cylon_tpu import config, obs
from cylon_tpu.obs import metrics, rank_report, trace
from cylon_tpu.status import (CylonKeyError, ExecutionError, InvalidError,
                              PredictedResourceExhausted)
from cylon_tpu.utils import timing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """Every test starts with the recorder disarmed, a fresh phase
    table, bench-mode flags restored and no armed injector."""
    from cylon_tpu.exec import recovery
    monkeypatch.delenv("CYLON_TPU_TRACE", raising=False)
    monkeypatch.delenv("CYLON_TPU_METRICS_JSON", raising=False)
    monkeypatch.delenv("CYLON_TPU_RANK_REPORT", raising=False)
    prev_bench, prev_async = config.BENCH_TIMINGS, config.TIMING_ASYNC
    trace.disarm()
    timing.reset()
    metrics._rearm_snapshots()
    recovery.install_faults("")
    yield
    trace.disarm()
    timing.reset()
    metrics._rearm_snapshots()
    recovery.install_faults("")
    config.BENCH_TIMINGS, config.TIMING_ASYNC = prev_bench, prev_async


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        c = metrics.counter("t_reg_c")
        c.inc()
        c.inc(4)
        assert metrics.counter("t_reg_c").value == 5
        g = metrics.gauge("t_reg_g")
        g.set(17)
        assert g.value == 17
        live = metrics.gauge("t_reg_live", fn=lambda: 42)
        assert live.value == 42

    def test_type_conflict_is_typed(self):
        metrics.counter("t_reg_conflict")
        with pytest.raises(InvalidError):
            metrics.gauge("t_reg_conflict")

    def test_group_is_dict_like_and_registry_backed(self):
        st = metrics.group("t_grp", ("a_events", "b_bytes"))
        st["a_events"] += 3
        st["b_bytes"] += 100
        assert dict(st) == {"a_events": 3, "b_bytes": 100}
        # the values live in the registry, not the view
        assert metrics.counter("t_grp_a_events").value == 3
        for k in st:
            st[k] = 0
        assert dict(st) == {"a_events": 0, "b_bytes": 0}

    def test_namespace_dynamic_keys(self):
        ns = metrics.namespace("t_ns")
        ns["x"] = ns.get("x", 0) + 7
        assert ns["x"] == 7 and ns.get("zzz") is None
        assert metrics.counter("t_ns_x").value == 7
        ns.clear()
        assert "x" not in ns
        assert metrics.counter("t_ns_x").value == 0

    def test_reset_prefix(self):
        metrics.counter("t_rst_one").inc(5)
        metrics.counter("other_t_rst").inc(5)
        metrics.reset("t_rst")
        assert metrics.counter("t_rst_one").value == 0
        assert metrics.counter("other_t_rst").value == 5

    def test_exec_stats_shims_are_registry_backed(self):
        from cylon_tpu.exec import checkpoint, memory
        checkpoint.reset_stats()
        memory.reset_stats()
        checkpoint._STATS["checkpoint_events"] += 2
        memory._STATS["spill_events"] += 1
        assert checkpoint.stats()["checkpoint_events"] == 2
        assert metrics.counter("ckpt_checkpoint_events").value == 2
        assert metrics.counter("memory_spill_events").value == 1
        checkpoint.reset_stats()
        memory.reset_stats()
        assert metrics.counter("ckpt_checkpoint_events").value == 0
        assert metrics.counter("memory_spill_events").value == 0


class TestHistogram:
    def test_percentiles_bit_consistent_with_sorted_list(self):
        """The serving-bench acceptance: histogram p50/p99 must equal
        np.percentile over the same observations EXACTLY."""
        h = metrics.histogram("t_hist_exact")
        h.reset()
        rng = np.random.default_rng(3)
        xs = list(rng.gamma(2.0, 0.05, 499))
        for x in xs:
            h.observe(x)
        arr = np.asarray(xs, float)
        for p in (50, 90, 99, 99.9):
            assert h.percentile(p) == float(np.percentile(arr, p)), p

    def test_bucket_counts_and_attainment(self):
        h = metrics.histogram("t_hist_buckets", buckets=(0.1, 1.0, 10.0))
        for x in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(x)
        assert sum(h.bucket_counts) == h.count == 5
        assert h.attainment(1.0) == 3 / 5
        assert h.attainment(0.01) == 0.0
        assert metrics.histogram("t_hist_buckets").value["count"] == 5

    def test_truncated_falls_back_to_buckets(self, monkeypatch):
        monkeypatch.setattr(metrics, "SAMPLE_CAP", 8)
        h = metrics.Histogram("t_hist_trunc")
        for x in np.linspace(0.01, 0.3, 40):
            h.observe(x)
        assert h.truncated
        p = h.percentile(50)
        assert p is not None and 0.0 < p < 1.0

    def test_empty_histogram_percentile_is_nan(self):
        """The edge contract (satellite fix): an EMPTY histogram's
        quantile is NaN — not None, not whatever np does on an empty
        array — so reports carry it through arithmetic and JSON."""
        import math
        h = metrics.Histogram("t_hist_empty")
        assert math.isnan(h.percentile(50))
        assert math.isnan(h.percentile(0)) and math.isnan(h.percentile(100))
        # the live-exposition property must not raise either — and it
        # exports the NaN as None so JSON snapshots stay strict-parseable
        v = h.value
        assert v["count"] == 0 and v["p50"] is None

    def test_fully_truncated_percentile_is_nan(self, monkeypatch):
        """Samples observed but NONE retained (cap exhausted before the
        first observation): bucket interpolation would fabricate a
        quantile from the grid alone — NaN by contract."""
        import math
        monkeypatch.setattr(metrics, "SAMPLE_CAP", 0)
        h = metrics.Histogram("t_hist_fully_trunc")
        for x in (0.5, 1.5, 2.5):
            h.observe(x)
        assert h.truncated and h.count == 3
        assert math.isnan(h.percentile(50))
        assert h.value["p99"] is None
        # attainment still answers from bucket counts
        assert h.attainment(100.0) > 0

    def test_percentile_range_is_typed(self):
        h = metrics.Histogram("t_hist_range")
        h.observe(1.0)
        for bad in (-1, 100.5, 1e9):
            with pytest.raises(InvalidError):
                h.percentile(bad)
        assert h.percentile(0) == h.percentile(100) == 1.0


class TestExposition:
    def test_prometheus_text_format(self):
        metrics.counter("t_prom_c").set(9)
        metrics.gauge("t_prom_g").set(3)
        h = metrics.histogram("t_prom_h", buckets=(1.0, 2.0))
        h.reset()
        h.observe(0.5)
        h.observe(1.5)
        text = metrics.prometheus_text()
        assert "# TYPE cylon_tpu_t_prom_c counter" in text
        assert "cylon_tpu_t_prom_c 9" in text
        assert "cylon_tpu_t_prom_g 3" in text
        assert 'cylon_tpu_t_prom_h_bucket{le="1"} 1' in text
        assert 'cylon_tpu_t_prom_h_bucket{le="2"} 2' in text
        assert 'cylon_tpu_t_prom_h_bucket{le="+Inf"} 2' in text
        assert "cylon_tpu_t_prom_h_count 2" in text
        # name sanitization: dots become underscores
        metrics.counter("t.prom.dotted").inc()
        assert "cylon_tpu_t_prom_dotted 1" in metrics.prometheus_text()

    def test_labelled_counter_is_one_series_a_label_set(self):
        """``counter(name, **labels)``: a counter a label set, the same
        handle on the same labels whatever their order, the family typed
        once in the exposition and each series spelled as Prometheus
        spells it."""
        a = metrics.counter("t_prom_lab", reason="x", site="s")
        assert metrics.counter("t_prom_lab", site="s", reason="x") is a
        b = metrics.counter("t_prom_lab", reason="y", site="s")
        a.set(2)
        b.set(5)
        text = metrics.prometheus_text()
        assert text.count("# TYPE cylon_tpu_t_prom_lab counter") == 1
        assert 'cylon_tpu_t_prom_lab{reason="x",site="s"} 2' in text
        assert 'cylon_tpu_t_prom_lab{reason="y",site="s"} 5' in text
        assert metrics.snapshot()['t_prom_lab{reason="y",site="s"}'] == 5

    def test_snapshot_carries_phase_collector(self):
        config.BENCH_TIMINGS = True
        timing.reset()
        with timing.region("t.snapcol"):
            pass
        snap = metrics.snapshot()
        assert "t.snapcol" in snap["phases"]

    def test_json_snapshot_write_and_poll(self, tmp_path, monkeypatch):
        path = str(tmp_path / "metrics.json")
        metrics.write_snapshot(path)
        doc = json.load(open(path, encoding="utf-8"))
        assert "ts" in doc and isinstance(doc["metrics"], dict)
        os.unlink(path)
        # armed poll: first call writes, second call inside the interval
        # does not
        monkeypatch.setenv("CYLON_TPU_METRICS_JSON", path)
        monkeypatch.setenv("CYLON_TPU_METRICS_INTERVAL_S", "3600")
        metrics._rearm_snapshots()
        assert metrics.maybe_write_snapshot() is True
        assert os.path.exists(path)
        os.unlink(path)
        assert metrics.maybe_write_snapshot() is False
        assert not os.path.exists(path)

    def test_unarmed_poll_writes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        metrics._rearm_snapshots()
        assert metrics.maybe_write_snapshot() is False
        assert os.listdir(tmp_path) == []


class TestBenchDetail:
    """The dedupe satellite's schema guarantee: the shared collector
    reports EXACTLY the keys each bench script always carried."""

    def test_default_selection_matches_bench_py(self):
        bd = obs.bench_detail()
        assert set(bd) == {
            "recovery_events",
            "spill_events", "bytes_spilled", "peak_ledger_bytes",
            "donated_bytes_reused",
            # the disk-tier pair (round 13): a bench number always says
            # whether it rode the out-of-core rung
            "disk_events", "bytes_to_disk",
            "checkpoint_events", "bytes_checkpointed",
            "resume_fast_forwarded_pieces", "resume_resharded_pieces",
            "resume_world_mismatch",
            # the compile-lifecycle block (round 19): a bench number
            # always says how many executables were live and how much
            # wall-clock went to XLA
            "compile",
            # the integrity block (round 20): a bench number always says
            # whether the audit was armed and whether it saw violations
            "audit"}
        assert isinstance(bd["recovery_events"], list)
        assert set(bd["compile"]) == {
            "programs_live", "cache_hits", "cache_misses",
            "cache_evictions", "compile_seconds"}
        assert set(bd["audit"]) == {
            "conservation_checks", "fingerprint_checks", "violations"}

    def test_q3q5_selection(self):
        bd = obs.bench_detail(spill_keys=("spill_events", "bytes_spilled",
                                          "peak_ledger_bytes"))
        assert set(bd) == {
            "recovery_events", "spill_events", "bytes_spilled",
            "peak_ledger_bytes",
            "checkpoint_events", "bytes_checkpointed",
            "resume_fast_forwarded_pieces", "resume_resharded_pieces",
            "resume_world_mismatch", "compile", "audit"}

    def test_serving_selection(self):
        bd = obs.bench_detail(
            spill_keys=("spill_events", "bytes_spilled", "readmit_events",
                        "cross_session_evictions", "peak_ledger_bytes"),
            ckpt_keys=())
        assert set(bd) == {
            "recovery_events", "spill_events", "bytes_spilled",
            "readmit_events", "cross_session_evictions",
            "peak_ledger_bytes", "compile", "audit"}

    def test_streaming_selection_no_events(self):
        bd = obs.bench_detail(spill_keys=("window_evictions",
                                          "bytes_spilled"),
                              ckpt_keys=(), events=None)
        assert set(bd) == {"window_evictions", "bytes_spilled", "compile",
                           "audit"}

    def test_plan_section_opt_in(self):
        """The profiler satellite: bench_detail(plan=...) adds a "plan"
        section; the default schema (asserted above) stays plan-free."""
        assert "plan" not in obs.bench_detail()
        bd = obs.bench_detail(plan={"mode": "analyze", "roots": []})
        assert bd["plan"] == {"mode": "analyze", "roots": []}

        class _QP:
            def to_dict(self):
                return {"mode": "explain", "roots": [{"op": "join"}]}
        assert obs.bench_detail(plan=_QP())["plan"]["roots"][0]["op"] \
            == "join"

    def test_drain_vs_keep(self):
        from cylon_tpu.exec import recovery
        recovery.reset_events()
        recovery._record("t.site", "predicted", "retry")
        kept = obs.bench_detail(events="keep")["recovery_events"]
        assert len(kept) == 1
        drained = obs.bench_detail()["recovery_events"]
        assert len(drained) == 1
        assert obs.bench_detail()["recovery_events"] == []


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

class TestTraceRecorder:
    def test_regions_and_bumps_land_without_bench_flag(self, tmp_path):
        """Arming the recorder alone makes regions record — the trace
        tier must not require CYLON_TPU_BENCH."""
        assert not config.BENCH_TIMINGS
        path = str(tmp_path / "tr.json")
        trace.arm(path=path, capacity=64)
        with timing.region("t.span"):
            time.sleep(0.001)
        timing.bump("t.instant")
        timing.add_bytes("t.bytes", 128)
        out = trace.export()
        doc = json.load(open(out, encoding="utf-8"))
        by_name = {}
        for e in doc["traceEvents"]:
            by_name.setdefault(e["name"], []).append(e)
        assert by_name["t.span"][0]["ph"] == "X"
        assert by_name["t.span"][0]["dur"] >= 1
        assert by_name["t.instant"][0]["ph"] == "i"
        assert by_name["t.bytes"][0]["args"]["bytes"] == 128
        # ...and the global phase table stayed EMPTY (timings off)
        assert "t.span" not in timing.snapshot()

    def test_ring_wrap_keeps_newest(self):
        rec = trace.arm(capacity=8)
        for i in range(20):
            rec.instant(f"ev{i}")
        evs = rec.events()
        assert len(evs) == 8
        assert [e[3] for e in evs] == [f"ev{i}" for i in range(12, 20)]
        assert rec.dropped == 12

    def test_ts_monotone_and_ids_present(self, tmp_path):
        path = str(tmp_path / "tr.json")
        trace.arm(path=path, capacity=32)
        for i in range(5):
            trace.instant(f"t.mono{i}")
        doc = json.load(open(trace.export(), encoding="utf-8"))
        tss = [e["ts"] for e in doc["traceEvents"] if "ts" in e]
        assert tss == sorted(tss)
        for e in doc["traceEvents"]:
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)

    def test_session_tagged_spans(self, tmp_path):
        path = str(tmp_path / "tr.json")
        trace.arm(path=path, capacity=32)
        with timing.attribution_scope("tenant_x"):
            with timing.region("t.sess"):
                pass
        doc = json.load(open(trace.export(), encoding="utf-8"))
        ev = next(e for e in doc["traceEvents"] if e["name"] == "t.sess")
        assert ev["args"]["session"] == "tenant_x"

    def test_async_pairs(self, tmp_path):
        path = str(tmp_path / "tr.json")
        trace.arm(path=path, capacity=32)
        trace.async_begin("t.piece", 3, piece=3)
        trace.async_end("t.piece", 3)
        doc = json.load(open(trace.export(), encoding="utf-8"))
        pair = [e for e in doc["traceEvents"] if e["name"] == "t.piece"]
        assert [e["ph"] for e in pair] == ["b", "e"]
        assert all(e["id"] == 3 and e["cat"] == "piece" for e in pair)

    def test_postmortem_dump_content(self, tmp_path):
        trace.arm(capacity=16)
        for i in range(20):
            timing.bump(f"t.pm{i}")
        with timing.region("t.last"):
            pass
        out = trace.postmortem("unit test", dir_path=str(tmp_path), n=8)
        doc = json.load(open(out, encoding="utf-8"))
        assert doc["reason"] == "unit test"
        assert doc["pid"] == os.getpid()
        assert len(doc["events"]) == 8
        assert doc["events"][-1]["name"] == "t.last"
        assert doc["dropped_events"] > 0

    def test_flush_for_abort_writes_postmortem(self, tmp_path,
                                               monkeypatch):
        """The drain/final-rung flush drops the breadcrumb next to the
        manifests — superseding the single last_region() string."""
        from cylon_tpu.exec import checkpoint
        ckdir = str(tmp_path / "ckpt")
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", ckdir)
        trace.arm(capacity=16)
        timing.bump("t.pre_abort")
        checkpoint.flush_for_abort("unit")
        doc = json.load(open(os.path.join(ckdir, "TRACE_POSTMORTEM.json"),
                             encoding="utf-8"))
        assert any(e["name"] == "t.pre_abort" for e in doc["events"])
        assert doc["reason"] == "abort flush: unit"

    def test_export_injection_surfaces_typed(self, tmp_path):
        from cylon_tpu.exec import recovery
        trace.arm(path=str(tmp_path / "tr.json"), capacity=16)
        recovery.install_faults("obs.export::1=predicted")
        with pytest.raises(PredictedResourceExhausted):
            trace.export()
        recovery.install_faults("")
        assert trace.export() is not None   # recovers once disarmed

    def test_export_oserror_surfaces_typed(self, tmp_path):
        trace.arm(capacity=16)
        missing = str(tmp_path / "no" / "such" / "dir" / "tr.json")
        with pytest.raises(ExecutionError):
            trace.export(missing)


class TestUnarmedContract:
    """The happy-path acceptance: with no profiler running and nothing
    armed, a region is one ``TraceMe`` that checks the profiler's flag
    and nothing else — zero filesystem writes, no ring, no phase table,
    no scope table.  (The cost is stated in docs/observability.md from a
    measurement; no wall-clock assertion here.)"""

    def test_unarmed_records_and_writes_nothing(self, tmp_path,
                                                monkeypatch):
        monkeypatch.chdir(tmp_path)
        assert not trace.armed() and timing._TRACE[0] is None
        assert not timing.TraceAnnotation.is_enabled()   # no profiler
        with timing.region("t.off"):
            pass
        with timing.region("t.off_args", bytes=7, rows=3):
            pass
        with timing.span("t.off_span_only", bytes=1):   # launch./pull.
            pass
        timing.bump("t.off_bump")
        trace.instant("t.off_instant")
        trace.complete("t.off_span", time.perf_counter())
        assert trace.export() is None
        assert trace.postmortem("nothing armed") is None
        assert not rank_report.armed()
        assert metrics.maybe_write_snapshot() is False
        assert os.listdir(tmp_path) == []
        # nothing was pushed anywhere: no recorder came to be, and the
        # phase table holds the bump alone (regions time only when asked)
        assert trace.recorder() is None
        assert set(timing.snapshot()) == {"t.off_bump"}
        assert timing.last_region() == "t.off_args"      # the breadcrumb

    def test_region_is_a_profiler_annotation_and_nothing_more(self):
        """What an unarmed region costs is what it constructs: one
        ``TraceAnnotation`` (jaxlib's TraceMe, which checks the
        profiler's flag on enter) named ``cylon.<region>``; the timed
        sinks are not entered."""
        made = []

        class Spy(timing.TraceAnnotation):
            def __init__(self, name, **kw):
                made.append((name, kw))
                super().__init__(name, **kw)

        real, timing.TraceAnnotation = timing.TraceAnnotation, Spy
        try:
            with timing.region("t.one"):
                pass
            with timing.attribution_scope("tenantA"):
                with timing.region("t.two", bytes=5):
                    pass
        finally:
            timing.TraceAnnotation = real
        assert made == [("cylon.t.one", {}),
                        ("cylon.t.two", {"session": "tenantA", "bytes": 5})]
        assert timing.snapshot() == {}      # BENCH_TIMINGS off: no table

    def test_operator_call_and_host_step_read_no_clock(self, env1,
                                                       tmp_path,
                                                       monkeypatch):
        """The contract restated for ``cylon.op.<op>`` (every
        ``plan.node``) and ``cylon.host.<step>`` (ISSUE 39): with nothing
        armed each constructs ONE ``TraceAnnotation``, reads no clock and
        writes nothing - on the facade alone and over a whole join ->
        groupby.  ``timing.span`` is PR 41's, byte for byte (it yields its
        annotation and arguments, for ``_NodeCtx.span_args``): the digest
        is of its source, to be changed knowingly."""
        import hashlib
        import inspect
        import types
        from cylon_tpu.obs import plan
        from cylon_tpu.relational import groupby_aggregate, join_tables
        assert hashlib.sha256(inspect.getsource(timing.span).encode()) \
            .hexdigest()[:16] == "8e5acb031509c9c7"
        left, right = _toy(env1, n=512)

        def query():
            return groupby_aggregate(join_tables(left, right, "k", "k"),
                                     "k", [("a", "sum")]).row_count

        query()                                   # compiled, caches warm
        monkeypatch.chdir(tmp_path)
        made, reads = [], []

        class Spy(timing.TraceAnnotation):
            def __init__(self, name, **kw):
                made.append(name)
                super().__init__(name, **kw)

        def perf_counter():
            reads.append(1)
            return time.perf_counter()

        monkeypatch.setattr(timing, "TraceAnnotation", Spy)
        monkeypatch.setattr(timing, "time", types.SimpleNamespace(
            perf_counter=perf_counter))
        before = metrics.snapshot()
        with plan.node("join", how="inner") as pn:
            assert not pn                          # no profile: the no-op
            with timing.span("host.join_plan"):
                pass
        assert made == ["cylon.op.join", "cylon.host.join_plan"]
        assert reads == []
        assert query() > 0
        assert reads == []                         # the whole query: none
        ops = [n for n in made if n.startswith("cylon.op.")]
        hosts = [n for n in made if n.startswith("cylon.host.")]
        assert ops[1:] == ["cylon.op.join", "cylon.op.groupby"]
        assert hosts[1:] == ["cylon.host.join_plan"]
        # nothing armed, nothing written: no ring, no table, no file, and
        # the registry gained no name
        assert trace.recorder() is None and timing.snapshot() == {}
        assert os.listdir(tmp_path) == []
        assert set(metrics.snapshot()) == set(before)

    def test_autoarm_needs_env(self, monkeypatch):
        monkeypatch.delenv("CYLON_TPU_TRACE", raising=False)
        trace.autoarm()
        assert not trace.armed()
        monkeypatch.setenv("CYLON_TPU_TRACE", "/tmp/t.json")
        trace.autoarm()
        assert trace.armed()
        assert trace.recorder().path == "/tmp/t.json"


# ---------------------------------------------------------------------------
# the program's spans on the profiler's clock (ISSUE 26, part B)
# ---------------------------------------------------------------------------

def _host_spans(trace_dir):
    """``(name, start_ns, end_ns, args)`` of every ``cylon.*`` event on
    the host planes of the newest xplane under ``trace_dir``."""
    import glob
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("cylon."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda x: x[1])


def _inside(spans, inner, outer):
    """Some ``inner`` span lies within some ``outer`` span."""
    return any(o[1] <= i[1] and i[2] <= o[2]
               for i in spans if i[0] == inner
               for o in spans if o[0] == outer)


@pytest.fixture()
def profiled(env1, tmp_path):
    """Run a thunk once to warm, then under ``jax.profiler`` with the
    flight recorder armed: ``(host spans, ring names)``."""
    import jax

    def run(thunk):
        thunk()
        rec = trace.arm(capacity=4096)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with timing.attribution_scope("tenantA"):
                thunk()
        finally:
            jax.profiler.stop_trace()
        return _host_spans(str(tmp_path)), [e[3] for e in rec.events()]

    return run


def _toy(env, n=4096):
    import cylon_tpu as ct
    rng = np.random.default_rng(3)
    left = ct.Table.from_pydict({"k": rng.integers(0, 3000, n),
                                 "a": rng.integers(0, 100, n)}, env)
    right = ct.Table.from_pydict({"k": rng.integers(0, 3000, n),
                                  "b": rng.integers(0, 100, n)}, env)
    return left, right


def test_join_groupby_spans_on_the_profilers_clock(env1, profiled):
    from cylon_tpu.relational import groupby_aggregate, join_tables
    left, right = _toy(env1)

    def query():
        g = groupby_aggregate(join_tables(left, right, "k", "k"), "k",
                              [("a", "sum"), ("b", "sum")])
        assert g.row_count > 0

    spans, ring = profiled(query)
    names = [s[0] for s in spans]
    for want in ("cylon.join.sort_count", "cylon.launch.join__count_fn",
                 "cylon.groupby.fused", "cylon.launch.fused__fused_fn",
                 "cylon.pull.host_array"):
        assert want in names, names
    assert _inside(spans, "cylon.launch.join__count_fn",
                   "cylon.join.sort_count")
    assert _inside(spans, "cylon.launch.fused__fused_fn",
                   "cylon.groupby.fused")
    assert _inside(spans, "cylon.pull.host_array", "cylon.groupby.fused")
    # the scope's tag is every span's `session`; a pull says its bytes
    assert all(s[3].get("session") == "tenantA" for s in spans)
    pulls = [s for s in spans if s[0] == "cylon.pull.host_array"]
    assert all(int(p[3]["bytes"]) > 0 for p in pulls)
    # one source, two sinks: the ring got the same spans from the same run
    assert sorted(n[len("cylon."):] for n in names) == \
        sorted(n for n in ring if not n.startswith("compile."))


def test_groupby_sort_spans_on_the_profilers_clock(env1, profiled):
    from cylon_tpu.relational import groupby_aggregate, sort_table
    left, _ = _toy(env1)

    def query():
        s = sort_table(groupby_aggregate(left, "k", [("a", "sum")]),
                       "a_sum")
        assert s.row_count > 0

    spans, ring = profiled(query)
    names = [s[0] for s in spans]
    for want in ("cylon.groupby.raw", "cylon.launch.groupby__raw_fn",
                 "cylon.sort.local", "cylon.launch.sort__local_sort_fn",
                 "cylon.pull.host_array"):
        assert want in names, names
    assert _inside(spans, "cylon.launch.groupby__raw_fn",
                   "cylon.groupby.raw")
    assert _inside(spans, "cylon.launch.sort__local_sort_fn",
                   "cylon.sort.local")
    assert {"groupby.raw", "sort.local", "launch.groupby__raw_fn",
            "launch.sort__local_sort_fn", "pull.host_array"} <= set(ring)


def test_ingest_regions_carry_rows_and_bytes(env1):
    rec = trace.arm(capacity=64)
    _toy(env1, n=512)
    spans = {e[3]: e[6] for e in rec.events() if e[2] == "X"}
    assert spans["table.from_pydict"] == {"rows": 512, "bytes": 2 * 512 * 8}
    assert spans["table.upload"] == {"rows": 512}


def test_exchange_spans_carry_rows_and_bytes(env4):
    """One ``exchange.<route>`` span per exchange, with the logical rows
    and bytes the always-on counters take, around the launches of the
    exchange's programs (ISSUE 28)."""
    from cylon_tpu import obs
    from cylon_tpu.relational import join_tables
    left, right = _toy(env4)
    join_tables(left, right, "k", "k").to_pandas()          # warm
    rows, nbytes, count = (obs.counter(c) for c in (
        "exchange_rows_total", "exchange_bytes_total", "exchange_count"))
    before = rows.value, nbytes.value, count.value
    rec = trace.arm(capacity=512)
    join_tables(left, right, "k", "k").to_pandas()
    events = [e for e in rec.events() if e[2] == "X"]
    exch = [e for e in events if e[3] == "exchange.flat"]
    assert len(exch) == count.value - before[2] == 2    # left, right
    assert sum(e[6]["rows"] for e in exch) == rows.value - before[0] \
        == left.row_count + right.row_count
    assert sum(e[6]["bytes"] for e in exch) == nbytes.value - before[1]
    # the round program is enqueued inside the span
    rounds = [e for e in events if e[3] == "launch.shuffle__round_fn"]
    assert len(rounds) == 2 and all(
        any(x[0] <= r[0] and r[0] + r[1] <= x[0] + x[1] for x in exch)
        for r in rounds)


# ---------------------------------------------------------------------------
# a round trip, both ends: the operator call and the named turns (ISSUE 39)
# ---------------------------------------------------------------------------

def _host_step_literals():
    """Every ``span("host.<step>")`` literal under ``cylon_tpu/``, and any
    ``host.`` span name built at run time (there must be none)."""
    import re
    literal = re.compile(r"""\bspan\(\s*["']host\.([A-Za-z0-9_.]*)["']""")
    built = re.compile(r"""\bspan\(\s*(?:f["']host\.|["']host\.["']\s*\+)""")
    found, dynamic = set(), []
    for root, _dirs, names in os.walk(os.path.join(REPO, "cylon_tpu")):
        for name in names:
            if name.endswith(".py"):
                with open(os.path.join(root, name), encoding="utf-8") as f:
                    text = f.read()
                found |= set(literal.findall(text))
                dynamic += built.findall(text)
    return found, dynamic


def test_op_and_host_spans_on_the_profilers_clock(env4, profiled):
    """A distributed ``join_tables`` + ``groupby_aggregate`` under
    ``jax.profiler``: the operator calls are ``cylon.op.<op>`` spans (the
    exchange's ``op.shuffle`` inside ``op.join``), the named turns
    ``cylon.host.<step>``; every name written, and every literal in the
    source, is of ``timing.HOST_STEPS`` - and the distributed join writes
    them all but the sort's own (``sort_splitters``: the test below), so
    the vocabulary holds no dead name.  (The rig's four devices stand for
    the issue's two: the module's programs are compiled for them
    already.)"""
    from cylon_tpu.relational import groupby_aggregate, join_tables
    left, right = _toy(env4)

    def query():
        g = groupby_aggregate(join_tables(left, right, "k", "k"), "k",
                              [("a", "sum"), ("b", "sum")])
        assert g.row_count > 0

    spans, ring = profiled(query)
    names = [s[0] for s in spans]
    vocabulary = {"cylon.host." + s for s in timing.HOST_STEPS
                  if s != "sort_splitters"}
    assert {n for n in names if n.startswith("cylon.host.")} == vocabulary
    assert _host_step_literals() == (set(timing.HOST_STEPS), [])
    assert [n for n in names if n.startswith("cylon.op.")] == [
        "cylon.op.join", "cylon.op.shuffle", "cylon.op.shuffle",
        "cylon.op.groupby"]
    for inner, outer in (
            ("cylon.op.shuffle", "cylon.op.join"),
            ("cylon.host.skew_detect", "cylon.join.shuffle"),
            ("cylon.host.exchange_plan", "cylon.op.shuffle"),
            ("cylon.launch.shuffle__round_fn", "cylon.op.shuffle"),
            ("cylon.host.skew_operands", "cylon.join.shuffle"),
            ("cylon.host.join_plan", "cylon.op.join"),
            ("cylon.launch.fused__fused_fn", "cylon.op.groupby")):
        assert _inside(spans, inner, outer), (inner, outer)
    # a host step is a stretch of a turn: no launch and no pull inside it
    bounds = [s for s in spans
              if s[0].startswith(("cylon.launch.", "cylon.pull."))]
    for h in (s for s in spans if s[0] in vocabulary - {
            # the lane pack / unpack ARE enqueues (plain jit, untagged)
            "cylon.host.exchange_pack", "cylon.host.exchange_unpack"}):
        assert not any(h[1] <= b[1] and b[2] <= h[2] for b in bounds), h
    # at most 12 named turns a four-chip query (ISSUE 39's budget)
    assert sum(n.startswith("cylon.host.") for n in names) == 12
    # one source, two sinks: the ring holds them beside launch.* / pull.*
    assert {"op.join", "op.shuffle", "op.groupby", "launch.join__count_fn",
            "pull.host_array"} | {n[len("cylon."):] for n in vocabulary} \
        <= set(ring)


def test_distributed_groupby_sort_spans_on_the_profilers_clock(env4,
                                                               profiled):
    """``groupby_aggregate`` -> ``sort_table`` on a partitioned table under
    ``jax.profiler`` (ISSUE 44): the two-phase groupby's three phases are
    regions, the sort's one host decision between two device programs is
    the named turn ``cylon.host.sort_splitters`` - no launch and no pull
    inside it -, the two exchanges say whose they are (``site``), and the
    sort's exchange region says how even its samples made the partition
    (``recv_max`` <= ``recv_cap``, ``samples`` a shard)."""
    from cylon_tpu import config, obs
    from cylon_tpu.relational import groupby_aggregate, sort_table
    left, _ = _toy(env4)
    out = []

    def query():
        g = groupby_aggregate(left, "k", [("a", "sum")])
        out[:] = [sort_table(g, "a_sum"), g.capacity]

    taken = obs.counter("sort_sample_sorts")
    before = taken.value
    spans, ring = profiled(query)
    assert taken.value - before == 2             # the warm-up and the run
    names = [s[0] for s in spans]
    assert [n for n in names if n.startswith("cylon.op.")] == [
        "cylon.op.groupby", "cylon.op.shuffle", "cylon.op.sort"]
    for region in ("groupby.combine", "groupby.shuffle", "groupby.final",
                   "sort.sample", "sort.exchange", "sort.local",
                   "host.sort_splitters"):
        assert names.count("cylon." + region) == 1, region
    for inner, outer in (
            ("cylon.launch.groupby__combine_fn", "cylon.groupby.combine"),
            ("cylon.op.shuffle", "cylon.groupby.shuffle"),
            ("cylon.launch.groupby__final_fn", "cylon.groupby.final"),
            ("cylon.groupby.final", "cylon.op.groupby"),
            ("cylon.launch.sort__sample_fn", "cylon.sort.sample"),
            ("cylon.host.sort_splitters", "cylon.sort.sample"),
            ("cylon.launch.sort__target_fn", "cylon.sort.exchange"),
            ("cylon.exchange.flat", "cylon.sort.exchange"),
            ("cylon.sort.exchange", "cylon.op.sort")):
        assert _inside(spans, inner, outer), (inner, outer)
    step, = (s for s in spans if s[0] == "cylon.host.sort_splitters")
    assert not any(step[1] <= b[1] and b[2] <= step[2] for b in spans
                   if b[0].startswith(("cylon.launch.", "cylon.pull.")))
    # the step lies between the sample's pull and the target program
    assert max(s[2] for s in spans if s[0] == "cylon.pull.host_array"
               and s[2] <= step[1]) <= step[1] <= min(
        s[1] for s in spans if s[0] == "cylon.launch.sort__target_fn")
    exch = [s[3] for s in spans if s[0] == "cylon.exchange.flat"]
    assert [a["site"] for a in exch] == ["groupby.recv", "sort.recv"]
    region, = (s[3] for s in spans if s[0] == "cylon.sort.exchange")
    got = np.asarray(out[0].valid_counts)
    assert int(region["samples"]) == min(out[1], config.sort_samples(4))
    assert int(region["recv_max"]) == got.max() == int(exch[1]["recv_max"])
    assert int(region["recv_cap"]) == out[0].capacity \
        == int(exch[1]["recv_cap"]) >= got.max()
    assert {"groupby.combine", "groupby.shuffle", "groupby.final",
            "host.sort_splitters", "sort.exchange"} <= set(ring)


@pytest.mark.parametrize("nested", [False, True])
def test_operator_that_raises_leaves_no_span_open(env4, monkeypatch, nested):
    """``cylon.op.<op>`` closes whatever the operator raised: every
    annotation entered is exited, innermost first, and the ring holds the
    closed spans - nested, the exchange's ``op.shuffle`` (where the error
    was raised) inside ``op.join``."""
    from cylon_tpu.parallel import shuffle
    from cylon_tpu.relational import join_tables
    left, right = _toy(env4)
    log = []

    class Spy(timing.TraceAnnotation):
        def __init__(self, name, **kw):
            self._name = name
            super().__init__(name, **kw)

        def __enter__(self):
            log.append(("in", self._name))
            return super().__enter__()

        def __exit__(self, *exc):
            log.append(("out", self._name))
            return super().__exit__(*exc)

    def boom(*a, **kw):
        raise InvalidError("boom")

    monkeypatch.setattr(timing, "TraceAnnotation", Spy)
    if nested:
        monkeypatch.setattr(shuffle, "exchange", boom)
    rec = trace.arm(capacity=256)
    with pytest.raises((InvalidError, CylonKeyError)):
        join_tables(left, right, "k" if nested else "no_such_column", "k")
    stack = []
    for what, name in log:
        if what == "in":
            stack.append(name)
        else:
            assert stack.pop() == name             # innermost first
    assert stack == []                             # none left open
    entered = [n for w, n in log if w == "in"]
    assert entered[0] == "cylon.op.join"
    ring = {e[3]: (e[0], e[0] + e[1]) for e in rec.events() if e[2] == "X"}
    assert "op.join" in ring
    if nested:
        assert "cylon.op.shuffle" in entered
        (a, b), (c, d) = ring["op.shuffle"], ring["op.join"]
        assert c <= a and b <= d


def test_validity_stand_in_is_built_once(env4):
    """What the matched round trips found (PERF.md §6, PR 39): the skew
    sampler's all-true validity stand-in was a fresh ``np.ones(cap, bool)``
    on every distributed join.  ``common.all_valid`` builds it once per
    capacity: a second join asks for the same read-only block."""
    from cylon_tpu.relational import common, join_tables
    left, right = _toy(env4)
    assert join_tables(left, right, "k", "k").row_count > 0
    before = common.all_valid.cache_info()
    assert join_tables(left, right, "k", "k").row_count > 0
    after = common.all_valid.cache_info()
    assert after.misses == before.misses and after.hits > before.hits
    ones = common.all_valid(left.column("k").data.shape[0])
    assert ones.dtype == bool and ones.all() and not ones.flags.writeable


def test_ring_tiles_every_operator_call(env4, tmp_path, monkeypatch):
    """Armed, the ring holds ``op.*`` and ``host.*`` beside ``launch.*`` /
    ``pull.*``, and ``scripts/round_trips_report.py`` reads the export:
    per outermost operator call launch + pull + turn IS the call's span
    (turn is what is in neither), the named steps inside it."""
    import importlib.util
    from cylon_tpu.relational import groupby_aggregate, join_tables
    left, right = _toy(env4)

    def query():
        return groupby_aggregate(join_tables(left, right, "k", "k"), "k",
                                 [("a", "sum"), ("b", "sum")]).row_count

    query()
    path = str(tmp_path / "p1.ring.json")
    rec = trace.arm(path=path, capacity=4096)
    for _ in range(3):
        query()
    names = {e[3] for e in rec.events()}
    assert {"op.join", "op.shuffle", "op.groupby", "host.skew_operands",
            "host.skew_detect", "host.exchange_plan",
            "launch.shuffle__round_fn",
            "pull.host_array"} <= names
    assert trace.export() == path
    monkeypatch.setattr(sys, "path", list(sys.path))
    spec = importlib.util.spec_from_file_location(
        "_round_trips_report", os.path.join(REPO, "scripts",
                                            "round_trips_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    calls = report.ring_calls(path)
    assert [c["op"] for c in calls] == ["op.join", "op.groupby"] * 3
    for c in calls:
        assert c["launch_s"] + c["pull_s"] + c["turn_s"] == pytest.approx(
            c["op_s"], rel=1e-9)
        assert c["turn_s"] > 0 and c["pull_s"] > 0
    join = calls[0]["by_name"]
    assert join["launch.shuffle__round_fn"] > 0
    assert 0 < join["host.skew_detect"] < calls[0]["turn_s"]
    assert report.tag_of(path) == "p1"
    assert any("op.join x3" in ln for ln in report.ring_lines("p1", calls))


@pytest.mark.parametrize("how,fused", [("inner", True), ("left", False)])
def test_join_sort_counters_and_plan_fields(env1, how, fused):
    """The join's one sort, as the registry and the plan node say it
    (ISSUE 35): ``join_sort_operands`` / ``join_sort_dispatches`` take one
    count program's static numbers a join, on the host; the ``join`` node
    shows ``sort_operands``, ``payload_operands``, ``aliased_key_lanes``.
    ``_toy``'s schema is the benchmark cells': narrow key (padding's
    sentinel inside it since ISSUE 50: no liveness operand) + idx + one
    operand shared by ``a`` and ``b``, the key's lane aliased; the registry
    counts the sort's liveness form (``key_sort_liveness``)."""
    from cylon_tpu.relational import groupby_aggregate, join_tables
    assert {"join_sort_operands", "join_sort_dispatches"} \
        <= set(metrics.snapshot())           # registered at import
    left, right = _toy(env1, n=4000)         # under capacity, as the cells
    ops, joins = (obs.counter(c) for c in (
        "join_sort_operands", "join_sort_dispatches"))
    folded = obs.counter("key_sort_liveness", form="folded", site="join")
    before = ops.value, joins.value, folded.value

    def query():
        j = join_tables(left, right, "k", "k", how=how)
        if fused:
            return groupby_aggregate(j, "k", [("a", "sum"), ("b", "sum")])
        return j.to_pandas()

    plan = obs.explain_analyze(query)
    assert (ops.value - before[0], joins.value - before[1],
            folded.value - before[2]) == (3, 1, 1)
    node, = [n for n in plan.to_dict()["roots"] if n["op"] == "join"]
    assert {k: node["attrs"][k] for k in (
        "sort_operands", "payload_operands", "aliased_key_lanes")} \
        == {"sort_operands": 3, "payload_operands": 1,
            "aliased_key_lanes": 1}


def test_benchmark_reads_join_sort_operands_per_join(tmp_path, monkeypatch,
                                                     capfd):
    """The yardstick's side of the two counters: ``benchmark/metrics/
    join_sort_operands_per_join.json`` through ``run.py``'s own ``main`` on
    the join cell's 65,536-row twin (the benchmark's test helpers; the CPU
    has no device plane, so the trace reduction is stood in for as in
    ``benchmark/tests/test_zipf.py``).  The twin's tables are AT capacity
    (65,536 rows), so its sort has no padding to place: 3.0, as the
    cells' 32,000,000 rows padded to 32,505,856 read since ISSUE 50 (the
    sentinel rides in the key; 4.0 until then)."""
    import importlib.util
    bench_tests = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "tests")
    spec = importlib.util.spec_from_file_location(
        "_bench_test_helpers", os.path.join(bench_tests, "helpers.py"))
    helpers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helpers)
    monkeypatch.setattr(sys, "path", list(sys.path))
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    name = "join_sort_operands_per_join"
    with open(os.path.join(bench_dir, "metrics", name + ".json")) as f:
        m = json.load(f)
    assert m["args"] == {"counter": "^join_sort_operands$",
                         "per": "^join_sort_dispatches$"}
    with open(os.path.join(bench_dir, "metrics", "tiny_" + name + ".json"),
              "w") as f:
        json.dump(dict(m, name="tiny_" + name,
                       workloads=["tiny_join_groupby_32m"]), f)
    run = helpers.load_run(bench_dir)
    helpers.steer_to_cpu(run, monkeypatch)
    monkeypatch.setattr(run, "_traced_queries", lambda one, n, spans, d: (
        [one() for _ in range(n)],
        {"n_queries": n, "n_chips": 1, "busy_s": 0.9, "window_s": 1.0,
         "idle_share": 0.1, "op_seconds": [], "gap_seconds": []})[1])
    before = tuple(obs.counter(c).value for c in (
        "join_sort_operands", "join_sort_dispatches"))
    capfd.readouterr()
    rc = run.main(["--workload", "tiny_join_groupby_32m", "--seed",
                   str(2**31 + 35), "--seconds", "0.5", "--trace", "1"])
    out = capfd.readouterr()
    assert rc == 0, out.err[-3000:]
    line = helpers.last_json_line(out.out)
    assert line["correct"] is True, line["compared"]
    ops, joins = (obs.counter(c).value - b for c, b in zip(
        ("join_sort_operands", "join_sort_dispatches"), before))
    assert joins > 0 and ops == 3 * joins
    # the reader sums the whole process's registry: other tests' joins too
    assert line["metrics"]["tiny_" + name]["unit"] == "count"
    if before == (0, 0):
        assert line["metrics"]["tiny_" + name]["value"] == 3.0


def test_compile_seconds_by_builder(env1):
    """A forced compile (a row count no other test uses) is attributed to
    the builder whose program was being launched, in
    ``compiler.stats()["by_builder"]`` and as a ``compile.<builder>`` span
    of the ring."""
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import groupby_aggregate
    compiler.install_listener()
    left, _ = _toy(env1, n=1536 + 8)
    before = compiler.stats()["by_builder"].get(
        "groupby__raw_fn", {"seconds": 0.0, "events": 0})
    total_before = compiler.stats()["compile_events"]
    rec = trace.arm(capacity=256)
    assert groupby_aggregate(left, "k", [("a", "max")]).row_count > 0
    st = compiler.stats()
    after = st["by_builder"]["groupby__raw_fn"]
    assert after["events"] >= before["events"] + 1
    assert after["seconds"] > before["seconds"]
    assert sum(b["events"] for b in st["by_builder"].values()) \
        <= st["compile_events"]
    assert st["compile_events"] > total_before
    assert "compile.groupby__raw_fn" in [e[3] for e in rec.events()]


# ---------------------------------------------------------------------------
# scheduler integration: baton handoffs on the timeline
# ---------------------------------------------------------------------------

def test_scheduler_baton_events_session_tagged(env4, tmp_path):
    from cylon_tpu.exec.scheduler import QueryScheduler
    trace.arm(path=str(tmp_path / "tr.json"), capacity=256)
    sched = QueryScheduler(env4, policy="fifo")
    sched.submit("tA", lambda: 1)
    sched.submit("tB", lambda: 2)
    sessions = sched.run(raise_errors=True)
    assert [s.result for s in sessions] == [1, 2]
    doc = json.load(open(trace.export(), encoding="utf-8"))
    grants = [e for e in doc["traceEvents"] if e["name"] == "sched.grant"]
    assert {g["args"]["session"] for g in grants} >= {"tA", "tB"}


# ---------------------------------------------------------------------------
# utils/timing edge cases (the satellite fixes)
# ---------------------------------------------------------------------------

class TestTimingEdgeCases:
    def test_reset_clears_last_region(self):
        with timing.region("t.lastreg"):
            pass
        assert timing.last_region() == "t.lastreg"
        timing.reset()
        assert timing.last_region() == ""

    def test_park_time_netted_from_global_table(self):
        """The satellite fix: global phase seconds must not include
        baton-park time inside spanning regions (the scope table
        already netted it)."""
        config.BENCH_TIMINGS = True
        timing.reset()
        with timing.region("t.gpark"):
            time.sleep(0.05)
            timing.exclude_from_scope(0.05)   # the scheduler's call
        s = timing.snapshot()["t.gpark"]["s"]
        assert s < 0.02, s
        timing.reset()
        with timing.region("t.gnopark"):
            time.sleep(0.05)
        assert timing.snapshot()["t.gnopark"]["s"] >= 0.04

    def test_exclusion_nets_across_nesting_in_both_tables(self):
        """A park inside the INNER region must net out of inner AND
        outer, in the scope table and the global table alike."""
        config.BENCH_TIMINGS = True
        timing.reset()
        with timing.attribution_scope("t_nest") as sc:
            with timing.region("t.outer"):
                with timing.region("t.inner"):
                    time.sleep(0.05)
                    timing.exclude_from_scope(0.05)
        snap = sc.snapshot()
        assert snap["t.inner"]["s"] < 0.02, snap
        assert snap["t.outer"]["s"] < 0.02, snap
        gsnap = timing.snapshot()
        assert gsnap["t.inner"]["s"] < 0.02, gsnap
        assert gsnap["t.outer"]["s"] < 0.02, gsnap

    def test_nested_scopes_are_disjoint(self):
        """Inner scope shadows: its regions land in the inner table
        only, and exclusion inside the inner scope does not drain the
        outer scope's unrelated regions."""
        timing.reset()
        with timing.attribution_scope("t_out") as so:
            with timing.region("t.only_outer"):
                time.sleep(0.02)
            with timing.attribution_scope("t_in") as si:
                with timing.region("t.only_inner"):
                    time.sleep(0.02)
                    timing.exclude_from_scope(0.02)
        assert "t.only_inner" not in so.snapshot()
        assert "t.only_outer" not in si.snapshot()
        assert si.snapshot()["t.only_inner"]["s"] < 0.01
        assert so.snapshot()["t.only_outer"]["s"] >= 0.015

    def test_sync_region_split_snapshot_roundtrip(self):
        config.BENCH_TIMINGS = True
        timing.reset()
        with timing.region("t.phase"):
            time.sleep(0.002)
        with timing.sync_region("t.phase"):
            time.sleep(0.002)
        # idempotent suffixing: an already-suffixed name stays single
        with timing.sync_region("t.phase" + timing.BLOCK_SUFFIX):
            pass
        snap = timing.snapshot()
        assert "t.phase" in snap
        assert "t.phase" + timing.BLOCK_SUFFIX in snap
        assert "t.phase" + timing.BLOCK_SUFFIX * 2 not in snap
        dispatch, block = timing.split_snapshot(snap)
        assert "t.phase" in dispatch and "t.phase" in block
        assert block["t.phase"] == snap["t.phase.block"]["s"]
        assert dispatch["t.phase"] == snap["t.phase"]["s"]


# ---------------------------------------------------------------------------
# per-rank report
# ---------------------------------------------------------------------------

class TestRankReport:
    def test_unarmed_by_default_armed_by_env(self, monkeypatch):
        assert not rank_report.armed()
        monkeypatch.setenv("CYLON_TPU_RANK_REPORT", "1")
        assert rank_report.armed()
        monkeypatch.delenv("CYLON_TPU_RANK_REPORT")
        rank_report.arm()
        assert rank_report.armed()
        rank_report.arm(False)
        assert not rank_report.armed()

    def test_single_process_report_shape(self):
        config.BENCH_TIMINGS = True
        timing.reset()
        with timing.region("t.rank_phase"):
            time.sleep(0.01)
        timing.bump("t.rank_bump")     # zero-second phase: skew None
        rep = rank_report.report()
        assert rep["ranks"] == 1
        ent = rep["phases"]["t.rank_phase"]
        assert ent["min_s"] == ent["median_s"] == ent["max_s"]
        assert ent["skew"] == 1.0
        assert rep["phases"]["t.rank_bump"]["skew"] is None


# ---------------------------------------------------------------------------
# slow: the CI schema validation drive (satellite 6)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_smoke_emits_valid_chrome_trace(tmp_path):
    """Drives scripts/bench_smoke.py with CYLON_TPU_TRACE armed and
    validates the emitted Chrome-trace JSON: schema fields, ts
    monotonicity, per-piece dispatch spans, balanced async in-flight
    pairs — the pipelined-join timeline the overlap scheduler's
    acceptance reads in Perfetto."""
    out = str(tmp_path / "smoke_trace.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", CYLON_TPU_TRACE=out)
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "bench_smoke.py"),
         "--rows=16384"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.load(open(out, encoding="utf-8"))
    events = doc["traceEvents"]
    assert events, "empty trace"
    tss = []
    for e in events:
        assert e["ph"] in ("X", "i", "b", "e", "M"), e
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        if "ts" in e:
            tss.append(e["ts"])
        if e["ph"] == "X":
            assert e["dur"] >= 1
    assert tss == sorted(tss), "ts not monotone"
    names = [e["name"] for e in events]
    # the pipelined phase spans are on the timeline...
    for phase in ("pipe.build_sort", "pipe.piece_join", "pipe.consume"):
        assert phase in names, phase
    # ...with one dispatch span per piece, piece-indexed
    disp = [e for e in events if e["name"] == "pipe.piece_dispatch"]
    assert len(disp) >= 2
    pieces = [e["args"]["piece"] for e in disp]
    assert len(set(pieces)) == len(pieces)
    assert all(isinstance(x, int) for x in pieces)
    # the sink's async in-flight spans pair up per chunk id
    begins = [e["id"] for e in events
              if e["name"] == "sink.chunk_inflight" and e["ph"] == "b"]
    ends = [e["id"] for e in events
            if e["name"] == "sink.chunk_inflight" and e["ph"] == "e"]
    assert begins and sorted(begins) == sorted(ends)
