"""Fleet survival under live traffic (ISSUE 18): preemptive
drain/requeue under ``priority``/``fair``, typed admission deadlines,
requeue-capacity overflow, per-tenant outcome accounting, and the
elastic mesh resize controller (exec/fleet) — acceptance: a preempted
tenant's answer stays BIT-EQUAL to its solo run, co-tenants' recovery
logs stay clean, and the unarmed happy path adds zero checkpoint
machinery."""

import os
import subprocess
import sys
import time

import pytest

from cylon_tpu.exec import checkpoint, fleet, memory, recovery, scheduler
from cylon_tpu.exec.scheduler import QueryScheduler
from cylon_tpu.exec.session import QuerySession
from cylon_tpu.status import (AdmissionTimeoutError, InvalidError,
                              RequeueOverflowError, ResumableAbort)
from test_scheduler import _pipe_fn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_state():
    recovery.install_faults("")
    recovery.reset_events()
    recovery.set_session(None, None)
    memory.reset_stats()
    checkpoint.reset_stats()
    checkpoint.reset_stages()
    scheduler.reset_family_history()
    yield
    recovery.install_faults("")
    recovery.reset_events()
    recovery.set_session(None, None)
    checkpoint.reset_stats()
    checkpoint.reset_stages()
    scheduler.reset_family_history()


class TestPreemption:
    def test_preempt_requeue_resume_bit_equal(self, env4, monkeypatch,
                                              tmp_path):
        """The tentpole's acceptance schedule: tB (priority 5) arrives
        while tA runs and preempts it at its next checkpoint boundary;
        tA requeues, fast-forwards its committed pieces on re-grant,
        gets preempted AGAIN by tB2 (after committing new pieces — the
        no-progress guard demands that), and still finishes bit-equal
        to its solo run.  tC shares the box untouched: its recovery
        event log stays empty (no cross-session contamination)."""
        solo_a = _pipe_fn(env4, 11, n=1800, chunks=6)()
        solo_b = _pipe_fn(env4, 22, n=900, chunks=2)()
        solo_c = _pipe_fn(env4, 33, n=1800, chunks=6)()
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", str(tmp_path))
        checkpoint.reset_stages()
        checkpoint.reset_stats()

        sched = QueryScheduler(env4, policy="priority",
                               max_concurrency=1)
        runs = {"n": 0}
        fn_a = _pipe_fn(env4, 11, n=1800, chunks=6)

        def tenant_a():
            # each replay submits the NEXT high-priority arrival — two
            # preemptions of tA, deterministically placed at its first
            # boundary after each (re)grant
            runs["n"] += 1
            if runs["n"] == 1:
                sched.submit("tB", _pipe_fn(env4, 22, n=900, chunks=2),
                             priority=5)
            elif runs["n"] == 2:
                sched.submit("tB2", _pipe_fn(env4, 22, n=900, chunks=2),
                             priority=5)
            return fn_a()

        a = sched.submit("tA", tenant_a)
        c = sched.submit("tC", _pipe_fn(env4, 33, n=1800, chunks=6))
        sched.run()

        b = next(s for s in sched.sessions if s.name == "tB")
        b2 = next(s for s in sched.sessions if s.name == "tB2")
        assert a.state == "done" and a.error is None, a.error
        assert a.preemptions == 2 and a.requeues == 2
        assert runs["n"] == 3                      # two replays
        # requeued replays FAST-FORWARD committed pieces, not recompute
        assert checkpoint.stats()["resume_fast_forwarded_pieces"] > 0
        assert a.result.equals(solo_a), "tA diverged from its solo run"
        assert b.result.equals(solo_b) and b2.result.equals(solo_b)
        assert c.result.equals(solo_c)
        assert c.recovery_events() == []
        assert a.outcome() == "preempted_requeued"
        st = sched.stats()
        assert st["preemptions"] == 2 and st["requeues"] == 2
        assert st["outcomes"] == {"preempted_requeued": 1,
                                  "completed": 3}

    def test_no_progress_guard_and_budget(self, env1):
        """A tenant that committed nothing since its last preemption is
        temporarily unpreemptable (storm guard), and an exhausted
        preemption budget excludes it permanently."""
        sched = QueryScheduler(env1, policy="priority")
        cand = QuerySession("hi", lambda: None, 5, priority=9)
        v = QuerySession("lo", lambda: None, 0, priority=0)
        assert sched._pick_victim(cand, [v]) is v
        # preempted once, no new pieces since: guarded
        v.preemptions, v.pieces_committed, v._progress_mark = 1, 3, 3
        assert sched._pick_victim(cand, [v]) is None
        v.pieces_committed = 4                     # made progress
        assert sched._pick_victim(cand, [v]) is v
        v.preemptions = v.preempt_budget           # budget exhausted
        assert sched._pick_victim(cand, [v]) is None
        # a draining session is never re-picked
        v.preemptions, v._drain_mode = 0, "preempt"
        assert sched._pick_victim(cand, [v]) is None
        # an equal-ranked candidate never preempts (strict outrank)
        v2 = QuerySession("peer", lambda: None, 1, priority=9)
        assert sched._pick_victim(cand, [v2]) is None

    def test_requeue_overflow_typed(self, env4, monkeypatch, tmp_path):
        """With requeue capacity 0, a completed preempt drain cannot be
        requeued: the tenant fails TYPED (RequeueOverflowError) with the
        original resumable abort — resume token included — chained as
        __cause__, never silently dropped."""
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", str(tmp_path))
        checkpoint.reset_stages()
        sched = QueryScheduler(env4, policy="priority",
                               max_concurrency=1, requeue_capacity=0)
        runs = {"n": 0}
        fn_a = _pipe_fn(env4, 11, n=1800, chunks=6)

        def tenant_a():
            runs["n"] += 1
            if runs["n"] == 1:
                sched.submit("tB", _pipe_fn(env4, 22, n=900, chunks=2),
                             priority=5)
            return fn_a()

        a = sched.submit("tA", tenant_a)
        sched.run()
        b = next(s for s in sched.sessions if s.name == "tB")
        assert b.state == "done" and b.error is None
        assert a.state == "failed"
        assert isinstance(a.error, RequeueOverflowError)
        assert isinstance(a.error.__cause__, ResumableAbort)
        assert a.outcome() == "failed_typed"
        assert sched.stats()["requeue_overflows"] == 1

    def test_unarmed_happy_path_adds_nothing(self, env4):
        """No priorities, no resize controller, checkpointing unarmed:
        the serving loop must carry ZERO preemption machinery — no
        checkpoint events, no filesystem writes, no recovery events, no
        votes beyond the baseline admission path (the PR 10/11 unarmed
        contract, extended to the fleet tier)."""
        assert not checkpoint.enabled()
        checkpoint.reset_stats()
        recovery.reset_events()
        sched = QueryScheduler(env4, policy="fair")
        sched.submit("t0", _pipe_fn(env4, 11))
        sched.submit("t1", _pipe_fn(env4, 22))
        sched.run(raise_errors=True)
        assert all(v == 0 for v in checkpoint.stats().values()), \
            checkpoint.stats()
        assert recovery.recovery_events() == []
        st = sched.stats()
        assert st["preemptions"] == 0 and st["requeues"] == 0
        assert st["fleet_drains"] == 0 and st["resize_target"] is None
        assert st["admission_timeouts"] == 0
        assert st["outcomes"] == {"completed": 2}


class TestAdmissionDeadline:
    def test_admission_timeout_typed(self, env1):
        """A pending session whose admission wait exceeds the deadline
        fails TYPED — AdmissionTimeoutError carrying the session name
        and waited seconds — with its wait period closed; the running
        tenant is untouched."""
        def holder():
            for _ in range(12):
                time.sleep(0.02)
                scheduler.maybe_yield()
            return "done"

        sched = QueryScheduler(env1, policy="fifo", budget_bytes=1000,
                               admission_timeout_s=0.05)
        a = sched.submit("tA", holder, footprint_bytes=600)
        b = sched.submit("tB", lambda: 1, footprint_bytes=600)
        sched.run()
        assert a.state == "done" and a.result == "done"
        assert b.state == "failed"
        assert isinstance(b.error, AdmissionTimeoutError)
        assert b.error.kind == "admission_timeout"
        assert b.error.session == "tB" and b.error.waited_s > 0.05
        assert b._wait_mark is None and b.admission_wait_s > 0
        assert b.outcome() == "failed_typed"
        st = sched.stats()
        assert st["admission_timeouts"] == 1
        assert st["outcomes"] == {"completed": 1, "failed_typed": 1}

    def test_admission_timeout_env_knob(self, env1, monkeypatch):
        """CYLON_TPU_ADMISSION_TIMEOUT_S arms the same deadline without
        a constructor change (the chaos/deploy surface)."""
        monkeypatch.setenv("CYLON_TPU_ADMISSION_TIMEOUT_S", "0.04")
        sched = QueryScheduler(env1)
        assert sched._admission_timeout() == pytest.approx(0.04)
        monkeypatch.setenv("CYLON_TPU_ADMISSION_TIMEOUT_S", "bogus")
        assert sched._admission_timeout() is None
        monkeypatch.setenv("CYLON_TPU_ADMISSION_TIMEOUT_S", "0")
        assert sched._admission_timeout() is None


class TestResizeController:
    def test_rejects_bad_target(self, env1):
        with pytest.raises(InvalidError):
            fleet.ResizeController(env1, target_world=0)

    def test_gated_on_checkpoint(self, env1):
        """Without durable checkpointing there is nothing to resume
        from: the controller must never engage (a drain now would lose
        work — the one thing this tier must never do)."""
        assert not checkpoint.enabled()
        ctrl = fleet.ResizeController(env1, target_world=2,
                                      queue_depth_high=0,
                                      min_committed_pieces=0)
        sched = QueryScheduler(env1, fleet=ctrl)
        assert ctrl.maybe_resize(sched) is False
        assert not ctrl.engaged and not sched._fleet_drain

    def test_pressure_triggers_and_breadcrumb(self, env1, monkeypatch,
                                              tmp_path):
        """Queue-depth pressure + durable progress engage the all-or-
        nothing fleet drain: every running tenant is flagged, the
        resize target latches, and the FLEET_RESIZE.json breadcrumb
        lands in the checkpoint root for the relauncher."""
        import json
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", str(tmp_path))
        ctrl = fleet.ResizeController(env1, target_world=2,
                                      queue_depth_high=1,
                                      min_committed_pieces=1)
        sched = QueryScheduler(env1, fleet=ctrl)
        run = sched.submit("hot", lambda: None)
        run.state, run.pieces_committed = "running", 3
        queued = sched.submit("cold", lambda: None)     # depth 1
        assert ctrl.should_resize(sched)
        assert ctrl.maybe_resize(sched) is True
        assert ctrl.engaged and sched._fleet_drain
        assert sched.resize_target == 2
        assert run._drain_mode == "fleet"
        assert queued._drain_mode is None               # pending: not flagged
        crumb = json.load(open(tmp_path / "FLEET_RESIZE.json"))
        assert crumb["target_world"] == 2
        assert crumb["from_world"] == env1.world_size
        assert crumb["queue_depth"] == 1
        assert sched.stats()["fleet_drains"] == 1
        # idempotent: an engaged controller never re-votes
        assert ctrl.maybe_resize(sched) is False

    def test_min_committed_guard(self, env1, monkeypatch, tmp_path):
        """Resizing a fleet that has committed nothing durable is just
        a restart — the controller waits for real progress."""
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", str(tmp_path))
        ctrl = fleet.ResizeController(env1, target_world=2,
                                      queue_depth_high=0,
                                      min_committed_pieces=5)
        sched = QueryScheduler(env1, fleet=ctrl)
        assert not ctrl.should_resize(sched)
        assert ctrl.maybe_resize(sched) is False

    def test_fleet_drain_resume_bit_equal(self, env4, monkeypatch,
                                          tmp_path):
        """End-to-end elastic drain in-process: the controller engages
        mid-traffic, every tenant exits resumable (ZERO failed-typed),
        and a resumed scheduler pass finishes all of them bit-equal
        with fast-forwarded pieces.  (The cross-world 4->2 relaunch leg
        runs in scripts/chaos_soak.py --fleet.)"""
        solos = {s: _pipe_fn(env4, s, n=1800, chunks=6)()
                 for s in (11, 22, 33)}
        monkeypatch.setenv("CYLON_TPU_CKPT_DIR", str(tmp_path))
        checkpoint.reset_stages()
        checkpoint.reset_stats()
        ctrl = fleet.ResizeController(env4, target_world=2,
                                      queue_depth_high=2)
        sched = QueryScheduler(env4, policy="fifo", max_concurrency=1,
                               fleet=ctrl)
        for i, s in enumerate((11, 22, 33)):
            sched.submit(f"t{i}", _pipe_fn(env4, s, n=1800, chunks=6))
        sched.run()
        assert sched.resize_target == 2
        st = sched.stats()
        assert st["outcomes"].get("failed_typed", 0) == 0
        assert all(s.outcome() in ("completed", "drained_resumable")
                   for s in sched.sessions)
        assert os.path.exists(tmp_path / "FLEET_RESIZE.json")

        # "relaunch" stand-in: resume in the same process
        monkeypatch.setenv("CYLON_TPU_RESUME", "1")
        checkpoint.reset_stages()
        checkpoint.reset_stats()
        sched2 = QueryScheduler(env4, policy="fifo", max_concurrency=1)
        for i, s in enumerate((11, 22, 33)):
            sched2.submit(f"t{i}", _pipe_fn(env4, s, n=1800, chunks=6))
        sched2.run(raise_errors=True)
        for i, s in enumerate((11, 22, 33)):
            assert sched2.sessions[i].result.equals(solos[s]), \
                f"t{i} diverged after the fleet drain resume"
        assert checkpoint.stats()["resume_fast_forwarded_pieces"] > 0


class TestFamilyHistory:
    def test_note_and_observe_peak(self):
        scheduler.reset_family_history()
        assert scheduler.observed_peak("mixA") is None
        scheduler.note_family_peak("mixA", 200)
        scheduler.note_family_peak("mixA", 150)     # max-update
        assert scheduler.observed_peak("mixA") == 200
        scheduler.note_family_peak("mixA", 500)
        assert scheduler.observed_peak("mixA") == 500


# ---------------------------------------------------------------------------
# acceptance drivers (slow tier)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_soak_fleet():
    """scripts/chaos_soak.py --fleet: the four pinned fleet schedules —
    preempt/requeue bit-equal, SIGKILL inside the preempt drain +
    resume, elastic 4->2 resize relaunch with zero failed tenants, and
    the typed admission deadline."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "chaos_soak.py"),
         "--fleet", "--rows", "2400", "--chunks", "4"],
        capture_output=True, text=True, timeout=560, cwd=REPO)
    assert p.returncode == 0, (p.stdout + p.stderr)[-4000:]
    assert '"failures": 0' in p.stdout


@pytest.mark.slow
def test_bench_serving_preemptive_64(tmp_path):
    """ISSUE 18 acceptance: the 64-tenant preemptive serving round
    — 8 high-priority arrivals against a running
    fleet, per-tenant p99 SLO attainment from the histogram registry,
    the per-tenant outcome table, and every answer bit-equal."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from bench_serving import run_serving
    finally:
        sys.path.remove(os.path.join(REPO, "scripts"))
    report = run_serving(tenants=64, queries=2, scale=0.004,
                         policy="priority", budget_mb="auto",
                         slo_ms=2000, preempt_tenants=8,
                         ckpt_dir=str(tmp_path))
    d = report["detail"]
    assert d["bit_equal"], d["failures"]
    assert not d["failures"]
    st = d["scheduler"]
    assert st["preemptions"] >= 1 and st["requeues"] >= 1
    assert st["outcomes"].get("failed_typed", 0) == 0
    assert sum(st["outcomes"].values()) == 64
    for name, info in d["tenants"].items():
        assert info["outcome"] in ("completed", "preempted_requeued")
        assert 0.0 <= info["slo_attainment"] <= 1.0
