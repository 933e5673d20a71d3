"""Rank-coherent failure recovery (cylon_tpu.exec.recovery +
cylon_tpu.status fault taxonomy): classification, the fault-injection
harness (``CYLON_TPU_FAULTS``), every consensus-ladder branch, and the
exchange watchdog — all exercised on the CPU rig, no real device OOM
needed.  docs/robustness.md."""

import numpy as np
import pandas as pd
import pytest

import cylon_tpu as ct
from cylon_tpu.exec import recovery
from cylon_tpu.status import (CapacityOverflowError, Code, CylonError,
                              DeviceOOMError, InvalidError,
                              PredictedResourceExhausted, RankDesyncError)


@pytest.fixture(autouse=True)
def _clean_injector():
    """Every test starts disarmed with empty event/occurrence state."""
    recovery.install_faults("")
    recovery.reset_events()
    yield
    recovery.install_faults("")
    recovery.reset_events()


def _tables(env, rng, n=4000):
    ldf = pd.DataFrame({"k": rng.integers(0, 500, n).astype(np.int64),
                        "a": rng.integers(0, 50, n).astype(np.int64)})
    rdf = pd.DataFrame({"k": rng.integers(0, 500, n).astype(np.int64),
                        "b": rng.integers(0, 50, n).astype(np.int64)})
    return (ldf, rdf, ct.Table.from_pandas(ldf, env),
            ct.Table.from_pandas(rdf, env))


# ---------------------------------------------------------------------------
# taxonomy + classification
# ---------------------------------------------------------------------------

class TestTaxonomy:
    def test_codes_and_kinds(self):
        assert PredictedResourceExhausted().code == Code.OutOfMemory
        assert DeviceOOMError().code == Code.OutOfMemory
        assert CapacityOverflowError().code == Code.CapacityError
        assert RankDesyncError().code == Code.ExecutionError
        assert PredictedResourceExhausted.kind == "predicted"
        assert DeviceOOMError.kind == "device_oom"
        assert CapacityOverflowError.kind == "capacity"
        assert RankDesyncError.kind == "desync"

    def test_predicted_is_memoryerror(self):
        # pre-taxonomy compat: foreign callers may catch MemoryError
        assert isinstance(PredictedResourceExhausted(), MemoryError)

    def test_classify_passthrough(self):
        for f in (PredictedResourceExhausted("x"), DeviceOOMError("x"),
                  CapacityOverflowError("x"), RankDesyncError("x")):
            assert recovery.classify(f) is f

    def test_classify_foreign_oom(self):
        e = RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating")
        f = recovery.classify(e)
        assert isinstance(f, DeviceOOMError) and f.__cause__ is e

    def test_classify_foreign_predicted(self):
        e = MemoryError("RESOURCE_EXHAUSTED (predicted): receive budget")
        f = recovery.classify(e)
        assert isinstance(f, PredictedResourceExhausted)

    def test_classify_non_faults(self):
        assert recovery.classify(ValueError("boom")) is None
        # typed engine errors are not recovery faults
        assert recovery.classify(InvalidError("bad arg")) is None

    def test_is_oom_shim(self):
        from cylon_tpu.relational.common import is_oom
        assert is_oom(RuntimeError("Out of memory while trying"))
        assert is_oom(PredictedResourceExhausted("anything"))
        assert not is_oom(ValueError("fine"))


# ---------------------------------------------------------------------------
# injection harness: grammar, rank/nth selectivity
# ---------------------------------------------------------------------------

class TestInjector:
    def test_grammar_rejects_unknown(self):
        with pytest.raises(ValueError):
            recovery.install_faults("nope.site=predicted")
        with pytest.raises(ValueError):
            recovery.install_faults("shuffle.recv_guard=nope")
        with pytest.raises(ValueError):
            recovery.install_faults("shuffle.recv_guard:0:1:9=predicted")

    def test_nth_selectivity(self):
        recovery.install_faults("groupby.device_oom::2=device_oom")
        assert recovery.injected("groupby.device_oom") is None   # 1st
        assert recovery.injected("groupby.device_oom") == "device_oom"
        assert recovery.injected("groupby.device_oom") is None   # consumed

    def test_every_occurrence(self):
        recovery.install_faults("groupby.device_oom::*=device_oom")
        assert all(recovery.injected("groupby.device_oom") == "device_oom"
                   for _ in range(3))

    def test_rank_selectivity(self):
        # this controller is process 0: a rank-1 spec never fires here
        recovery.install_faults("shuffle.recv_guard:1=predicted")
        assert recovery.injected("shuffle.recv_guard") is None
        recovery.install_faults("shuffle.recv_guard:0=predicted")
        assert recovery.injected("shuffle.recv_guard") == "predicted"

    def test_probe_armed_is_rank_uniform(self):
        """`armed` must depend only on the spec list and the per-site hit
        counter (both identical across ranks), never on whether THIS rank
        fired — a rank-0 spec keeps every rank's guard consensus engaged
        until its occurrence passes, then disengages everywhere."""
        recovery.install_faults("shuffle.recv_guard:1:2=predicted")
        # this controller is rank 0: the spec never fires here, but the
        # site stays armed through occurrence 2 and disarms after
        assert recovery.probe("shuffle.recv_guard") == (None, True)   # hit 1
        assert recovery.probe("shuffle.recv_guard") == (None, True)   # hit 2
        assert recovery.probe("shuffle.recv_guard") == (None, False)  # hit 3
        recovery.install_faults("shuffle.recv_guard::*=predicted")
        assert recovery.probe("shuffle.recv_guard")[1] is True
        assert recovery.probe("shuffle.recv_guard")[1] is True

    def test_unarmed_probe_is_silent(self):
        assert recovery.probe("shuffle.recv_guard") == (None, False)

    def test_grammar_accepts_disk_sites_and_enospc(self):
        recovery.install_faults("disk.write::1=enospc")
        recovery.install_faults("disk.write=corrupt,disk.read=stall")
        with pytest.raises(ValueError):
            recovery.install_faults("shuffle.recv_guard=enospc_typo")


# ---------------------------------------------------------------------------
# bounded IO retry (retry_io): the shared transient-OSError backoff
# ---------------------------------------------------------------------------

class TestRetryIO:
    def test_flaky_then_ok_succeeds(self, monkeypatch):
        """The regression the helper exists for: a single transient
        OSError (an NFS blip) no longer aborts — attempt 2 lands."""
        monkeypatch.setattr("time.sleep", lambda s: None)
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] == 1:
                raise OSError(5, "transient EIO")
            return "landed"

        assert recovery.retry_io(flaky, "ckpt.write") == "landed"
        assert calls[0] == 2

    def test_bounded_and_reraises_last(self, monkeypatch):
        monkeypatch.setattr("time.sleep", lambda s: None)
        calls = [0]

        def always():
            calls[0] += 1
            raise OSError(5, "still down")

        with pytest.raises(OSError):
            recovery.retry_io(always, "ckpt.write", attempts=3)
        assert calls[0] == 3        # bounded: never an unbounded loop

    def test_enospc_is_non_transient(self, monkeypatch):
        """A full disk does not heal on a millisecond backoff: ENOSPC
        re-raises immediately so the caller's typed degrade path owns
        it."""
        import errno
        monkeypatch.setattr("time.sleep", lambda s: None)
        calls = [0]

        def full():
            calls[0] += 1
            raise OSError(errno.ENOSPC, "disk full")

        with pytest.raises(OSError):
            recovery.retry_io(full, "disk.write")
        assert calls[0] == 1

    def test_non_oserror_propagates_untouched(self):
        with pytest.raises(ValueError):
            recovery.retry_io(lambda: (_ for _ in ()).throw(
                ValueError("not io")), "ckpt.write")

    def test_on_retry_callback_and_counter(self, monkeypatch):
        monkeypatch.setattr("time.sleep", lambda s: None)
        from cylon_tpu.obs import metrics
        c0 = metrics.counter("recovery_io_retries").value
        hits = [0]
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] < 3:
                raise OSError(5, "blip")
            return 1

        assert recovery.retry_io(
            flaky, "disk.write",
            on_retry=lambda: hits.__setitem__(0, hits[0] + 1)) == 1
        assert hits[0] == 2
        assert metrics.counter("recovery_io_retries").value == c0 + 2


class TestDiskCorruptClassification:
    def test_disk_site_corruption_is_a_fault(self):
        from cylon_tpu.status import CheckpointCorruptError
        e = CheckpointCorruptError("spill page bad", site="disk.read")
        assert recovery.classify(e) is e
        # the ladder's recompute rung exists for it
        assert Code.SerializationError in recovery.RETRY_RUNGS

    def test_ckpt_site_corruption_stays_non_fault(self):
        """Checkpoint-site corruption keeps its local restore-degrade
        handling — the ladder must NOT adopt it."""
        from cylon_tpu.status import CheckpointCorruptError
        assert recovery.classify(
            CheckpointCorruptError("page bad", site="ckpt.load")) is None
        assert recovery.classify(
            CheckpointCorruptError("page bad")) is None

    def test_wire_round_trip(self):
        from cylon_tpu.status import CheckpointCorruptError
        e = CheckpointCorruptError("x", site="disk.read")
        wire = recovery._wire_code(e)
        back = recovery._fault_from_wire(wire, "peer corrupt")
        assert isinstance(back, CheckpointCorruptError)
        assert back.site == "disk.read"

    def test_all_four_kinds_constructible(self):
        """Acceptance: every typed fault kind is constructible via
        injection on the CPU rig."""
        recovery.install_faults("join.piece_cap=capacity")
        with pytest.raises(CapacityOverflowError):
            recovery.maybe_inject("join.piece_cap")
        recovery.install_faults("shuffle.recv_guard=predicted")
        with pytest.raises(PredictedResourceExhausted):
            recovery.maybe_inject("shuffle.recv_guard")
        recovery.install_faults("groupby.device_oom=device_oom")
        with pytest.raises(RuntimeError) as ei:  # foreign-shaped on purpose
            recovery.maybe_inject("groupby.device_oom")
        assert isinstance(recovery.classify(ei.value), DeviceOOMError)
        recovery.install_faults("exchange.stall=desync")
        with pytest.raises(RankDesyncError):
            recovery.maybe_inject("exchange.stall")

    def test_ckpt_sites_and_kinds_parse(self):
        """The durable-checkpoint grammar extensions: ckpt.write /
        ckpt.load sites, `corrupt` raises typed, `kill` parses (firing
        it would SIGKILL this process — the chaos-soak harness and
        tests/test_checkpoint.py exercise that in child processes)."""
        from cylon_tpu.status import CheckpointCorruptError
        recovery.install_faults("ckpt.load=corrupt")
        with pytest.raises(CheckpointCorruptError):
            recovery.maybe_inject("ckpt.load")
        recovery.install_faults("ckpt.write:0:2=kill")
        kind, armed = recovery.probe("ckpt.write")
        assert (kind, armed) == (None, True)   # occurrence 1: armed only
        assert recovery.probe("ckpt.write")[0] == "kill"

    def test_elastic_sites_and_kinds_parse(self):
        """The elastic-resume grammar extensions: the ckpt.reshard site
        (corrupt parses as interceptable — exec/checkpoint converts it
        to a typed CheckpointCorruptError — and kill parses; firing it
        would SIGKILL this process, exercised by chaos_soak --elastic)
        and the `term` kind (delivers SIGTERM — the preemption notice;
        tests/test_checkpoint.py fires it under an installed grace
        handler)."""
        recovery.install_faults("ckpt.reshard=corrupt")
        assert recovery.maybe_inject(
            "ckpt.reshard", intercept=("corrupt",)) == "corrupt"
        recovery.install_faults("ckpt.reshard::2=kill")
        kind, armed = recovery.probe("ckpt.reshard")
        assert (kind, armed) == (None, True)
        assert recovery.probe("ckpt.reshard")[0] == "kill"
        recovery.install_faults("ckpt.write::3=term")
        assert recovery.probe("ckpt.write") == (None, True)
        recovery.install_faults("")

    def test_install_faults_fully_resets_state(self):
        """Regression (chaos-soak hygiene): re-installing a schedule
        must clear the per-site occurrence counters AND the recorded
        event log — otherwise iteration N+1's `nth` specs fire shifted
        by iteration N's probe count and its report inherits stale
        events."""
        recovery.install_faults("groupby.device_oom::2=device_oom")
        assert recovery.injected("groupby.device_oom") is None       # hit 1
        assert recovery.injected("groupby.device_oom") == "device_oom"
        # re-install: counters restart — the nth=2 spec must NOT fire at
        # the first post-install occurrence (a stale counter would put
        # the site at hit 3 and the spec would never fire again)
        recovery.install_faults("groupby.device_oom::2=device_oom")
        assert recovery.injected("groupby.device_oom") is None       # hit 1
        assert recovery.injected("groupby.device_oom") == "device_oom"
        # ... and the recorded event log is cleared as well
        recovery.install_faults("groupby.device_oom::1=device_oom")
        with pytest.raises(RuntimeError):
            recovery.maybe_inject("groupby.device_oom")
        assert len(recovery.recovery_events()) == 1
        recovery.install_faults("groupby.device_oom::1=device_oom")
        assert recovery.recovery_events() == []


# ---------------------------------------------------------------------------
# ladder branches (unit level)
# ---------------------------------------------------------------------------

class TestLadder:
    def test_ok_passthrough(self):
        assert recovery.run_with_recovery(
            lambda: 42, True, lambda nc: None, "t") == 42
        assert recovery.recovery_events() == []

    def test_oom_rungs_4_then_16(self):
        seen = []

        def fb(nc):
            seen.append(nc)
            if nc == 4:
                raise RuntimeError("RESOURCE_EXHAUSTED again")
            return "ok"

        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        assert recovery.run_with_recovery(boom, True, fb, "t") == "ok"
        assert seen == [4, 16]
        acts = [e["action"] for e in recovery.recovery_events()]
        assert acts == ["retry_chunks_4", "retry_chunks_16"]

    def test_capacity_single_halving_rung(self):
        seen = []

        def boom():
            raise CapacityOverflowError("cap", site="join.piece_cap")

        assert recovery.run_with_recovery(
            boom, True, lambda nc: seen.append(nc) or "ok", "t") == "ok"
        assert seen == [8]  # exactly one cap-halving step

    def test_exhaustion_raises_typed(self):
        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        def fb(nc):
            raise RuntimeError("RESOURCE_EXHAUSTED still")

        with pytest.raises(DeviceOOMError):
            recovery.run_with_recovery(boom, True, fb, "t")
        acts = [e["action"] for e in recovery.recovery_events()]
        assert acts == ["retry_chunks_4", "retry_chunks_16", "abort"]

    def test_non_fault_propagates_untouched(self):
        def boom():
            raise ValueError("not a fault")

        with pytest.raises(ValueError):
            recovery.run_with_recovery(boom, True, lambda nc: "ok", "t")
        assert recovery.recovery_events() == []

    def test_desync_never_retries(self):
        def boom():
            raise RankDesyncError("peer hung", site="exchange.stall")

        with pytest.raises(RankDesyncError):
            recovery.run_with_recovery(boom, True, lambda nc: "ok", "t")
        assert [e["action"] for e in recovery.recovery_events()] == ["abort"]

    def test_nested_ladder_never_reescalates(self):
        """A fallback re-entering a guarded op gets NO rungs of its own —
        the outer ladder owns the bounded escalation."""
        inner_fallback_calls = []

        def inner():
            def boom():
                raise RuntimeError("RESOURCE_EXHAUSTED inner")
            return recovery.run_with_recovery(
                boom, True, lambda nc: inner_fallback_calls.append(nc),
                "inner")

        def fb(nc):
            if nc == 4:
                inner()  # typed DeviceOOMError escalates the OUTER ladder
            return "ok"

        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED outer")

        assert recovery.run_with_recovery(boom, True, fb, "outer") == "ok"
        assert inner_fallback_calls == []

    def test_counted_in_timing_stats(self):
        from cylon_tpu.utils import timing

        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

        recovery.run_with_recovery(boom, True, lambda nc: "ok", "t")
        snap = timing.snapshot()
        assert any(k.startswith("recovery.t.device_oom.retry")
                   for k in snap), snap


# ---------------------------------------------------------------------------
# ladder branches through the real operators (injection-driven)
# ---------------------------------------------------------------------------

class TestInjectedOperators:
    def test_predicted_guard_retry_join(self, env4, rng):
        """The acceptance scenario, single-controller edition: a predicted
        receive-budget fault at the shuffle guard reroutes the join through
        the streaming pipeline with ONE logged recovery event, and the
        result is identical to the un-injected run."""
        from cylon_tpu.relational import join_tables
        ldf, rdf, lt, rt = _tables(env4, rng)
        recovery.install_faults("shuffle.recv_guard:0:1=predicted")
        j = join_tables(lt, rt, "k", "k", how="inner")
        got = j.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        exp = ldf.merge(rdf, on="k").sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got[exp.columns], exp,
                                      check_dtype=False)
        evs = recovery.recovery_events()
        assert len(evs) == 1, evs
        assert evs[0] == {"site": "join", "kind": "predicted",
                          "action": "retry_chunks_4"}

    def test_device_oom_escalates_to_16(self, env4, rng):
        """4 → 16 chunk escalation: the first fallback rung hits the
        (still-armed) injected fault, the second succeeds."""
        from cylon_tpu.relational import groupby_aggregate
        ldf, _, _, _ = _tables(env4, rng)
        t = ct.Table.from_pandas(ldf, env4)
        recovery.install_faults(
            "groupby.device_oom::1=device_oom,"
            "groupby.device_oom::2=device_oom")
        g = groupby_aggregate(t, "k", [("a", "sum")])
        got = g.to_pandas().sort_values("k").reset_index(drop=True)
        exp = (ldf.groupby("k", as_index=False).agg(a_sum=("a", "sum")))
        exp.columns = got.columns
        pd.testing.assert_frame_equal(got, exp.sort_values("k")
                                      .reset_index(drop=True),
                                      check_dtype=False)
        acts = [e["action"] for e in recovery.recovery_events()]
        assert "retry_chunks_4" in acts and "retry_chunks_16" in acts

    def test_device_oom_exhaustion_typed_raise(self, env4, rng):
        """4 → 16 → typed DeviceOOMError when the fault never clears."""
        from cylon_tpu.relational import groupby_aggregate
        ldf, _, _, _ = _tables(env4, rng, n=1200)
        t = ct.Table.from_pandas(ldf, env4)
        recovery.install_faults("groupby.device_oom::*=device_oom")
        with pytest.raises(DeviceOOMError):
            groupby_aggregate(t, "k", [("a", "sum")])
        acts = [e["action"] for e in recovery.recovery_events()
                if e["site"] == "groupby"]
        assert acts[0] == "retry_chunks_4"
        assert "retry_chunks_16" in acts
        assert acts[-1] == "abort"

    def test_capacity_overflow_escalates_ladder(self, env4, rng):
        """An injected CapacityOverflowError on the first packed-piece
        join (inside the 4-chunk fallback) moves the outer ladder to its
        next rung (halving the piece caps) and still completes
        correctly."""
        from cylon_tpu.relational import join_tables
        ldf, rdf, lt, rt = _tables(env4, rng)
        recovery.install_faults(
            "shuffle.recv_guard:0:1=predicted,join.piece_cap::1=capacity")
        j = join_tables(lt, rt, "k", "k", how="inner")
        got = j.to_pandas().sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        exp = ldf.merge(rdf, on="k").sort_values(["k", "a", "b"]) \
            .reset_index(drop=True)
        pd.testing.assert_frame_equal(got[exp.columns], exp,
                                      check_dtype=False)
        acts = [e["action"] for e in recovery.recovery_events()
                if e["site"] == "join"]
        # predicted -> 4-chunk rung (hits capacity fault) -> 16-chunk rung
        assert acts[0] == "retry_chunks_4"
        assert "retry_chunks_16" in acts

    def test_packed_piece_cap_check_is_typed(self, env4, rng):
        from cylon_tpu.relational.piece import PieceSource
        ldf, _, _, _ = _tables(env4, rng, n=800)
        t = ct.Table.from_pandas(ldf, env4)
        src = PieceSource(t, pad=8)
        w = env4.world_size
        with pytest.raises(CapacityOverflowError):
            src.packed(np.zeros(w, np.int64), np.full(w, 64, np.int64),
                       piece_cap=32)


# ---------------------------------------------------------------------------
# overlap scheduler × recovery interplay (ISSUE 6)
# ---------------------------------------------------------------------------

class TestOverlapRobustness:
    """The phase-overlapped piece scheduler (CYLON_TPU_PACKED_OVERLAP)
    must not change WHAT the recovery ladder sees or WHERE typed faults
    surface: deferred phase faults re-raise at the same consume point,
    and the ladder's escalation sequence is identical with overlap on
    or off."""

    def test_piece_future_defers_typed_not_foreign(self):
        from cylon_tpu.exec.pipeline import _PieceFuture

        def typed():
            raise CapacityOverflowError("deferred until consumed")

        fut = _PieceFuture(typed, defer_faults=True)   # held, no raise yet
        with pytest.raises(CapacityOverflowError):
            fut.get()
        # the non-overlapped schedule raises at dispatch
        with pytest.raises(CapacityOverflowError):
            _PieceFuture(typed, defer_faults=False)

        def foreign():
            raise ValueError("not a taxonomy fault")

        # foreign exceptions must NOT be detached from their dispatch
        # context — they raise immediately even when deferring
        with pytest.raises(ValueError):
            _PieceFuture(foreign, defer_faults=True)

    def test_phase_sync_fault_surfaces_typed(self, env4, rng, monkeypatch):
        """A fault injected at the overlap scheduler's designated
        pre-loop sync point (pipe.phase_sync) surfaces as a TYPED fault
        there — not as a raw jax error from an arbitrary later pull."""
        from cylon_tpu import config
        from cylon_tpu.exec import pipelined_join
        ldf, rdf, lt, rt = _tables(env4, rng, n=1500)
        monkeypatch.setattr(config, "PACKED_OVERLAP", True)
        recovery.install_faults("pipe.phase_sync::1=predicted")
        with pytest.raises(PredictedResourceExhausted):
            pipelined_join(lt, rt, "k", "k", how="inner", n_chunks=3)
        assert recovery.recovery_events() == [
            {"site": "pipe.phase_sync", "kind": "predicted",
             "action": "injected"}]
        # with overlap off the designated sync point does not exist
        # (per-phase pulls instead) — the same armed fault never fires
        monkeypatch.setattr(config, "PACKED_OVERLAP", False)
        recovery.install_faults("pipe.phase_sync::1=predicted")
        out = pipelined_join(lt, rt, "k", "k", how="inner", n_chunks=3)
        assert out.row_count == len(ldf.merge(rdf, on="k"))
        assert recovery.recovery_events() == []

    def test_piece_cap_ladder_identical_overlap_on_off(self, env4, rng,
                                                       monkeypatch):
        """Injected CapacityOverflow inside the pipelined fallback: the
        consensus ladder must take the identical escalation sequence and
        produce bit- and order-equal output with overlap on or off."""
        import gc
        from cylon_tpu import config
        from cylon_tpu.relational import join_tables
        ldf, rdf, lt, rt = _tables(env4, rng)
        runs = {}
        for overlap in (True, False):
            # drain leaked spillable registrations from the previous
            # mode's run: a phantom spill rung would (legitimately)
            # change the ladder sequence for reasons unrelated to overlap
            gc.collect()
            monkeypatch.setattr(config, "PACKED_OVERLAP", overlap)
            recovery.install_faults(
                "shuffle.recv_guard:0:1=predicted,"
                "join.piece_cap::1=capacity")
            j = join_tables(lt, rt, "k", "k", how="inner")
            runs[overlap] = (j.to_pandas(), recovery.recovery_events())
            recovery.install_faults("")
        (df_on, ev_on), (df_off, ev_off) = runs[True], runs[False]
        assert ev_on == ev_off
        assert any(e["action"] == "retry_chunks_16" for e in ev_on), ev_on
        pd.testing.assert_frame_equal(df_on, df_off)

    def test_spill_upload_fault_identical_overlap_on_off(self, env4, rng,
                                                         monkeypatch):
        """Budget-forced spilled sources: a device-OOM fault injected at
        the spill.upload re-entry fires inside the piece dispatch — under
        overlap, while dispatching ahead of the consume point — and the
        ladder must classify it and converge to the identical escalation
        sequence and bit-equal result in both dispatch modes."""
        import gc
        from cylon_tpu import config
        from cylon_tpu.exec import pipelined_join
        _ldf, _rdf, lt, rt = _tables(env4, rng)
        monkeypatch.setattr(config, "HBM_BUDGET_BYTES", 4096)
        runs = {}
        for overlap in (True, False):
            gc.collect()
            monkeypatch.setattr(config, "PACKED_OVERLAP", overlap)
            recovery.install_faults("spill.upload::1=device_oom")

            def attempt(nc):
                return pipelined_join(lt, rt, "k", "k", how="inner",
                                      n_chunks=nc)

            out = recovery.run_with_recovery(
                lambda: attempt(4), True, attempt, "join", env=env4)
            runs[overlap] = (out.to_pandas(), recovery.recovery_events())
            recovery.install_faults("")
        (df_on, ev_on), (df_off, ev_off) = runs[True], runs[False]
        assert ev_on and ev_on == ev_off
        assert ev_on[0]["kind"] == "device_oom", ev_on
        pd.testing.assert_frame_equal(df_on, df_off)

    def test_groupby_oom_ladder_identical_overlap_on_off(self, env4, rng,
                                                         monkeypatch):
        """The chaos-soak workload shape (pipelined join into a
        GroupBySink under run_with_recovery) with an injected device OOM
        at the groupby site: identical ladder events and bit-equal
        finalize with overlap on or off.  The sink keys on a NON-join
        column so the cross-chunk combine (groupby_aggregate — where the
        site is probed) actually runs."""
        import gc
        from cylon_tpu import config
        from cylon_tpu.exec import GroupBySink, pipelined_join
        n = 2000
        ldf = pd.DataFrame({"k": rng.integers(0, 500, n).astype(np.int64),
                            "g": rng.integers(0, 7, n).astype(np.int64),
                            "a": rng.integers(0, 50, n).astype(np.int64)})
        rdf = pd.DataFrame({"k": rng.integers(0, 500, n).astype(np.int64),
                            "b": rng.integers(0, 50, n).astype(np.int64)})
        lt = ct.Table.from_pandas(ldf, env4)
        rt = ct.Table.from_pandas(rdf, env4)
        runs = {}
        for overlap in (True, False):
            gc.collect()
            monkeypatch.setattr(config, "PACKED_OVERLAP", overlap)
            recovery.install_faults("groupby.device_oom::1=device_oom")

            def attempt(nc):
                sink = GroupBySink("g", [("a", "sum")])
                pipelined_join(lt, rt, "k", "k", how="inner",
                               n_chunks=nc, sink=sink)
                return sink.finalize()

            out = recovery.run_with_recovery(
                lambda: attempt(4), True, attempt, "soak", env=env4)
            runs[overlap] = (out.to_pandas().sort_values("g")
                             .reset_index(drop=True),
                             recovery.recovery_events())
            recovery.install_faults("")
        (df_on, ev_on), (df_off, ev_off) = runs[True], runs[False]
        assert ev_on and ev_on == ev_off
        pd.testing.assert_frame_equal(df_on, df_off)


# ---------------------------------------------------------------------------
# consensus + watchdog
# ---------------------------------------------------------------------------

class TestConsensusAndWatchdog:
    def test_consensus_single_controller_is_local(self, env4):
        # one process drives the whole mesh: the local code IS the vote
        assert recovery.consensus_code(env4.mesh, Code.OK) == Code.OK
        assert recovery.consensus_code(
            env4.mesh, Code.OutOfMemory) == Code.OutOfMemory
        assert recovery.consensus_code(None, Code.CapacityError) \
            == Code.CapacityError

    def test_consensus_program_is_one_pmax(self, env8):
        """The consensus builder's program: a single unconditional pmax —
        verified the same way the trace-safety gate does."""
        from cylon_tpu.analysis import jaxpr_check, registry
        registry.collect()
        decl = registry.get("cylon_tpu.exec.recovery._consensus_fn")
        assert decl is not None and decl.collectives == {"pmax"}
        assert jaxpr_check.verify_builder(decl, env8.mesh) == []

    def test_guard_consensus_local(self, env4):
        assert recovery.guard_consensus(env4.mesh, True)
        assert not recovery.guard_consensus(env4.mesh, False)

    def test_ckpt_commit_consensus_local(self, env4):
        # single-controller: the local staged epoch IS the agreed epoch
        # (no collective) — multiprocess divergence is exercised by the
        # kill-resume scenario in tests/multihost_driver.py
        assert recovery.ckpt_commit_consensus(env4.mesh, 3) == 3
        assert recovery.ckpt_commit_consensus(None, 0) == 0
        with pytest.raises(ValueError):
            recovery.ckpt_commit_consensus(env4.mesh, 1 << 21)

    def test_watchdog_passthrough_when_off(self):
        assert recovery.exchange_watchdog("exchange.counts",
                                          lambda: 7, timeout_s=0) == 7

    def test_watchdog_completes_within_deadline(self):
        assert recovery.exchange_watchdog("exchange.counts",
                                          lambda: 7, timeout_s=5.0) == 7

    def test_watchdog_propagates_thunk_error(self):
        def boom():
            raise ValueError("inner")

        with pytest.raises(ValueError):
            recovery.exchange_watchdog("exchange.counts", boom,
                                       timeout_s=5.0)

    def test_watchdog_converts_stall_to_desync(self):
        """An injected peer stall becomes a typed RankDesyncError carrying
        the site and the last-known timing phase."""
        from cylon_tpu.utils import timing
        with timing.region("pipe.unit_test_phase"):
            pass
        recovery.install_faults("exchange.stall=stall")
        with pytest.raises(RankDesyncError) as ei:
            recovery.exchange_watchdog("exchange.counts",
                                       lambda: 7, timeout_s=0.2)
        assert ei.value.site == "exchange.counts"
        assert ei.value.phase == "pipe.unit_test_phase"

    def test_watchdog_stall_through_shuffle(self, env4, rng, monkeypatch):
        """End to end: a stalled exchange count pull surfaces as a typed
        RankDesyncError from shuffle_table (no infinite block), and the
        ladder refuses to retry it."""
        from cylon_tpu import config
        from cylon_tpu.relational.repart import shuffle_table
        monkeypatch.setattr(config, "EXCHANGE_WATCHDOG_S", 0.2)
        ldf, _, lt, _ = _tables(env4, rng, n=800)
        recovery.install_faults("exchange.stall=stall")
        with pytest.raises(RankDesyncError):
            shuffle_table(lt, ["k"])


# ---------------------------------------------------------------------------
# taxonomy at the real guard site
# ---------------------------------------------------------------------------

class TestGuardSiteTyped:
    def test_peer_fault_placeholder_is_typed(self):
        """Ranks following a peer's agreed fault must synthesize a TYPED
        taxonomy fault of the SAME class (the wire encoding separates
        predicted from device OOM) — classify() passes it through,
        keeping enclosing ladders and type-dispatching callers (a
        driver's abort-vs-halve) on the same branch on every rank."""
        from cylon_tpu.exec.recovery import _fault_from_wire, _wire_code
        for local in (PredictedResourceExhausted("x"), DeviceOOMError("x"),
                      CapacityOverflowError("x"), RankDesyncError("x")):
            synth = _fault_from_wire(_wire_code(local), "peer")
            assert type(synth) is type(local), (local, synth)
            assert recovery.classify(synth) is synth
        # predicted sorts BELOW a real device OOM within Code.OutOfMemory:
        # mixed ranks coherently agree on the device_oom interpretation
        assert _wire_code(PredictedResourceExhausted("x")) \
            < _wire_code(DeviceOOMError("x"))
        assert _wire_code(None) == 0

    def test_recv_guard_honors_injected_kind(self, env4, rng):
        """A non-predicted kind injected at the guard site raises THAT
        kind (not the predicted shape), so simulations of real device
        OOMs at the exchange behave like real device OOMs."""
        from cylon_tpu.relational.repart import shuffle_table
        ldf, _, lt, _ = _tables(env4, rng, n=800)
        recovery.install_faults("shuffle.recv_guard::1=capacity")
        with pytest.raises(CapacityOverflowError):
            shuffle_table(lt, ["k"])

    def test_recv_guard_raises_typed(self, env8, rng, monkeypatch):
        from cylon_tpu import config
        from cylon_tpu.relational.repart import shuffle_table
        monkeypatch.setattr(config, "EXCHANGE_RECV_BUDGET_BYTES", 4096)
        monkeypatch.setattr(config, "EXCHANGE_RECV_GUARD_CPU", True)
        n = 4000
        t = ct.Table.from_pandas(
            pd.DataFrame({"k": np.full(n, 7, np.int64),
                          "v": rng.random(n)}), env8)
        with pytest.raises(PredictedResourceExhausted) as ei:
            shuffle_table(t, ["k"])
        assert ei.value.site == "shuffle.recv_guard"
        assert "RESOURCE_EXHAUSTED (predicted)" in str(ei.value)
