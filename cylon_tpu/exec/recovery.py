"""Rank-coherent failure recovery: classification, consensus, injection.

Cylon's distributed operators are ``local partition → all-to-all shuffle →
local op`` (SURVEY §0), which on TPU makes every failure-recovery decision
a COLLECTIVE decision: if one rank's receive-budget guard fires and it
retries at a different chunk count while its peers proceed, the next
collective deadlocks the whole mesh.  This module is the one place those
decisions are made, built on four pillars (docs/robustness.md):

1. **Typed fault taxonomy** (classes live in :mod:`cylon_tpu.status`):
   :class:`~cylon_tpu.status.PredictedResourceExhausted` (guard fired
   pre-allocation, HBM not poisoned — safe in-process retry),
   :class:`~cylon_tpu.status.DeviceOOMError` (real XLA
   RESOURCE_EXHAUSTED), :class:`~cylon_tpu.status.CapacityOverflowError`
   (pow2 piece/output cap exceeded) and
   :class:`~cylon_tpu.status.RankDesyncError` (peer hang / structural
   divergence).  :func:`classify` is the ONLY sanctioned place that
   string-matches runtime OOM text (lint rule TS105 enforces this).

2. **Rank-coherent retry ladder** (:func:`run_with_recovery`): in a
   multiprocess (``jax.distributed``) session, ranks all-reduce a small
   status code — max over :class:`~cylon_tpu.status.Code` values via a
   one-element ``pmax`` shard_map program — after every guarded attempt,
   so every rank takes the IDENTICAL branch: same fallback chunk count,
   same cap-halving step, or same typed abort.  Escalation is bounded and
   deterministic (predicted OOM: spill-then-retry at the SAME chunk
   count first — the host spill tier, :mod:`cylon_tpu.exec.memory`,
   frees resident bytes without discarding completed work — then chunks
   4 → 16; capacity overflow: one cap-halving step at 8 chunks), nested
   ladders never re-escalate (the outer ladder owns the rungs), and
   every recovery event is logged and counted in
   :mod:`cylon_tpu.utils.timing` phase stats.

3. **Fault injection** (``CYLON_TPU_FAULTS="site[:rank][:nth]=kind"``):
   each typed fault is constructible at its named site on the CPU rig, so
   the whole ladder is testable without a real device OOM.  Sites:
   ``shuffle.recv_guard``, ``join.piece_cap``, ``groupby.device_oom``,
   ``exchange.stall``, ``spill.evict``, ``spill.upload``.  Kinds:
   ``predicted``, ``device_oom``, ``capacity``, ``desync``, ``stall``
   (fires inside the watchdog) and ``spill_stall`` (hangs a spill-tier
   host↔device transfer; at ``spill.evict`` the ``predicted`` kind
   simulates rank-local memory PRESSURE — consensus'd, then evicted —
   rather than raising).  ``rank`` defaults to every rank (``*``);
   ``nth`` is the 1-based occurrence to fire on (default 1; ``*`` =
   every occurrence).

4. **Exchange watchdog** (:func:`exchange_watchdog`): an optional timeout
   (``CYLON_TPU_WATCHDOG_S``) around multihost exchange host-syncs that
   converts a peer hang into a typed
   :class:`~cylon_tpu.status.RankDesyncError` carrying the site and the
   last-known timing phase, instead of an infinite block.

The rank-coherence invariant underlying all of this: **no rank-local
control flow after a collective has been entered** — any guard that can
abort an exchange must take its raise/proceed decision through
:func:`guard_consensus` BEFORE the first collective of that exchange is
dispatched.

**Serving-session isolation** (:mod:`cylon_tpu.exec.scheduler`): when
the multi-tenant scheduler interleaves concurrent queries, each session
runs on its own thread tagged via :func:`set_session`.  Three things
follow from the tag: (1) recovery EVENTS carry the session name, so one
tenant's retry ladder is auditable in isolation
(:func:`events_for_session`) and never pollutes another's log; (2) the
injection grammar grows an optional ``@session`` selector
(``site[:rank][:nth]=kind@tenant``, with ``nth`` counted against the
TARGET session's own probes) so chaos schedules can fault one tenant
while its neighbors run clean; (3) the guard/spill/ladder consensus
wires carry a small session NAMESPACE field above the payload — in a
multiprocess session a rank that enters a consensus poll while a peer is
voting from a different session raises a typed
:class:`RankDesyncError` instead of silently adopting a foreign
tenant's fault code.  The ladder's nesting depth (``_tls.depth``) is
already thread-local, so concurrent ladders never see each other's
escalation state.
"""

from __future__ import annotations

import errno
import os
import threading

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import config
from ..ctx.context import ROW_AXIS
from ..obs import trace as _trace
from ..status import (CapacityOverflowError, CheckpointCorruptError, Code,
                      CylonError, DataIntegrityError, DeviceOOMError,
                      FAULT_TYPES, PredictedResourceExhausted,
                      RankDesyncError, ResumableAbort)
from ..utils.cache import program_cache

shard_map = jax.shard_map

#: injection site names (docs/robustness.md spec grammar).  The spill
#: sites (exec/memory): ``spill.evict`` is probed by the ledger's
#: admission path — kind ``predicted`` there simulates rank-local
#: memory PRESSURE (consensus'd, then evicted) rather than raising —
#: and ``spill.upload`` guards the host→device re-entry of spilled
#: windows.  The checkpoint sites (exec/checkpoint): ``ckpt.write``
#: wraps the page write + manifest commit of one piece, ``ckpt.load``
#: the resume-path restore — kind ``corrupt`` there corrupts (or
#: simulates detecting a corrupted) page instead of raising.
#: ``pipe.phase_sync`` is the overlap scheduler's designated pre-loop
#: batched pull (exec/pipeline._pull_phase_outputs) — injecting there
#: proves deferred-phase faults surface typed at the consensus-coherent
#: sync point, not inside an arbitrary later pull.  The stream sites
#: (cylon_tpu/stream): ``stream.append`` wraps one micro-batch's ingest
#: (shuffle + ledger admission + sink absorb) — ``kill`` there is the
#: chaos harness's mid-ingest crash — and ``stream.watermark`` wraps the
#: watermark min-vote that closes event-time windows.  ``ckpt.reshard``
#: wraps the elastic resume's foreign-rank page read + re-shard
#: (exec/checkpoint.load_foreign_pieces): ``corrupt`` there simulates a
#: failed foreign-page hash check (the stage degrades to recompute,
#: never a wrong answer) and ``kill`` crashes mid-reshard — the resumed
#: rerun must converge anyway.
#: ``obs.export`` wraps the flight recorder's Chrome-trace write
#: (cylon_tpu/obs/trace.export): injecting there proves a hung or
#: corrupt trace write surfaces TYPED instead of silently losing the
#: timeline the operator armed.  The disk-tier sites (exec/memory):
#: ``disk.write`` wraps one registration's host→disk demotion (kinds
#: ``corrupt`` = flip a page byte after hashing so the promote-side
#: verification catches it, ``stall`` = hang the page write inside the
#: watchdog, ``enospc`` = the write fails with a non-transient
#: ``OSError(ENOSPC)`` and the demotion degrades to keeping the page
#: host-resident — never a crash) and ``disk.read`` wraps the
#: disk→host/device promotion's verify pass (``corrupt`` simulates a
#: failed sha check — the owner degrades to recompute, never a wrong
#: answer; ``stall`` hangs the verify read inside the watchdog).
#: ``sched.preempt`` fires at a serving session's preemptive/fleet
#: drain boundary (exec/checkpoint.drain_requested, on the VICTIM's
#: thread — so ``@session`` targets the drained tenant and ``nth``
#: counts its own drain boundaries): ``stall`` widens the drain window,
#: ``kill``/``term`` deliver the signal mid-drain — the chaos-soak
#: schedule proving a crash DURING a preemption drain still resumes
#: every tenant bit-identically (docs/serving.md, docs/robustness.md).
#: ``compile.build`` guards every facade-routed compile
#: (exec/compiler._lifecycle): ``stall`` hangs the build inside the
#: compile watchdog (typed CompileTimeoutError), ``kill`` SIGKILLs
#: mid-compile AFTER the intent journal hit disk (the quarantine
#: drill), and ``corrupt`` poisons the persistent warm-manifest entry
#: the facade just wrote — the next process must drop it on the hash
#: check (clean miss), never load wrong code.
#: The integrity-audit sites (exec/integrity, docs/robustness.md
#: "Integrity audit tier"): ``exchange.corrupt`` fires just AFTER an
#: exchange delivered its buffers — kind ``corrupt`` is INTERCEPTED
#: there and flips one element of one received column in place (rank/
#: nth/``@session``-selectable), the silent-corruption drill the armed
#: fingerprint layer must catch; and ``audit.verify`` wraps the armed
#: fingerprint verification's consensus pull — ``stall`` there hangs
#: the audit vote inside the exchange watchdog (typed RankDesyncError,
#: never a hang).
SITES = ("shuffle.recv_guard", "join.piece_cap", "groupby.device_oom",
         "exchange.stall", "spill.evict", "spill.upload",
         "disk.write", "disk.read",
         "ckpt.write", "ckpt.load", "ckpt.reshard", "pipe.phase_sync",
         "stream.append", "stream.watermark", "obs.export",
         "sched.preempt", "compile.build",
         "exchange.corrupt", "audit.verify")

#: fault kinds accepted by the injection grammar; ``spill_stall`` hangs
#: a spill-tier host↔device transfer inside the watchdog (the spill
#: analog of ``stall``); ``corrupt`` flips checkpoint page bytes (write)
#: or simulates a failed hash check (load/reshard); ``enospc`` makes a
#: disk-tier page write fail with a NON-transient ``OSError(ENOSPC)``
#: (the bounded IO retry gives up immediately — a full disk does not
#: heal in milliseconds — and the demotion degrades in-memory);
#: ``kill`` SIGKILLs the PROCESS at the site — the chaos-soak harness's
#: hard-crash primitive (the parent reruns the workload with
#: ``CYLON_TPU_RESUME=1``) — and ``term`` delivers SIGTERM to the
#: process at the site: the spot-VM preemption notice (exec/preempt) —
#: with the grace handler armed the process keeps running and DRAINS at
#: its next checkpoint boundary; unarmed, default disposition applies,
#: exactly like a real preemption
KINDS = ("predicted", "device_oom", "capacity", "desync", "stall",
         "spill_stall", "corrupt", "enospc", "kill", "term")


# ---------------------------------------------------------------------------
# classification — the sanctioned string-matching boundary (TS105)
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")


def is_oom(e: Exception) -> bool:
    """Device out-of-memory, as surfaced by XLA/PJRT (either a typed
    taxonomy OOM or a foreign runtime error carrying the XLA text)."""
    if isinstance(e, (PredictedResourceExhausted, DeviceOOMError)):
        return True
    s = str(e)
    return any(m in s for m in _OOM_MARKERS)


def classify(e: Exception) -> CylonError | None:
    """Map an exception onto the typed fault taxonomy.

    Typed faults pass through unchanged.  Foreign exceptions carrying XLA
    OOM text become :class:`PredictedResourceExhausted` (when the message
    says ``(predicted)`` — the pre-allocation guard shape) or
    :class:`DeviceOOMError`, with the original on ``__cause__``.  A
    :class:`CheckpointCorruptError` from a DISK-TIER site (``disk.*``,
    exec/memory) is a fault too: a corrupt spill page's owner has no
    other copy of its data, so the ladder's remedy is ONE recompute of
    the stage at the same streaming configuration (never a wrong
    answer).  Checkpoint-site corruption keeps its existing non-fault
    classification — the pipeline handles it locally (restore degrades
    to recompute of remaining pieces).  Returns ``None`` for everything
    else (not a recovery fault: re-raise it)."""
    if isinstance(e, FAULT_TYPES):
        return e
    if isinstance(e, CheckpointCorruptError) \
            and str(getattr(e, "site", "") or "").startswith("disk."):
        return e
    if isinstance(e, CylonError):
        return None  # typed engine errors (Invalid/Type/...) are not faults
    s = str(e)
    if any(m in s for m in _OOM_MARKERS):
        cls = (PredictedResourceExhausted if "(predicted)" in s
               else DeviceOOMError)
        fault = cls(s)
        fault.__cause__ = e
        return fault
    return None


# ---------------------------------------------------------------------------
# compiler-crash classification
# ---------------------------------------------------------------------------

#: the shape of a compiler-PROCESS death: a helper subprocess's name and
#: the signal.  A kernel Mosaic *refuses* ("Mosaic failed to compile") is
#: an invalid program, not a dead compiler, and is not in this set.
_CRASH_SIGS = ("tpu_compile_helper", "SIGSEGV")


def is_compiler_crash(e: Exception) -> bool:
    """True when the error says the XLA compiler died rather than that the
    program is invalid.  On the supported installation XLA:TPU compiles
    in-process, so such a death takes the process and nothing raises
    this; :func:`_resumable` keeps the classification for a runtime that
    does surface one."""
    s = str(e)
    return any(sig in s for sig in _CRASH_SIGS)


# ---------------------------------------------------------------------------
# serving-session identity (exec/scheduler tags each session's thread)
# ---------------------------------------------------------------------------

def set_session(name: str | None, ordinal: int | None = None) -> None:
    """Tag recovery state on THIS thread with a serving-session identity
    (the scheduler calls this on each session's thread): recorded events
    carry the session name, ``@session``-selective injector specs match
    against it, and the consensus wires ride its namespace.  ``None``
    clears the tag (the default, and the whole-process single-query
    behavior — nothing changes outside a scheduler)."""
    _tls.session = name
    _tls.session_ord = ordinal


def current_session() -> str | None:
    """The serving-session name tagged on this thread, or None."""
    return getattr(_tls, "session", None)


def _session_ns() -> int:
    """Small per-session consensus-wire namespace: 0 with no session
    tagged, else 1 + (ordinal mod 30) — enough to catch ranks voting
    from different sessions without outgrowing the int32 wire."""
    o = getattr(_tls, "session_ord", None)
    return 0 if o is None else 1 + (int(o) % 30)


def events_for_session(name: str) -> list[dict]:
    """Recorded recovery events tagged with serving session ``name`` —
    the per-tenant isolation audit (tests/test_scheduler.py asserts one
    tenant's ladder leaves its neighbors' logs empty)."""
    return [e for e in _EVENTS if e.get("session") == name]


# ---------------------------------------------------------------------------
# fault injection harness
# ---------------------------------------------------------------------------

class _FaultSpec:
    __slots__ = ("site", "rank", "nth", "kind", "session", "fired")

    def __init__(self, site: str, rank, nth, kind: str, session=None):
        self.site = site
        self.rank = rank      # int or None (= every rank)
        self.nth = nth        # int (1-based) or None (= every occurrence)
        self.kind = kind
        self.session = session  # str or None (= any serving session)
        self.fired = False


_FAULTS: list[_FaultSpec] | None = None   # None = parse env on first probe
_HITS: dict = {}    # occurrence counters: site -> n, (site, session) -> n


def _parse_faults(spec: str) -> list[_FaultSpec]:
    out = []
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        lhs, _, kind = entry.partition("=")
        # optional trailing @session selector: the spec fires only on a
        # thread tagged with that serving session (exec/scheduler), and
        # its `nth` counts against THAT session's own probe sequence
        kind, _, session = kind.strip().partition("@")
        session = session.strip() or None
        kind = kind.strip()
        if kind not in KINDS:
            raise ValueError(
                f"CYLON_TPU_FAULTS: unknown kind {kind!r} in {entry!r}; "
                f"kinds: {KINDS}")
        parts = lhs.strip().split(":")
        site = parts[0]
        if site not in SITES:
            raise ValueError(
                f"CYLON_TPU_FAULTS: unknown site {site!r} in {entry!r}; "
                f"sites: {SITES}")
        rank = None
        nth: int | None = 1
        if len(parts) > 1 and parts[1] not in ("", "*"):
            rank = int(parts[1])
        if len(parts) > 2:
            nth = None if parts[2] == "*" else int(parts[2])
        if len(parts) > 3:
            raise ValueError(f"CYLON_TPU_FAULTS: bad entry {entry!r} "
                             "(grammar: site[:rank][:nth]=kind[@session])")
        out.append(_FaultSpec(site, rank, nth, kind, session))
    return out


def install_faults(spec: str | None) -> None:
    """(Re)program the injector: ``spec`` in the env-var grammar, ``""``
    to disarm, ``None`` to re-read ``CYLON_TPU_FAULTS`` from the
    environment.  FULLY resets injector state either way: armed ``nth``
    occurrence counters, one-shot ``fired`` flags AND the recorded
    recovery-event log — so back-to-back chaos-soak iterations (and
    tests) start from a clean slate instead of inheriting the previous
    schedule's hit counts (which would silently shift every ``nth``
    spec by the prior iteration's probe count)."""
    global _FAULTS
    _HITS.clear()
    _EVENTS.clear()
    if spec is None:
        spec = os.environ.get("CYLON_TPU_FAULTS", "")
    _FAULTS = _parse_faults(spec)


def probe(site: str) -> tuple[str | None, bool]:
    """Probe the injector at a named site → ``(kind, armed)``.

    ``kind`` is the fault kind firing on THIS rank at this occurrence
    (consuming one-shot specs), or None.  ``armed`` is True while ANY
    spec could still fire at this site on ANY rank — computed from the
    spec list and the per-site hit counter only, both of which advance
    identically on every rank of an SPMD session (same env var / same
    ``install_faults`` call, probes at the same program points), so
    ``armed`` is rank-UNIFORM even when ``kind`` is rank-selective.
    Guards use it to decide — coherently — whether a consensus poll is
    needed at all.

    ``@session``-selective specs match only on a thread tagged with that
    serving session (:func:`set_session`), and their ``nth`` counts
    against the TARGET session's own probe sequence at the site — a
    co-tenant's interleaved probes never shift the firing point."""
    global _FAULTS
    if _FAULTS is None:
        install_faults(None)
    if not _FAULTS:
        return None, False
    _HITS[site] = hit = _HITS.get(site, 0) + 1
    sess = current_session()
    sess_hit = hit
    if sess is not None:
        skey = (site, sess)
        _HITS[skey] = sess_hit = _HITS.get(skey, 0) + 1
    rank = jax.process_index()

    def _could_fire(f) -> bool:
        """Could this spec still fire at this site on ANY rank?  Must be
        computed from rank-UNIFORM state only — the per-site and
        per-(site, session) hit counters, which advance identically on
        every rank (same program points; scheduled sessions are
        pick-consensus-aligned) — never from the rank-local ``fired``
        flag: a rank+session-selective one-shot flips ``fired`` only on
        the firing rank, and an armed flag keyed on it would diverge
        the guards' consensus-poll gating across ranks."""
        if f.site != site:
            return False
        if f.nth is None:
            return True                      # every-occurrence: always
        if f.session is None:
            return f.nth >= hit              # pre-session semantics
        if f.session == sess:
            return f.nth >= sess_hit         # this probe included
        # another session's spec: its NEXT probe is occurrence +1
        return f.nth >= _HITS.get((site, f.session), 0) + 1

    # armed BEFORE consuming one-shots (the firing probe itself reads
    # as armed, exactly like the pre-session semantics)
    armed = any(_could_fire(f) for f in _FAULTS)
    kind = None
    for f in _FAULTS:
        if f.site != site or f.fired:
            continue
        if f.rank is not None and f.rank != rank:
            continue
        if f.session is not None and f.session != sess:
            continue
        if f.nth is not None and f.nth != (sess_hit if f.session is not None
                                           else hit):
            continue
        f.fired = f.nth is not None
        kind = f.kind
        break
    return kind, armed


def injected(site: str) -> str | None:
    """Probe the injector at a named site: counts the occurrence and
    returns the armed fault kind (consuming one-shot specs), or None."""
    return probe(site)[0]


def faults_declare(site: str) -> bool:
    """True when any installed (or env-declared) spec names ``site`` —
    a STATIC query that consumes no occurrence counter, for facades that
    arm a guarded slow path only while their site could ever fire
    (exec/compiler.armed)."""
    global _FAULTS
    if _FAULTS is None:
        install_faults(None)
    return any(f.site == site for f in _FAULTS)


def make_fault(kind: str, site: str) -> Exception:
    """The typed (or deliberately foreign) exception for an injected
    fault.  ``device_oom`` returns a FOREIGN RuntimeError carrying the
    XLA message shape so the injection also exercises :func:`classify`."""
    if kind == "predicted":
        return PredictedResourceExhausted(
            f"RESOURCE_EXHAUSTED (predicted): injected fault at {site}",
            site=site)
    if kind == "device_oom":
        return RuntimeError(
            f"RESOURCE_EXHAUSTED: injected device OOM at {site}")
    if kind == "capacity":
        return CapacityOverflowError(f"injected capacity overflow at {site}",
                                     site=site)
    if kind == "corrupt":
        return CheckpointCorruptError(
            f"injected checkpoint corruption at {site}", site=site)
    if kind == "enospc":
        return OSError(errno.ENOSPC, f"injected ENOSPC at {site}")
    return RankDesyncError(f"injected rank desync at {site}", site=site,
                           phase=_last_phase())


def hard_kill(site: str) -> None:
    """The ``kill`` fault kind: SIGKILL this process at ``site`` — the
    chaos-soak harness's hard-crash primitive (a libtpu/compiler crash
    takes the process down with no Python unwind; SIGKILL is the honest
    simulation).  The parent harness restarts the workload with
    ``CYLON_TPU_RESUME=1`` against the surviving committed checkpoints."""
    import signal
    from ..utils.logging import log
    log.warning("recovery: injected kill at %s — SIGKILL self", site)
    try:
        # flight-recorder breadcrumb: SIGKILL allows no Python unwind,
        # so the postmortem dump (obs/trace, armed runs only) is written
        # HERE — the one place the process still runs — landing next to
        # the checkpoint manifests like the drain-path dump does
        from ..obs import trace
        trace.postmortem(f"injected kill at {site}")
    except Exception:  # noqa: BLE001 — the kill must proceed regardless
        pass
    os.kill(os.getpid(), signal.SIGKILL)


def soft_term(site: str) -> None:
    """The ``term`` fault kind: deliver SIGTERM to THIS process at
    ``site`` — the spot-VM preemption notice (exec/preempt,
    docs/robustness.md "Elastic resume & preemption grace").  With the
    grace handler armed (``CYLON_TPU_PREEMPT_GRACE_S``) the handler
    only sets a flag and the process drains at its next checkpoint
    boundary; unarmed, the default disposition terminates the process —
    both are exactly what a real preemption does."""
    import signal
    from ..utils.logging import log
    log.warning("recovery: injected preemption notice at %s — SIGTERM self",
                site)
    os.kill(os.getpid(), signal.SIGTERM)


def maybe_inject(site: str, intercept: tuple = ()) -> str | None:
    """Raise the armed fault for ``site`` (no-op when nothing is armed).
    Call at each named injection point.  The ``kill`` kind never raises:
    it SIGKILLs the process.  The ``term`` kind never raises either: it
    delivers SIGTERM (the preemption notice) and execution continues to
    the next checkpoint boundary's drain poll.  Kinds named in
    ``intercept`` are RETURNED for site-specific handling instead of
    recorded-and-raised (the checkpoint sites intercept ``corrupt``: on
    write it flips page bytes after hashing rather than raising)."""
    kind = injected(site)
    if kind is None:
        return None
    if kind == "kill":
        hard_kill(site)
    if kind == "term":
        _record(site, kind, "sigterm")
        soft_term(site)
        return None
    if kind in intercept:
        return kind
    _record(site, kind, "injected")
    raise make_fault(kind, site)


# ---------------------------------------------------------------------------
# recovery-event log
# ---------------------------------------------------------------------------

_EVENTS: list[dict] = []


def _last_phase() -> str:
    from ..utils import timing
    return timing.last_region()


def _record(site: str, kind: str, action: str) -> None:
    from ..utils import timing
    from ..utils.logging import log
    ev = {"site": site, "kind": kind, "action": action}
    sess = current_session()
    if sess is not None:
        # serving sessions get per-tenant audit trails; the key is
        # absent outside a scheduler so single-query logs are unchanged
        ev["session"] = sess
    _EVENTS.append(ev)
    timing.bump(f"recovery.{site}.{kind}.{action}")
    log.warning("recovery: %s fault at %s -> %s", kind, site, action)


def recovery_events() -> list[dict]:
    """Events recorded since the last :func:`reset_events`/:func:`drain_events`
    (each ``{"site", "kind", "action"}``), oldest first."""
    return list(_EVENTS)


def drain_events() -> list[dict]:
    out = list(_EVENTS)
    _EVENTS.clear()
    return out


def reset_events() -> None:
    _EVENTS.clear()


# ---------------------------------------------------------------------------
# SPMD consensus: all-reduce (max) one status code across ranks
# ---------------------------------------------------------------------------

@program_cache()
def _consensus_fn(mesh: Mesh, w: int):
    """One int32 status code per shard → the elementwise pmax, replicated.
    The whole program is one unconditional collective — the minimal
    rank-coherence primitive (docs/robustness.md)."""

    def per_shard(code):
        return jax.lax.pmax(code, ROW_AXIS)

    # pinned: the consensus wire must never be evicted, journaled or
    # fault-injected — it IS the mechanism coordinating those
    from .compiler import jit as _jit
    return _jit(shard_map(per_shard, mesh=mesh, in_specs=(P(ROW_AXIS),),
                          out_specs=P()), pinned=True)


def _consensus_wire(mesh: Mesh | None, wire: int) -> int:
    """Max-reduce one raw int32 across ranks — the transport for both
    :func:`consensus_code` (plain Code) and the ladder's type-carrying
    wire encoding (:func:`_wire_code`).  Single-controller sessions have
    no rank-divergent control flow by construction, so the local value
    IS the consensus; multiprocess sessions run the one-element pmax
    program — every rank must call this at the same point (it is a
    collective), and the result pull runs under the exchange watchdog."""
    if mesh is None or jax.process_count() == 1:
        return int(wire)
    w = int(mesh.devices.size)
    sharding = NamedSharding(mesh, P(ROW_AXIS))
    arr = jax.make_array_from_callback(
        (w,), sharding, lambda idx: np.full((1,), int(wire), np.int32))
    res = _consensus_fn(mesh, w)(arr)
    return exchange_watchdog("exchange.consensus",
                             lambda: int(np.asarray(res)[0]))


def _ns_consensus(mesh: Mesh | None, payload: int, base: int,
                  what: str) -> int:
    """Max-reduce ``payload`` (< ``base``) with the serving-session
    namespace riding ABOVE it: ``wire = ns * base + payload``.  With no
    session tagged (ns = 0, the single-query default) this is exactly
    the plain wire.  In a multiprocess session, an agreed wire whose
    namespace differs from this rank's means a peer entered the poll
    from a DIFFERENT serving session — a scheduler interleave divergence
    — and adopting its payload would hand one tenant another tenant's
    fault, so it raises typed instead (docs/serving.md, recovery
    isolation).

    Detection is deliberately ONE-SIDED: the max-reduce surfaces the
    collision on every rank whose namespace is BELOW the agreed one;
    the highest-namespace rank sees its own ns win and proceeds — until
    its now-aborted peers leave it alone in its next collective, where
    the exchange watchdog converts the hang into the same typed desync.
    A ckpt-commit-style complemented second round would make detection
    symmetric, but would double the consensus cost of EVERY guarded
    operator in multiprocess sessions to harden a divergence the
    scheduler's pick consensus (exec/scheduler._pick) already prevents
    upstream; this layer is defense-in-depth, not the primary fence."""
    ns = _session_ns()
    agreed = _consensus_wire(mesh, ns * base + int(payload))
    _trace.instant("consensus." + what, wire=int(agreed))
    if agreed // base != ns:
        raise RankDesyncError(
            f"cross-session consensus collision at {what}: this rank "
            f"voted in session namespace {ns}, the agreed wire is from "
            f"namespace {agreed // base} — ranks are interleaving "
            "different serving sessions", site=what, phase=_last_phase())
    return agreed % base


def consensus_code(mesh: Mesh | None, code: Code | int) -> Code:
    """The agreed (max) status code across every rank of the session.
    Session-namespaced: concurrent serving sessions' polls can never
    silently satisfy each other (:func:`_ns_consensus`)."""
    return Code(_ns_consensus(mesh, int(Code(int(code))), 64,
                              "exchange.consensus"))


def _wire_code(fault: CylonError | None) -> int:
    """Ladder consensus encoding: ``Code*4 + sub`` where the predicted
    OOM shape sorts BELOW a real device OOM within the same Code.  The
    max then agrees not just on the retry rung but on the fault TYPE
    every rank must raise on abort — callers above the ladder (a
    driver's loop) dispatch on the class, and a rank aborting with
    `predicted` while a peer aborts with `device_oom` would take
    divergent abort-vs-retry branches."""
    if fault is None:
        return 0
    sub = 0 if isinstance(fault, PredictedResourceExhausted) else 1
    return int(fault.code) * 4 + sub


def _unwire(wire: int) -> Code:
    return Code(int(wire) // 4)


def _fault_from_wire(wire: int, msg: str) -> CylonError:
    """The typed taxonomy fault every rank must raise for an agreed wire
    value — identical class on every rank by construction."""
    code = _unwire(wire)
    if code == Code.OutOfMemory:
        return (PredictedResourceExhausted(msg) if wire % 4 == 0
                else DeviceOOMError(msg))
    if code == Code.CapacityError:
        return CapacityOverflowError(msg)
    if code == Code.SerializationError:
        # a peer's disk-tier spill page failed verification: every rank
        # takes the identical recompute rung (the corrupt owner's data
        # exists nowhere else — recompute, never a wrong answer)
        return CheckpointCorruptError(msg, site="disk.read")
    if code == Code.IntegrityFault:
        # a peer's conservation law or armed fingerprint failed: every
        # rank takes the identical one-recompute rung (silent corruption
        # degrades to recompute, never to a wrong answer)
        return DataIntegrityError(msg, site="audit.verify",
                                  phase=_last_phase())
    return RankDesyncError(msg, phase=_last_phase())


def guard_consensus(mesh: Mesh | None, local_fault: bool) -> bool:
    """Pre-collective raise/proceed agreement for capacity guards: True
    when ANY rank's guard fired — then every rank raises the identical
    typed fault BEFORE the exchange's first collective is dispatched (the
    rank-coherence invariant).  Runs unconditionally on every rank in a
    multiprocess session (it is itself a tiny collective)."""
    local = Code.OutOfMemory if local_fault else Code.OK
    return consensus_code(mesh, local) != Code.OK


def spill_consensus(mesh: Mesh | None, local_need: bool) -> bool:
    """Evict/re-admit agreement for the spill tier (exec/memory): True
    when ANY rank is under memory pressure — then every rank runs the
    identical deterministic LRU eviction, because a rank-local eviction
    would desync the next collective exactly like a rank-local retry
    (docs/robustness.md).  Rides the same one-int32 pmax wire as the
    fault codes, with the dedicated :class:`Code.SpillRequired` vote.
    Callers poll only when the pressure predicate or an armed injector
    can be non-OK somewhere — the under-budget happy path stays
    collective-free."""
    local = Code.SpillRequired if local_need else Code.OK
    return consensus_code(mesh, local) == Code.SpillRequired


def drain_consensus(mesh: Mesh | None, local_flag: bool) -> bool:
    """Preemption-grace drain agreement (exec/preempt → exec/checkpoint
    ``drain_requested``): True when ANY rank has received a SIGTERM
    preemption notice — then every rank flushes, commits and raises the
    identical typed ``ResumableAbort`` at the SAME checkpoint boundary.
    A rank draining alone would leave its peers hanging in the next
    piece's commit collective, which is the desync this module exists
    to prevent.  Rides the same one-int32 pmax wire as the fault codes
    with the dedicated :class:`Code.PreemptDrain` vote,
    session-namespaced like every other wire.  Polled ONLY at the
    checkpoint boundaries of sessions with BOTH the grace budget and
    durable checkpointing armed — unarmed sessions stay collective-free
    (one env read per boundary)."""
    local = Code.PreemptDrain if local_flag else Code.OK
    return consensus_code(mesh, local) == Code.PreemptDrain


def preempt_consensus(mesh: Mesh | None, victim_plus1: int) -> int:
    """Preempt-DECISION agreement (exec/scheduler._maybe_preempt): every
    rank votes its locally chosen victim as ``ordinal + 1`` (0 = no
    eligible victim) and the max wins, so either every rank flags the
    SAME running tenant for a boundary drain or none does.  Policy
    inputs like fair-share clocks are wall time and not rank-uniform —
    without the vote one rank could drain tenant A while its peers keep
    granting it, leaving them alone in A's next collective.  Rides the
    count transport (one-int32 pmax, session-namespaced) under its own
    site label; entered only when the preemptive preconditions (policy,
    checkpointing armed, candidate blocked) hold — all rank-uniform —
    so the happy path stays collective-free."""
    return int(_ns_consensus(
        mesh, min(max(int(victim_plus1), 0), (1 << 20) - 1),
        1 << 20, "sched.preempt"))


def count_consensus(mesh: Mesh | None, n: int) -> int:
    """Max-agree a small non-negative count across ranks — the spill
    tier's eviction-COUNT wire (exec/memory.ensure_headroom) and the
    scheduler's pick-agreement wire: every rank then takes the identical
    action, so the eviction sequence is identical even when a straggling
    GC leaves one rank's balance momentarily higher.  Same transport as
    the ladder's code wire, session-namespaced like it."""
    return int(_ns_consensus(mesh, min(max(int(n), 0), (1 << 20) - 1),
                             1 << 20, "exchange.count"))


#: epoch field width of the checkpoint-commit wire (epochs are per-stage
#: piece counters, far below this; the vote code rides above it)
_CKPT_EPOCH_BASE = 1 << 20

#: session-namespace base for the checkpoint wires: the payload
#: (CkptCommit * 2^20 + epoch ≈ 50.3M max) fits under 2^26, and the
#: namespace (≤ 30) on top stays inside the int32 pmax transport
#: (30 * 2^26 + 50.3M ≈ 2.064e9 < 2^31)
_CKPT_NS_BASE = 1 << 26


def ckpt_commit_consensus(mesh: Mesh | None, epoch: int) -> int:
    """Phase 2 of the durable checkpoint's two-phase manifest commit
    (exec/checkpoint): every rank has already STAGED its manifest (phase
    1, a rank-local atomic write) and now votes :class:`Code.CkptCommit`
    with its staged epoch riding the same one-int32 pmax wire as the
    fault codes.  Only after the votes agree does any rank rename
    staged → committed, so a manifest is either committed on EVERY rank
    at the identical epoch or on none — a crash between stage and commit
    leaves only staged files, which resume ignores.  A diverging epoch
    is a structural desync (ranks checkpointing different pieces) and
    raises typed rather than committing torn state.  The wires are
    session-namespaced like every other consensus (:func:`_ns_consensus`
    at :data:`_CKPT_NS_BASE`): two serving tenants' stages commonly sit
    at EQUAL epoch numbers, so without the namespace a rank-schedule
    divergence could durably commit one tenant's manifest against
    another tenant's vote.

    Like the resume vote, this runs over the LIVE mesh only.  After an
    elastic re-shard the first post-reshard commit re-votes the epoch
    over the NEW mesh — stale rank dirs from the old world never
    participate (they are directories, not voters) and are superseded
    by the rewrite's higher manifest generation (exec/checkpoint)."""
    epoch = int(epoch)
    if not 0 <= epoch < _CKPT_EPOCH_BASE:
        raise ValueError(f"checkpoint epoch {epoch} out of wire range")
    if mesh is None or jax.process_count() == 1:
        return epoch
    # two rounds: a max-reduce alone cannot surface divergence to the
    # rank HOLDING the max (its own vote IS the max), so the epoch also
    # rides the wire complemented — max of the complement is the
    # complement of the MIN — and every rank compares both extremes
    # against its own stage before renaming anything
    wire = int(Code.CkptCommit) * _CKPT_EPOCH_BASE + epoch
    agreed = _ns_consensus(mesh, wire, _CKPT_NS_BASE, "ckpt.commit")
    inv = _ns_consensus(mesh, int(Code.CkptCommit) * _CKPT_EPOCH_BASE
                        + (_CKPT_EPOCH_BASE - 1 - epoch),
                        _CKPT_NS_BASE, "ckpt.commit")
    lo = _CKPT_EPOCH_BASE - 1 - (inv % _CKPT_EPOCH_BASE)
    if agreed != wire or lo != epoch:
        raise RankDesyncError(
            f"checkpoint commit diverged: this rank staged epoch {epoch}, "
            f"consensus saw [{lo}, {agreed % _CKPT_EPOCH_BASE}] — ranks "
            "are checkpointing different pieces", site="ckpt.commit",
            phase=_last_phase())
    return epoch


def watermark_consensus(mesh: Mesh | None, n: int) -> int:
    """Min-agree the streaming watermark across ranks (the event-time
    window-close vote, :mod:`cylon_tpu.stream.window`).  ``n`` is this
    rank's CLOSABLE-WINDOW count — the number of tumbling windows whose
    end its local (monotone, per-rank) watermark has passed; window
    ordinals stay far below the wire width, unlike raw int64 event-time
    nanoseconds.  Every rank then closes exactly the agreed MINIMUM — a
    rank that has not yet seen events past a window's end holds the
    whole session's close back, because closing rank-locally would emit
    (and evict) different window state per rank, the desync this module
    exists to prevent.  Rides the pmax transport complemented (max of
    the complement = complement of the min — the ckpt-resume trick) and
    is session-namespaced like every other wire, so a streaming tenant's
    vote can never satisfy another tenant's poll."""
    n = int(n)
    if not 0 <= n < _CKPT_EPOCH_BASE:
        raise ValueError(f"watermark window count {n} out of wire range")
    if mesh is None or jax.process_count() == 1:
        return n
    wire = _CKPT_EPOCH_BASE - 1 - n
    return _CKPT_EPOCH_BASE - 1 - (
        _ns_consensus(mesh, wire, 1 << 20, "stream.watermark")
        % _CKPT_EPOCH_BASE)


def _plan_hash_consensus(mesh: Mesh | None, code: Code, plan_hash: int,
                         site: str, what: str) -> None:
    """Adopt-one-plan agreement shared by the skew-split and topology
    routes: every rank votes ``code`` with two 20-bit slices of the
    canonical plan hash riding the pmax wire — EACH slice in both
    polarities (plain, then complemented), four rounds total, so a rank
    passes a slice's pair only when its value equals both the max AND
    the min across the mesh: any divergence in either slice raises on
    EVERY rank, exactly like the checkpoint-commit vote.  A diverging
    hash is a structural desync — ranks about to enter DIFFERENT
    exchange plans (different collective sequences) — and raises typed
    BEFORE the plan's first collective is dispatched, the
    rank-coherence invariant this module exists for."""
    lo20 = int(plan_hash) & ((1 << 20) - 1)
    hi20 = (int(plan_hash) >> 20) & ((1 << 20) - 1)
    if mesh is None or jax.process_count() == 1:
        return
    base = int(code) * _CKPT_EPOCH_BASE
    for label, slice20 in (("lo", lo20), ("hi", hi20)):
        for complemented in (False, True):
            v = (_CKPT_EPOCH_BASE - 1 - slice20) if complemented \
                else slice20
            wire = base + v
            agreed = _ns_consensus(mesh, wire, _CKPT_NS_BASE, site)
            if agreed != wire:
                peer = agreed % _CKPT_EPOCH_BASE
                if complemented:
                    peer = _CKPT_EPOCH_BASE - 1 - peer
                raise RankDesyncError(
                    f"{what} vote diverged: this rank computed plan "
                    f"hash slice {label}={slice20:#x}, consensus saw "
                    f"{peer:#x} — ranks are about to enter different "
                    f"exchange plans", site=site, phase=_last_phase())


def skew_plan_consensus(mesh: Mesh | None, plan_hash: int) -> None:
    """Adopt-one-plan agreement for the adaptive skew-split route
    (relational/skew.py, docs/skew.md): every rank computes the plan —
    heavy-key set, contiguous rank groups, salted fan-out chunk bounds —
    from the SAME allgathered sample + count sidecars, then votes
    :class:`Code.SkewPlan` over the four-round double-polarity hash
    wire (:func:`_plan_hash_consensus`).  The recovery ladder's retries
    re-detect and re-vote: determinism of the detection inputs makes
    the re-voted hash identical, which chaos_soak's ``--skew``
    schedules assert.

    Polled ONLY when a non-empty plan was decided (plan-armed joins) —
    the plan decision itself is rank-uniform by construction
    (``host_array`` allgathers the sample), so the unarmed / no-heavy-key
    path stays collective-free (the bench's zero-extra-collectives
    contract at skew 0)."""
    _plan_hash_consensus(mesh, Code.SkewPlan, plan_hash, "skew.plan",
                         "skew-plan")


def topo_plan_consensus(mesh: Mesh | None, plan_hash: int) -> None:
    """Adopt-one-plan agreement for the multi-slice topology route
    (cylon_tpu/topo — the TS116 facade is the only sanctioned caller;
    docs/topology.md): every rank derives the topology plan — slice
    map, flat/hierarchical route, gateway scheme — from the SAME device
    attributes / ``CYLON_TPU_SLICES`` declaration, then votes
    :class:`Code.TopoPlan` over the four-round double-polarity hash
    wire (:func:`_plan_hash_consensus`) BEFORE the first hierarchical
    collective, so recovery ladders, checkpoints and elastic resume
    (slice loss → re-shard onto the surviving world, which re-votes the
    NEW topology) all adopt one plan.  Voted once per (mesh, plan) —
    single-slice sessions never reach it (zero collectives on the flat
    route, the chaos ``--multislice`` unarmed-leg contract)."""
    _plan_hash_consensus(mesh, Code.TopoPlan, plan_hash, "topo.plan",
                         "topology-plan")


def fingerprint_consensus(mesh: Mesh | None, fp: int) -> None:
    """Rank-coherent verification of an order-invariant content
    fingerprint (exec/integrity — the TS118 facade is the only
    sanctioned caller; docs/robustness.md "Integrity audit tier"):
    every rank computes the REPLICATED 64-bit mesh fingerprint for the
    same stage boundary and votes :class:`Code.IntegrityFault` with two
    20-bit slices of it over the four-round double-polarity hash wire
    (:func:`_plan_hash_consensus`), so a rank whose device delivered
    different bytes raises typed BEFORE anyone commits the stage —
    identically on every rank, exactly like a plan vote.  Polled only
    under ``CYLON_TPU_AUDIT=1`` in multiprocess sessions: the unarmed
    path (and any single-controller session, where the replicated
    fingerprint is trivially coherent) stays collective-free."""
    _plan_hash_consensus(mesh, Code.IntegrityFault, fp, "audit.verify",
                         "fingerprint-audit")


def ckpt_resume_consensus(mesh: Mesh | None, n: int) -> int:
    """Min-agree the resume fast-forward count (exec/pipeline): each
    rank votes how many committed pieces IT restored and verified, and
    every rank fast-forwards exactly the MINIMUM — a rank whose page
    failed its content-hash check (rank-local disk corruption) degrades
    the whole session's fast-forward coherently, because a rank-local
    fallback would leave the recomputing rank alone in the per-piece
    commit collectives.  The count rides the wire complemented so the
    pmax transport yields the min; adopting the min needs no divergence
    check (divergence IS the input here, and min is the agreement) —
    but the wire IS session-namespaced, so a vote arriving from another
    serving tenant's resume surfaces typed instead of silently clamping
    this tenant's fast-forward.

    The vote is over the LIVE mesh, never over checkpoint rank
    directories: an elastic resume (docs/robustness.md "Elastic resume
    & preemption grace") commonly has rank dirs OUTNUMBERING live ranks
    (world shrank — every live rank reads all N foreign dirs and votes
    the count it could verify) or UNDERNUMBERING them (world grew — a
    live rank with no own-rank dir simply votes what the foreign scan
    yielded, 0 if the checkpoint root is not shared).  Either way the
    min over live ranks is well-defined, and for an all-or-nothing
    re-shard adoption the caller compares the agreed min against its
    own count and discards EVERYTHING on any shortfall (old-layout
    pieces cannot partially splice into a new-layout loop)."""
    n = int(n)
    if not 0 <= n < _CKPT_EPOCH_BASE:
        raise ValueError(f"resume fast-forward count {n} out of wire range")
    if mesh is None or jax.process_count() == 1:
        return n
    wire = (int(Code.CkptCommit) * _CKPT_EPOCH_BASE
            + (_CKPT_EPOCH_BASE - 1 - n))
    return _CKPT_EPOCH_BASE - 1 - (
        _ns_consensus(mesh, wire, _CKPT_NS_BASE, "ckpt.resume")
        % _CKPT_EPOCH_BASE)


# ---------------------------------------------------------------------------
# exchange watchdog
# ---------------------------------------------------------------------------

def exchange_watchdog(site: str, thunk, timeout_s: float | None = None,
                      stalled: bool | None = None):
    """Run a blocking exchange host-sync under an optional deadline.

    With ``CYLON_TPU_WATCHDOG_S`` unset/0 this is a plain call.  With a
    deadline, the sync runs in a worker thread; if it does not complete in
    time the hang is converted into a typed :class:`RankDesyncError`
    carrying the site and the last-known timing phase.  The injector kind
    ``stall`` (site ``exchange.stall``) simulates the peer hang;
    ``stalled=True`` forces the simulated hang directly (the spill tier
    routes its site-local ``spill_stall`` injections through this — a
    hung host↔device transfer then surfaces typed at ``spill.evict`` /
    ``spill.upload`` instead of silently blocking)."""
    t = config.EXCHANGE_WATCHDOG_S if timeout_s is None else float(timeout_s)
    if t <= 0:
        return thunk()
    if stalled is None:
        stalled = injected("exchange.stall")
    box: dict = {}

    def run():
        if stalled:
            # simulated peer hang: the data never arrives
            import time
            time.sleep(4 * t)
            return
        try:
            box["value"] = thunk()
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["error"] = e

    th = threading.Thread(target=run, daemon=True,
                          name=f"cylon-watchdog-{site}")
    th.start()
    th.join(t)
    if "error" in box:
        raise box["error"]
    if "value" not in box:
        _record(site, "desync", "watchdog")
        raise RankDesyncError(
            f"exchange watchdog: no progress at {site} within {t:g}s — a "
            "peer rank hung in (or never entered) the exchange",
            site=site, phase=_last_phase())
    return box["value"]


# ---------------------------------------------------------------------------
# bounded IO retry — the shared transient-OSError backoff helper
# ---------------------------------------------------------------------------

#: registry counter: transient-OSError retries taken by retry_io across
#: every adopter (checkpoint page/manifest writes, disk-tier spill pages)
from ..obs import metrics as _obs_metrics  # noqa: E402

_IO_RETRIES = _obs_metrics.counter(
    "recovery_io_retries",
    help="transient-OSError retries taken by the bounded IO backoff")

#: errno values retry_io treats as NON-transient: a full disk (or quota)
#: does not heal on a millisecond backoff — the caller's typed degrade
#: path owns those, not the retry loop
_NON_TRANSIENT_ERRNOS = frozenset(
    getattr(errno, name)
    for name in ("ENOSPC", "EDQUOT", "EROFS", "ENOENT", "EISDIR")
    if hasattr(errno, name))


def retry_io(fn, site: str, attempts: int = 3, base_delay_s: float = 0.05,
             on_retry=None):
    """Run a filesystem thunk with a SMALL bounded exponential-backoff
    retry on transient ``OSError`` — the shared-storage-blip helper
    (docs/robustness.md "Disk tier & scan pushdown"): a single NFS hiccup
    during a GKE drain used to abort a checkpoint commit that a
    3-attempt backoff saves.  Bounded by construction: at most
    ``attempts`` calls, delays ``base * 2^i`` (≈0.15 s total at the
    defaults) — never an unbounded loop.  Non-transient errnos (ENOSPC,
    EDQUOT, EROFS, ENOENT, EISDIR) re-raise IMMEDIATELY: the caller's
    typed degrade/classification path owns those.  Non-OSError
    exceptions propagate untouched.  ``on_retry`` (optional thunk) runs
    once per retry — adopters bump their own counters through it; the
    shared ``recovery_io_retries`` registry counter and a
    ``io_retry.<site>`` timing bump always fire."""
    import time as _time
    last: OSError | None = None
    for i in range(max(int(attempts), 1)):
        if i:
            from ..utils import timing
            from ..utils.logging import log
            _IO_RETRIES.inc()
            timing.bump(f"io_retry.{site}")
            if on_retry is not None:
                on_retry()
            log.warning("%s: transient OSError (%s); retry %d/%d after "
                        "%.3fs backoff", site, last, i, attempts - 1,
                        base_delay_s * (2 ** (i - 1)))
            _time.sleep(base_delay_s * (2 ** (i - 1)))
        try:
            return fn()
        except OSError as e:
            if e.errno in _NON_TRANSIENT_ERRNOS:
                raise
            last = e
    raise last


# ---------------------------------------------------------------------------
# the rank-coherent retry ladder
# ---------------------------------------------------------------------------

#: bounded deterministic escalation per agreed fault code: device/predicted
#: OOM retries the streaming fallback at growing chunk counts; a capacity
#: overflow takes exactly one cap-halving step (pieces are ~1/n_chunks
#: sized, so 8 chunks halves the 4-chunk default's piece cap); a DISK-TIER
#: corruption (Code.SerializationError from a ``disk.*`` site — a spill
#: page failed its sha check, so that owner's data exists nowhere else)
#: takes exactly one recompute of the stage at the base streaming
#: configuration — corruption degrades to recompute, never a wrong answer;
#: an INTEGRITY fault (Code.IntegrityFault — a conservation law or armed
#: content fingerprint caught data in flight being mutated) mirrors the
#: disk-corruption rung exactly: ONE recompute of the stage at the base
#: streaming configuration, then a typed abort on repeat
RETRY_RUNGS = {Code.OutOfMemory: (4, 16), Code.CapacityError: (8,),
               Code.SerializationError: (4,),
               Code.IntegrityFault: (4,)}

_tls = threading.local()


def _resumable(exc, label: str):
    """The ladder's FINAL rung (docs/robustness.md "Durable checkpoints
    & resume"): when durable checkpointing is armed
    (``CYLON_TPU_CKPT_DIR``) and the fault is one no in-process rung can
    cure — a real :class:`DeviceOOMError` (HBM may be poisoned) or an
    error that reports the compiler's death — flush the checkpoint session and
    convert into a typed :class:`ResumableAbort` carrying the resume
    token, so a supervisor can relaunch with ``CYLON_TPU_RESUME=1`` and
    fast-forward past every committed piece.  Anything else (or with
    checkpointing unarmed) returns the input unchanged."""
    from . import checkpoint
    if not checkpoint.enabled():
        return exc
    if not (isinstance(exc, DeviceOOMError) or is_compiler_crash(exc)):
        return exc
    token = checkpoint.flush_for_abort(label)
    kind = getattr(exc, "kind", "compiler_crash")
    _record(label, kind, "resumable_abort")
    ra = ResumableAbort(
        f"{label}: unrecoverable {kind} fault with durable checkpoints "
        f"armed — committed piece state flushed; rerun the same workload "
        f"in a FRESH process with CYLON_TPU_RESUME=1 to fast-forward past "
        f"committed pieces (resume token: {token})", token=token)
    ra.__cause__ = exc
    return ra


def _attempt(fn, label: str = ""):
    """(result, fault) — non-fault exceptions propagate (a reported
    compiler crash takes the FINAL checkpoint rung on the way out when
    one is armed)."""
    try:
        return fn(), None
    except Exception as e:  # noqa: BLE001 — classify filters
        fault = classify(e)
        if fault is None:
            exc = _resumable(e, label)
            if exc is e:
                raise
            raise exc
        return None, fault


def run_with_recovery(primary, can_fallback: bool, fallback, label: str,
                      env=None):
    """``primary()`` under the consensus retry ladder: classify any fault,
    agree on ONE status code across ranks, and either return, retry
    ``fallback(n_chunks)`` on the deterministic rung schedule
    (:data:`RETRY_RUNGS`), or raise the typed fault — identically on every
    rank.  ``env`` (a CylonEnv) supplies the mesh for the consensus
    all-reduce; without it (or single-process) consensus is local.

    Nested invocations (a fallback re-entering a guarded operator) never
    re-escalate: the outer ladder owns the rung schedule, so the total
    number of retries stays bounded.

    Protocol cost, stated plainly: in a MULTIPROCESS session every
    guarded operator call ends in one tiny pmax + host pull even on the
    happy path — that pull drains previously dispatched device work, so
    cross-operator dispatch overlap (deferred counts) is traded for the
    guarantee that a rank-local fault on any peer is seen by every rank
    before anyone commits to a result.  Single-controller sessions (the
    benched configurations) skip consensus entirely and keep full
    overlap."""
    mesh = getattr(env, "mesh", None)
    multi = mesh is not None and jax.process_count() > 1
    nested = getattr(_tls, "depth", 0) > 0

    def agree(fault):
        """(agreed Code, rank-coherent fault|None): consensus over the
        wire encoding, so ranks agree on the fault TYPE, not just the
        rung — a rank whose local fault differs from (or lacks) the
        agreed one adopts a synthesized fault of the agreed class
        (classify() passes typed faults through, keeping ENCLOSING
        ladders and type-dispatching callers coherent too).  The wire is
        session-namespaced (_ns_consensus): one serving session's ladder
        can never adopt a fault a peer rank voted from ANOTHER session's
        ladder."""
        wire = _wire_code(fault)
        agreed_w = _ns_consensus(mesh, wire, 1024, label) if multi else wire
        if agreed_w == 0:
            return Code.OK, None
        if fault is None or _wire_code(fault) != agreed_w:
            fault = _fault_from_wire(
                agreed_w, f"peer rank fault during {label} "
                          f"(consensus {_unwire(agreed_w).name})")
        return _unwire(agreed_w), fault

    result, fault = _attempt(primary, label)
    agreed, fault = agree(fault)
    if agreed == Code.OK:
        return result
    kind = getattr(fault, "kind", "fault")

    # ---- spill rung: free resident bytes, retry the SAME configuration --
    # A predicted fault fired BEFORE any allocation (HBM clean), so if the
    # host spill tier can free resident bytes, the cheapest recovery is to
    # evict and re-run at the same chunk count — no completed device work
    # is discarded (exec/memory, docs/robustness.md).  Rank-coherent by
    # construction: the fault TYPE is post-consensus (the wire encoding
    # separates predicted from device OOM), and spill_for_retry's eviction
    # set/order is a pure function of the rank-uniform ledger.  Chunk
    # escalation below remains the backstop when spilling is insufficient
    # (or there is nothing to spill).
    if not nested and isinstance(fault, PredictedResourceExhausted):
        from . import memory
        # the TAKE-THE-RUNG decision is agreed, not balance-gated: a
        # straggling GC could leave spillable bytes visible on one rank
        # only, and a rank retrying while its peers escalate is the
        # desync this module exists to prevent.  (The gate itself runs
        # on every rank: fault type and nesting depth are uniform.)
        local_can = config.SPILL_ENABLED and memory.spillable_bytes() > 0
        do_spill = spill_consensus(mesh, local_can) if multi else local_can
        if do_spill:
            # eviction goes through the scheduler facade (TS109): the
            # serving tier is the one sanctioned admission/eviction
            # mediator, so even the ladder's rung stays attributable
            from . import scheduler
            scheduler.spill_retry()
            from ..utils.logging import log as _log
            _record(label, kind, "spill_retry")
            _log.warning("%s %s fault; spill rung: resident state evicted "
                         "to host, retrying at the same configuration",
                         label, kind)
            _tls.depth = getattr(_tls, "depth", 0) + 1
            try:
                result, fault = _attempt(primary, label)
            finally:
                _tls.depth -= 1
            agreed, fault = agree(fault)
            if agreed == Code.OK:
                return result
            kind = getattr(fault, "kind", kind)

    rungs = RETRY_RUNGS.get(agreed, ())
    if not rungs or not can_fallback or nested:
        _record(label, kind, "abort")
        raise _resumable(fault, label)

    from ..utils.logging import log
    last = fault
    _tls.depth = getattr(_tls, "depth", 0) + 1
    try:
        for nc in rungs:
            _record(label, kind, f"retry_chunks_{nc}")
            log.warning("%s %s fault (%s); rank-coherent retry via "
                        "streaming fallback with %d chunks", label, kind,
                        type(last).__name__, nc)
            result, fault = _attempt(lambda: fallback(nc), label)
            agreed, fault = agree(fault)
            if agreed == Code.OK:
                return result
            last, kind = fault, getattr(fault, "kind", kind)
            if agreed not in RETRY_RUNGS:
                break
    finally:
        _tls.depth -= 1
    _record(label, kind, "abort")
    raise _resumable(last, label)


# ---------------------------------------------------------------------------
# trace-safety declaration (cylon_tpu.analysis.registry): the consensus
# program is ONE unconditional pmax — the jaxpr pass verifies exactly that
# (a conditional consensus would be the deadlock it exists to prevent).
# ---------------------------------------------------------------------------

def _trace_consensus(mesh):
    w = int(mesh.devices.size)
    fn = _unwrap(_consensus_fn(mesh, w))
    return jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((w,), np.int32))


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._consensus_fn", _trace_consensus,
                collectives={"pmax"}, tags=("recovery",))
