"""Status / error model.

TPU-native equivalent of the reference's C++ ``Status``/``Code`` pair
(reference: cpp/src/cylon/status.hpp:65, cpp/src/cylon/code.hpp:19).  The
reference threads a ``Status{code, msg}`` through every call; in Python the
idiomatic carrier is an exception hierarchy, but we keep the same code
vocabulary so bindings and tests can assert on error categories.
"""

from __future__ import annotations

import enum


class Code(enum.IntEnum):
    """Error codes mirroring reference cpp/src/cylon/code.hpp:19-40."""

    OK = 0
    OutOfMemory = 1
    KeyError = 2
    TypeError = 3
    Invalid = 4
    IOError = 5
    CapacityError = 6
    IndexError = 7
    UnknownError = 9
    NotImplemented = 10
    SerializationError = 11
    RError = 12
    #: spill-tier consensus vote (exec/memory): a rank under memory
    #: pressure requests a COLLECTIVE eviction; rides the same pmax wire
    #: as the fault codes (docs/robustness.md, "why eviction is
    #: collective").  Not an error class — never raised.
    SpillRequired = 46
    #: durable-checkpoint two-phase commit vote (exec/checkpoint): every
    #: rank has STAGED its manifest and votes this code with the staged
    #: epoch riding the same pmax wire, so a manifest is committed on
    #: every rank at the identical epoch or on none.  Not an error class
    #: — never raised.
    CkptCommit = 47
    #: preemption-grace drain vote (exec/preempt + exec/checkpoint): a
    #: rank that received SIGTERM with the grace budget armed requests a
    #: COLLECTIVE drain at the next checkpoint boundary, so every rank
    #: commits the same prefix and raises the same typed ResumableAbort
    #: instead of one rank draining while its peers enter the next
    #: collective alone.  Not an error class — never raised.
    PreemptDrain = 48
    #: skew-plan adoption vote (exec/recovery.skew_plan_consensus +
    #: relational/skew.py): every rank has computed the adaptive
    #: skew-split plan (heavy-key set, rank groups, salted fan-out) from
    #: the allgathered sample and votes this code with two 20-bit slices
    #: of the plan hash riding the pmax wire, so the recovery ladder,
    #: checkpoints and elastic resume all see ONE plan — a rank whose
    #: hash diverges raises typed instead of entering the split
    #: exchange's collectives alone.  Not an error class — never raised.
    SkewPlan = 49
    #: topology-plan adoption vote (exec/recovery.topo_plan_consensus +
    #: cylon_tpu/topo): every rank has derived the multi-slice topology
    #: plan (slice map, route choice, gateway scheme) from the same
    #: device attributes / CYLON_TPU_SLICES declaration and votes this
    #: code with two 20-bit slices of the canonical plan hash riding the
    #: pmax wire BEFORE the first hierarchical collective, so recovery
    #: ladders, checkpoints and elastic resume all adopt ONE topology —
    #: a rank whose slice map diverges raises typed instead of entering
    #: a two-hop exchange its peers route differently.  Not an error
    #: class — never raised.
    TopoPlan = 50
    #: data-integrity audit fault (exec/integrity + exec/recovery): a
    #: conservation law or an armed content fingerprint failed — bytes
    #: in flight were lost, duplicated or mutated.  Raised as
    #: :class:`DataIntegrityError` and retried ONCE by the ladder's
    #: recompute rung (mirroring the disk-corruption rung: corruption
    #: degrades to recompute, never to a wrong answer); the fingerprint
    #: verdict itself rides the double-polarity plan-hash wire with this
    #: code so every rank agrees on the failing site before anyone
    #: raises.  Must stay < 64: the wire packs ``code*4+sub`` under the
    #: ladder's 1024 base and ``code << 20`` under the checkpoint
    #: namespace base.
    IntegrityFault = 51
    CodeGenError = 40
    ExpressionValidationError = 41
    ExecutionError = 42
    AlreadyExists = 45


class CylonError(Exception):
    """Base error carrying a :class:`Code`."""

    code: Code = Code.UnknownError

    def __init__(self, msg: str = "", code: Code | None = None):
        super().__init__(msg)
        if code is not None:
            self.code = code

    @property
    def msg(self) -> str:
        return str(self)


class InvalidError(CylonError):
    code = Code.Invalid


# ---------------------------------------------------------------------------
# Fault taxonomy (docs/robustness.md).  Every recoverable capacity/comms
# failure in the engine is one of these four types; the consensus retry
# ladder (cylon_tpu.exec.recovery) dispatches on them, and string-matching
# XLA messages outside recovery.py is a lint finding (TS105).  Each class
# carries a short ``kind`` tag used by recovery-event logs and the
# fault-injection grammar.
# ---------------------------------------------------------------------------

class PredictedResourceExhausted(CylonError, MemoryError):
    """A capacity guard fired BEFORE any device allocation (e.g. the
    exchange receive-budget guard, parallel/shuffle.py): HBM is NOT
    poisoned, so an in-process retry at a degraded configuration is safe.
    Subclasses MemoryError and keeps ``RESOURCE_EXHAUSTED (predicted)`` in
    the message so pre-taxonomy callers keep classifying it as OOM."""

    code = Code.OutOfMemory
    kind = "predicted"

    def __init__(self, msg: str = "", site: str | None = None):
        super().__init__(msg)
        self.site = site


class DeviceOOMError(CylonError):
    """A real XLA/PJRT RESOURCE_EXHAUSTED surfaced by the runtime: device
    memory was actually exhausted (and on some rigs the process's HBM is
    poisoned).  Foreign runtime errors are wrapped into this type by
    ``cylon_tpu.exec.recovery.classify`` (the one sanctioned
    string-matching site); the original exception rides ``__cause__``."""

    code = Code.OutOfMemory
    kind = "device_oom"

    def __init__(self, msg: str = "", site: str | None = None):
        super().__init__(msg)
        self.site = site


class CapacityOverflowError(CylonError):
    """A pow2-bucketed static capacity (piece cap, output cap) was
    exceeded by the actual row counts — the planned shape family cannot
    hold the data; the remedy is a deterministic re-plan at a smaller
    piece size (cap halving), not a memory retry."""

    code = Code.CapacityError
    kind = "capacity"

    def __init__(self, msg: str = "", site: str | None = None):
        super().__init__(msg)
        self.site = site


class RankDesyncError(CylonError):
    """Ranks stopped advancing together: a peer hung in (or never
    entered) a collective, detected by the exchange watchdog, or a
    consensus poll disagreed structurally.  Carries the site and the
    last-known timing phase for postmortems."""

    code = Code.ExecutionError
    kind = "desync"

    def __init__(self, msg: str = "", site: str | None = None,
                 phase: str | None = None):
        super().__init__(msg)
        self.site = site
        self.phase = phase


class DataIntegrityError(CylonError):
    """The integrity audit tier (exec/integrity) caught data in flight
    being lost, duplicated or mutated: a conservation law over the
    exchange count sidecar failed (always-on, pure host math), or an
    armed order-invariant content fingerprint stopped matching across a
    stage boundary (``CYLON_TPU_AUDIT=1``).  Carries the facade ``site``
    (``exchange.conserve``, ``audit.verify``, ``ckpt.audit`` ...) and
    the dataflow ``phase`` (``post_exchange``, ``post_stitch``,
    ``stream_absorb``, ``resume``).  A fault type: the consensus ladder
    recomputes the affected stage ONCE (the silent-corruption analogue
    of the disk-corruption rung), then aborts typed on repeat — never a
    wrong answer, never an unbounded retry loop."""

    code = Code.IntegrityFault
    kind = "integrity"

    def __init__(self, msg: str = "", site: str | None = None,
                 phase: str | None = None):
        super().__init__(msg)
        self.site = site
        self.phase = phase


#: the recovery-fault types, in one tuple for isinstance dispatch
FAULT_TYPES = (PredictedResourceExhausted, DeviceOOMError,
               CapacityOverflowError, RankDesyncError,
               DataIntegrityError)


class NumericOverflowError(CylonError):
    """An armed-audit accumulator check (ops/groupby finalize under
    ``CYLON_TPU_AUDIT=1``) found an int64 sum/count at the saturation
    rail: the combine tree wrapped (or is one combine away from
    wrapping), so the aggregate would be silently wrong.  NOT a fault
    type — no retry rung can un-wrap modular arithmetic, so the
    contract is abort-not-wrong: classified typed, surfaced to the
    caller, never retried."""

    code = Code.ExecutionError
    kind = "overflow"

    def __init__(self, msg: str = "", site: str | None = None,
                 column: str | None = None):
        super().__init__(msg)
        self.site = site
        self.column = column


class ResumableAbort(CylonError):
    """The retry ladder's FINAL rung (exec/recovery + exec/checkpoint):
    an unrecoverable fault (real device OOM on an HBM-poisoning rig, a
    reported compiler crash) arrived while durable checkpointing
    was armed — committed piece state has been flushed, and a FRESH
    process launched with ``CYLON_TPU_RESUME=1`` fast-forwards past the
    committed pieces bit-identically instead of recomputing.  ``token``
    is the resume token (the checkpoint directory); the original fault
    rides ``__cause__``.  Terminal by design: never retried in-process
    (the whole point is that in-process retries are doomed here)."""

    code = Code.ExecutionError
    kind = "resumable"

    def __init__(self, msg: str = "", token: str | None = None):
        super().__init__(msg)
        self.token = token


class AdmissionTimeoutError(CylonError):
    """A pending serving session exceeded the admission deadline
    (``CYLON_TPU_ADMISSION_TIMEOUT_S`` or the scheduler's
    ``admission_timeout_s``) while waiting at the head of line: the
    tenant is failed TYPED instead of waiting unboundedly behind a
    long-running co-tenant (docs/serving.md, "Admission deadline").
    Rank-coherent under multi-controller runs — the expiry decision
    rides the count-consensus wire, so every rank fails the same
    session."""

    code = Code.ExecutionError
    kind = "admission_timeout"

    def __init__(self, msg: str = "", session: str | None = None,
                 waited_s: float | None = None):
        super().__init__(msg)
        self.session = session
        self.waited_s = waited_s


class RequeueOverflowError(CylonError):
    """A preempted tenant drained resumably but the scheduler's requeue
    capacity was already exhausted: the tenant stays failed TYPED with
    its resume token preserved on ``__cause__`` (the original
    :class:`ResumableAbort`), so an operator can relaunch it with
    ``CYLON_TPU_RESUME=1`` instead of silently losing the work
    (docs/serving.md, "Preemption & elastic serving")."""

    code = Code.CapacityError
    kind = "requeue_overflow"

    def __init__(self, msg: str = "", session: str | None = None):
        super().__init__(msg)
        self.session = session


class CompileQuarantinedError(CapacityOverflowError):
    """A compile signature is QUARANTINED: the compile-intent journal
    (exec/compiler) shows a predecessor process died mid-compile on this
    exact (builder, shape-signature) pair, so re-lowering it would walk
    straight back into the compiler crash.  Subclasses
    :class:`CapacityOverflowError` deliberately — the recovery ladder's
    ``Code.CapacityError`` rung re-plans at a halved piece cap, which
    changes the operand shapes and therefore the signature, sidestepping
    the quarantined program instead of re-crashing
    (docs/robustness.md, "Compile lifecycle")."""

    kind = "quarantined"

    def __init__(self, msg: str = "", site: str | None = None,
                 signature: str | None = None):
        super().__init__(msg, site=site)
        self.signature = signature


class CompileTimeoutError(CylonError):
    """A ``.lower()``/``.compile()`` exceeded the compile watchdog budget
    (``CYLON_TPU_COMPILE_TIMEOUT_S``): the build thread is hung inside
    XLA, so the caller surfaces TYPED instead of wedging the whole rank
    (and, in multi-controller runs, desyncing its peers).  Same worker
    thread + bounded ``join`` pattern as the exchange watchdog
    (exec/recovery.exchange_watchdog), but typed for the compile axis so
    serving can count / alert on slow-compile tenants separately from
    collective desyncs."""

    code = Code.ExecutionError
    kind = "compile_timeout"

    def __init__(self, msg: str = "", site: str | None = None,
                 signature: str | None = None):
        super().__init__(msg)
        self.site = site
        self.signature = signature


class CheckpointCorruptError(CylonError):
    """A checkpoint page or manifest failed its content-hash check (or
    an injected ``corrupt`` fault simulated that) on the resume path:
    the stage's remaining pieces are recomputed instead of restored —
    corruption degrades resume to recompute, never to a wrong answer."""

    code = Code.SerializationError
    kind = "corrupt"

    def __init__(self, msg: str = "", site: str | None = None):
        super().__init__(msg)
        self.site = site


class CylonTypeError(CylonError):
    code = Code.TypeError


class CylonKeyError(CylonError):
    code = Code.KeyError


class CylonIndexError(CylonError):
    code = Code.IndexError


class CylonIOError(CylonError):
    code = Code.IOError


class NotImplementedCylonError(CylonError):
    code = Code.NotImplemented


class ExecutionError(CylonError):
    code = Code.ExecutionError


class Status:
    """Value-style status for APIs that prefer returns over raises.

    Mirrors reference ``cylon::Status`` (status.hpp:65): ``is_ok()``,
    ``get_code()``, ``get_msg()``.
    """

    __slots__ = ("code", "msg")

    def __init__(self, code: Code = Code.OK, msg: str = ""):
        self.code = Code(code)
        self.msg = msg

    @staticmethod
    def OK() -> "Status":
        return Status(Code.OK)

    def is_ok(self) -> bool:
        return self.code == Code.OK

    def get_code(self) -> Code:
        return self.code

    def get_msg(self) -> str:
        return self.msg

    def raise_if_failed(self) -> None:
        if not self.is_ok():
            raise CylonError(self.msg, self.code)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"Status({self.code.name}, {self.msg!r})"
