"""Per-phase bench timers (the reference's CYLON_BENCH_TIMER analog).

The reference wraps hot regions in a compile-time ``CYLON_BENCH_TIMER(ctx,
tag, ...)`` macro that prints ``[BENCH] tag ms`` on rank 0 when built with
``-D_CYLON_BENCH`` (util/macros.hpp:102-117).  Here the switch is the
runtime flag ``config.BENCH_TIMINGS`` (env ``CYLON_TPU_BENCH=1``): when off,
:func:`region` is one ``jax.profiler.TraceAnnotation`` (a TraceMe that
checks the profiler's flag) and nothing else; when on, wall-time per named
region accumulates in a process-global table that a driver snapshots
into its phase-breakdown detail.

:func:`region` is the ONE choke point of the program's own tracing, with
four sinks: the phase table (``CYLON_TPU_BENCH``), the per-session
:class:`AttributionScope`, the flight recorder's ring (``obs/trace.py``,
``CYLON_TPU_TRACE``) and — always, with no switch of its own — jax's
profiler, where every region is a ``cylon.<name>`` span on the device
events' clock whenever someone runs ``jax.profiler.trace`` around the work
(docs/observability.md).  The two boundaries where the host meets the
device — ``launch.<builder>`` (analysis/runtime.tag_program: the enqueue of
every program_cache program) and ``pull.<kind>`` (utils/host: every
sanctioned host pull) — go through :func:`span`, the same thing without the
tables: they nest inside the operator regions and must not be summed twice.
So do the operator call itself, ``op.<op>`` (obs/plan._NodeCtx: every
``plan.node``), and the named stretches of host work between a pull's return
and the next launch, ``host.<step>`` (:data:`HOST_STEPS`): what is in no
``launch.*`` / ``pull.*`` span of an ``op.*`` span is a TURN of the host, and
``benchmark/readers/trace_round_trips.py`` reads all of it off the spans.

JAX dispatch is async — a region covering only device work would time the
dispatch, not the execution.  Regions are therefore placed around phases
that end in a host synchronization (count-matrix pulls, ``np.asarray`` of
sidecars); purely-async phases are flushed explicitly by the caller
(``block=`` argument) when exact attribution matters.

**The observer effect, and the async mode.**  Those explicit flushes
(:func:`maybe_block`) SERIALIZE piece production against piece compute —
exactly the overlap the pipelined operators exist for — so blocking
attribution both slows the profiled iteration and HIDES overlap wins in
the phase numbers.  ``CYLON_TPU_TIMING=async`` (config.TIMING_ASYNC)
keeps the regions as dispatch-only markers: ``maybe_block`` becomes a
no-op, each region records only the host time it took to ENQUEUE its
work, and the caller blocks once at iteration end (its final
output sync).  Phase numbers then read as "host time to dispatch": a
phase that stops dominating dispatch has genuinely left the critical
path.  Exact per-phase device attribution still needs ``block`` mode.

**Session-scoped attribution.**  The serving tier
(:mod:`cylon_tpu.exec.scheduler`) interleaves many tenants' queries on
one mesh, and a single process-global table would blend their phases —
tenant A's ``pipe.piece_join`` seconds indistinguishable from tenant
B's.  :func:`attribution_scope` opens a PRIVATE phase table routed by
thread identity: every :func:`region`/:func:`bump`/:func:`add_bytes` on
the scoped thread also lands in the scope's table (regions time
unconditionally inside a scope, independent of ``CYLON_TPU_BENCH`` —
the fair-share policy needs per-session dispatch seconds even in
production runs).  Scopes on different threads are DISJOINT by
construction — no cross-tenant attribution bleed — while the
process-global table keeps accumulating the union exactly as before
(a driver's snapshot is unchanged).  :func:`last_region` is
likewise scope-local when a scope is active, so a watchdog fault raised
on one tenant's thread carries that tenant's phase breadcrumb, not a
neighbor's.
"""

from __future__ import annotations

import contextlib
import threading
import time

from jax.profiler import TraceAnnotation

from .. import config

#: every region is also a ``cylon.<name>`` span of jax's profiler
ANNOTATION_PREFIX = "cylon."

#: the closed vocabulary of ``span("host.<step>")``: the stretches of an
#: operator call between a pull's return and the next launch in which the
#: host does real work (docs/observability.md, "A round trip, both ends").
#: Held by tests/test_obs.py over the source and over a profiled query,
#: not by a check at run time: :func:`span` stays as it is.
HOST_STEPS = ("skew_operands", "skew_weigh", "skew_detect", "exchange_pack",
              "exchange_plan", "exchange_close", "exchange_unpack",
              "join_plan", "sort_splitters")

#: name -> [total_seconds, call_count]
_ACCUM: dict[str, list] = {}

#: most recently entered region name — the exchange watchdog attaches it
#: to RankDesyncError as the last-known phase (always tracked, even with
#: timings off: one list-slot store per region)
_LAST_REGION = [""]

#: per-thread stack of active AttributionScopes (serving sessions run on
#: their own threads, so thread identity IS session identity here).  The
#: same TLS carries the thread's cumulative baton-park seconds
#: (``.excluded``): the PROCESS-GLOBAL table nets a region's own
#: thread's park time out exactly like the scope table does — a region
#: spanning a serving yield must not charge co-tenants' slices to the
#: global phase either (the fair-share no-bleed invariant, now applied
#: to both tables).
_SCOPE_TLS = threading.local()

#: the observability trace sink (cylon_tpu.obs.trace installs the armed
#: flight recorder here): every region exit becomes a timeline span,
#: every bump/add_bytes an instant.  One list load per region when
#: unarmed — the trace tier's whole happy-path cost in this module.
_TRACE: list = [None]


class AttributionScope:
    """One session's private phase table — see module docstring.  Obtain
    via :func:`attribution_scope`; read with :meth:`snapshot` (same shape
    as the module-level :func:`snapshot`) and :meth:`total_seconds` (the
    fair-share policy's accumulated-dispatch-time input)."""

    __slots__ = ("tag", "last", "_accum", "_bytes", "_excluded")

    def __init__(self, tag: str = ""):
        self.tag = tag
        self.last = ""
        self._accum: dict[str, list] = {}
        self._bytes: dict[str, int] = {}
        #: cumulative seconds this thread spent parked at the serving
        #: baton (scheduler._yield_turn) — subtracted from any region
        #: whose window contains the park, so a yield INSIDE a region
        #: (join.shuffle, pipe.consume) never charges co-tenants' slices
        #: to this scope's phase table or fair-share clock
        self._excluded = 0.0

    def _add(self, name: str, dt: float, n: int = 1) -> None:
        acc = self._accum.setdefault(name, [0.0, 0])
        acc[0] += dt
        acc[1] += n

    def _add_bytes(self, name: str, nbytes: int) -> None:
        self._bytes[name] = self._bytes.get(name, 0) + int(nbytes)
        self._accum.setdefault(name, [0.0, 0])

    def total_seconds(self) -> float:
        return sum(v[0] for v in self._accum.values())

    def absorb(self, other: "AttributionScope") -> None:
        """Merge another scope's table into this one — the plan
        profiler's node scopes shadow an enclosing serving-session scope
        exactly like nested scopes always did, so on node exit the
        node's SELF table is absorbed into the session scope: the
        tenant's fair-share clock and phase table see the same seconds
        with profiling on or off (obs/plan.py)."""
        for k, v in other._accum.items():
            self._add(k, v[0], v[1])
        for k, b in other._bytes.items():
            self._add_bytes(k, b)

    def snapshot(self) -> dict:
        out = {}
        for k, v in sorted(self._accum.items(), key=lambda kv: -kv[1][0]):
            ent = {"s": round(v[0], 4), "n": v[1]}
            if self._bytes.get(k):
                ent["b"] = self._bytes[k]
            out[k] = ent
        return out


def _scope() -> AttributionScope | None:
    stack = getattr(_SCOPE_TLS, "stack", None)
    return stack[-1] if stack else None


def exclude_from_scope(seconds: float) -> None:
    """Mark ``seconds`` of the current thread's wall time as NOT this
    thread's work — the serving scheduler calls this with the time a
    session spent parked at the baton, so regions spanning a yield point
    attribute only the tenant's own dispatch time (no co-tenant bleed
    into phase tables or the fair-share clock).  Nets out of BOTH the
    active scope's table and the process-global ``_ACCUM`` table (the
    global phase seconds previously absorbed co-tenants' slices inside
    spanning regions)."""
    s = float(seconds)
    _SCOPE_TLS.excluded = getattr(_SCOPE_TLS, "excluded", 0.0) + s
    sc = _scope()
    if sc is not None:
        sc._excluded += s


@contextlib.contextmanager
def attribution_scope(tag: str = ""):
    """Route this THREAD's regions/bumps/byte attributions into a private
    :class:`AttributionScope` (in addition to the process-global table)
    until exit.  Nested scopes shadow (innermost wins).  Yields the
    scope; its table survives the exit for later snapshots."""
    sc = AttributionScope(tag)
    stack = getattr(_SCOPE_TLS, "stack", None)
    if stack is None:
        stack = _SCOPE_TLS.stack = []
    stack.append(sc)
    try:
        yield sc
    finally:
        stack.pop()


def _annotation(name: str, sc, args: dict):
    """The ``cylon.<name>`` span of jax's profiler for one region/span: a
    TraceMe that checks the profiler's flag on enter.  The active scope's
    tag rides as ``session``."""
    if sc is not None and sc.tag:
        return TraceAnnotation(ANNOTATION_PREFIX + name, session=sc.tag,
                               **args)
    return TraceAnnotation(ANNOTATION_PREFIX + name, **args)


@contextlib.contextmanager
def span(name: str, **args):
    """A BOUNDARY span: on jax's profiler and in the flight recorder's
    ring, like a region, but in no table.  For the places where the
    host meets the device — ``launch.<builder>`` (the enqueue of a
    program), ``pull.<kind>`` (a host pull) and ``exchange.<route>`` (one
    exchange's launches) — which nest inside the operator regions:
    entered into the phase/scope tables they would be counted twice in
    every sum over a table (a session's fair-share clock, a plan node's
    seconds, a driver's dispatch total).

    Yields ``(annotation, args)``: what is known only inside the span (a
    filter's ``rows_out``) is added to both - ``set_metadata`` on the
    one, ``update`` on the other (obs/plan._NodeCtx.span_args)."""
    ann = _annotation(name, _scope(), args)
    with ann:
        tr = _TRACE[0]
        if tr is None:
            yield ann, args
            return
        t0 = time.perf_counter()
        try:
            yield ann, args
        finally:
            tr.span(name, t0, time.perf_counter() - t0, args or None)


def set_args(live, **args) -> None:
    """Arguments an open :func:`span` / :func:`region` learns while it runs
    (``live`` is what the ``with`` yielded): ``set_metadata`` on the
    profiler's annotation, ``update`` on the flight recorder's arguments,
    like those given at entry."""
    ann, given = live
    ann.set_metadata(**args)
    given.update(args)


@contextlib.contextmanager
def region(name: str, block=None, **args):
    """Time a named region (when ``config.BENCH_TIMINGS`` — or always,
    scope-locally, inside an :func:`attribution_scope`).  ``block`` may be
    a jax array (or pytree leaf list) to block_until_ready before stopping
    the clock, charging async device work to this region.

    Every region is ALSO a ``cylon.<name>`` span on jax's profiler
    (``TraceAnnotation``): whoever runs ``jax.profiler.trace`` around the
    work finds the program's spans on the trace's host plane, on the
    device events' clock, nested under the call that opened them — the
    program is not told and has no switch for it.  With no profiler
    running that is a ``TraceMe`` that checks one flag.  ``args`` (small
    scalars: ``bytes=``, ``rows=``) go to the annotation and to the
    flight recorder's span; the active scope's tag goes in as
    ``session``.  Yields what :func:`span` yields, for :func:`set_args`."""
    sc = _scope()
    if sc is not None:
        sc.last = name
    else:
        _LAST_REGION[0] = name
    ann = _annotation(name, sc, args)
    with ann:
        if not config.BENCH_TIMINGS and sc is None and _TRACE[0] is None:
            yield ann, args
            return
        t0 = time.perf_counter()
        ex0 = sc._excluded if sc is not None else 0.0
        gex0 = getattr(_SCOPE_TLS, "excluded", 0.0)
        try:
            yield ann, args
        finally:
            if block is not None and not config.TIMING_ASYNC:
                import jax
                jax.block_until_ready(block)
            dt = time.perf_counter() - t0
            tr = _TRACE[0]
            if tr is not None:
                tr.span(name, t0, dt, args or None)
            if config.BENCH_TIMINGS:
                # baton-park time that fell inside this region's window
                # is not this THREAD's work (exclude_from_scope); like
                # the scope table below, the global table nets it out —
                # the cumulative counters handle nesting correctly
                gnet = getattr(_SCOPE_TLS, "excluded", 0.0) - gex0
                acc = _ACCUM.setdefault(name, [0.0, 0])
                acc[0] += max(dt - gnet, 0.0)
                acc[1] += 1
            if sc is not None:
                sc._add(name, max(dt - (sc._excluded - ex0), 0.0))


#: snapshot-key suffix marking a BLOCKING host-sync region — the
#: dispatch/block attribution split (``split_snapshot``)
BLOCK_SUFFIX = ".block"


@contextlib.contextmanager
def sync_region(name: str):
    """Time a deliberate blocking host pull under ``name + '.block'``.

    The async attribution mode (``CYLON_TPU_TIMING=async``) turns every
    :func:`region` into a dispatch-only marker; the wall time those
    markers no longer capture is spent at the few designated sync points
    (the pipelined join's batched phase pull, per-piece count/meta
    pulls, the bench driver's final output sync).  Wrapping exactly those
    pulls in ``sync_region`` splits each phase into *dispatch* time (its
    plain region) and *block* time (its ``.block`` twin), so phase
    overlap is directly measurable: a phase that overlaps well shows
    near-zero dispatch AND near-zero block — its device work hides under
    another phase's block point."""
    with region(name if name.endswith(BLOCK_SUFFIX)
                else name + BLOCK_SUFFIX):
        yield


def split_snapshot(snap: dict) -> tuple[dict, dict]:
    """Split a :func:`snapshot` into ``(dispatch, block)`` second-maps:
    ``.block``-suffixed regions (``sync_region``) land in ``block`` under
    their base name; everything else is dispatch(-or-blocking-mode)
    attribution."""
    dispatch, block = {}, {}
    for k, v in snap.items():
        if k.endswith(BLOCK_SUFFIX):
            block[k[:-len(BLOCK_SUFFIX)]] = v["s"]
        else:
            dispatch[k] = v["s"]
    return dispatch, block


def maybe_block(x) -> None:
    """block_until_ready(x) ONLY when bench timings are on AND the timing
    mode is blocking — lets a region charge async device work to itself
    for attribution without serializing dispatch in production runs.  In
    async mode (``CYLON_TPU_TIMING=async``) this is a no-op even while
    timing: regions become dispatch-only markers and the caller blocks
    once at iteration end, so the measurement no longer perturbs the
    dispatch/compute overlap it measures."""
    if config.BENCH_TIMINGS and not config.TIMING_ASYNC:
        import jax
        jax.block_until_ready(x)


def last_region() -> str:
    """Name of the most recently entered region ("" before the first) —
    the failure-recovery watchdog's last-known-phase breadcrumb.  Inside
    an :func:`attribution_scope` this is the SCOPE's last region, so a
    fault on one serving session's thread never reports a co-tenant's
    phase."""
    sc = _scope()
    if sc is not None:
        return sc.last
    return _LAST_REGION[0]


def bump(name: str) -> None:
    """Count an event in the phase table without timing it (recovery
    events, exec/recovery): shows up in :func:`snapshot` with s=0 and the
    occurrence count, mirrored into the metrics registry
    (``timing_event_<name>``) and — when the flight recorder is armed —
    the trace timeline.  Unconditional — recovery events are rare and
    must be countable even without ``CYLON_TPU_BENCH``."""
    acc = _ACCUM.setdefault(name, [0.0, 0])
    acc[1] += 1
    _EVENT_COUNTS[name] = _EVENT_COUNTS.get(name, 0) + 1
    tr = _TRACE[0]
    if tr is not None:
        tr.instant(name)
    sc = _scope()
    if sc is not None:
        sc._add(name, 0.0)


# Registry-backed attribution tables (cylon_tpu.obs.metrics — the typed
# registry this module's counters migrated onto).  The dict-like views
# keep every call site verbatim while the values live in (and export
# from) the registry; the collector hands the phase table itself to
# metrics.snapshot() / the periodic JSON snapshots.
from ..obs import metrics as _metrics  # noqa: E402

#: name -> bytes moved, the spill tier's phase attribution: seconds alone
#: cannot say whether ``spill.upload`` is PCIe-bound or dispatch-bound —
#: GB/phase does.  Unconditional like bump(): spill traffic must be
#: attributable even without CYLON_TPU_BENCH.
_BYTES = _metrics.namespace("timing_bytes")

#: bump() occurrence counts, registry-visible for Prometheus exposition
_EVENT_COUNTS = _metrics.namespace("timing_event")

_metrics.register_collector(lambda: {"phases": snapshot()})


def add_bytes(name: str, nbytes: int) -> None:
    """Attribute ``nbytes`` of host↔device traffic to a named phase
    (exec/memory spill/evict/upload); appears as ``b`` in
    :func:`snapshot` entries and as ``timing_bytes_<name>`` in the
    metrics registry."""
    _BYTES[name] = _BYTES.get(name, 0) + int(nbytes)
    _ACCUM.setdefault(name, [0.0, 0])
    tr = _TRACE[0]
    if tr is not None:
        tr.instant(name, {"bytes": int(nbytes)})
    sc = _scope()
    if sc is not None:
        sc._add_bytes(name, nbytes)


def reset() -> None:
    """Zero the phase table, byte/event attribution AND the last-region
    breadcrumb (a fresh profile must not inherit the previous
    workload's final phase as its crash breadcrumb) plus the thread's
    park-exclusion accumulator."""
    _ACCUM.clear()
    _BYTES.clear()
    _EVENT_COUNTS.clear()
    _LAST_REGION[0] = ""
    _SCOPE_TLS.excluded = 0.0


def total_seconds() -> float:
    """Unrounded seconds of the whole phase table."""
    return sum(v[0] for v in _ACCUM.values())


def snapshot() -> dict:
    """{region: {"s": total_seconds, "n": calls[, "b": bytes_moved]}}
    sorted by cost; ``b`` appears only for phases that attributed
    host↔device bytes (:func:`add_bytes`)."""
    out = {}
    for k, v in sorted(_ACCUM.items(), key=lambda kv: -kv[1][0]):
        ent = {"s": round(v[0], 4), "n": v[1]}
        if _BYTES.get(k):
            ent["b"] = _BYTES[k]
        out[k] = ent
    return out
