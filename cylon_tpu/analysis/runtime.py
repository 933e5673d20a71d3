"""Pass 3 — the retrace / transfer sentinel (runtime hooks).

Retraces and host round-trips are invisible on CPU test rigs: a builder
whose cache key omits a shape-dependent static argument silently
recompiles per call (seconds-per-compile on a remote TPU), and a stray
``np.asarray`` inside an op turns a device-resident pipeline into a
host ping-pong.  This module makes both observable and budget-checkable:

* **compile attribution** — every program built through
  :func:`cylon_tpu.utils.cache.program_cache` is tagged
  (:func:`tag_program`) so that XLA compile events (``jax.monitoring``,
  ``/jax/core/compile/backend_compile_duration``) occurring during its
  calls are recorded against ``(builder, shape_signature)``;
* **retrace detection** — a second compile for the SAME (builder,
  signature) means the jit cache failed to hold (unstable key, donated
  buffer mismatch, weak-type flapping): rule RT301.  More distinct
  compiled programs for one builder than its declared budget
  (:mod:`cylon_tpu.analysis.registry`) is a shape-family explosion:
  rule RT302;
* **transfer ledger** — :func:`transfer_scope` counts sanctioned host
  pulls (the :mod:`cylon_tpu.utils.host` funnel calls
  :func:`note_transfer`) so tests can assert an op's device↔host budget:
  rule RT303.

Everything is off (near-zero overhead: one truthiness check per builder
call) until :func:`enable` — ``tests/conftest.py`` enables it under
``CYLON_TPU_TRACECHECK=1``.
"""

from __future__ import annotations

import contextlib
import threading
from collections import Counter
from dataclasses import dataclass, field

_lock = threading.Lock()

#: sentinel state — module-level singleton, None while disabled
_state = None

_local = threading.local()


@dataclass
class SentinelState:
    #: (builder, signature) -> number of program CALLS that triggered an
    #: XLA backend compile (a second one for the same signature = retrace)
    compiles: Counter = field(default_factory=Counter)
    #: builder -> number of distinct cache keys built (program_cache misses)
    builds: Counter = field(default_factory=Counter)
    #: builder -> number of cache hits (for cache-health reporting)
    hits: Counter = field(default_factory=Counter)
    #: compiles not attributable to any tagged builder
    untagged_compiles: int = 0
    listener_installed: bool = False


def enabled() -> bool:
    return _state is not None


def enable() -> "SentinelState":
    """Install the sentinel (idempotent).  Returns the live state."""
    global _state
    with _lock:
        if _state is None:
            _state = SentinelState()
        if not _state.listener_installed:
            import jax
            jax.monitoring.register_event_duration_secs_listener(_on_event)
            _state.listener_installed = True
    return _state


def reset() -> None:
    """Zero the counters (keeps the listener installed)."""
    if _state is not None:
        _state.compiles.clear()
        _state.builds.clear()
        _state.hits.clear()
        _state.untagged_compiles = 0


def state() -> "SentinelState | None":
    return _state


def _on_event(event: str, duration: float, **kwargs) -> None:
    # one logical program call can emit several backend_compile events
    # (main program + auxiliary reshard/convert programs); the sentinel
    # counts COMPILING CALLS, so the listener just raises a flag the call
    # wrapper collapses to one count per call
    st = _state
    if st is None or not event.startswith("/jax/core/compile/backend_compile"):
        return
    if getattr(_local, "builder", None) is None:
        with _lock:
            st.untagged_compiles += 1
    else:
        _local.call_compiled = True


def _signature(args, kwargs) -> tuple:
    """Cheap shape signature of a program call: (shape, dtype) leaves.
    Only computed while the sentinel is enabled."""
    sig = []

    def leaf(x):
        shp = getattr(x, "shape", None)
        if shp is not None:
            sig.append((tuple(shp), str(getattr(x, "dtype", ""))))
        elif isinstance(x, (tuple, list)):
            for e in x:
                leaf(e)

    for a in args:
        leaf(a)
    for a in kwargs.values():
        leaf(a)
    return tuple(sig)


def note_builder(name: str, key, miss: bool) -> None:
    """Called by program_cache on every lookup."""
    st = _state
    if st is None:
        return
    with _lock:
        (st.builds if miss else st.hits)[name] += 1


def note_transfer(kind: str, n: int = 1) -> None:
    """Called by the utils.host funnel on every sanctioned host pull."""
    ledger = getattr(_local, "ledger", None)
    if ledger is not None:
        ledger[kind] += n


def tag_program(name: str, program, key=()):
    """Wrap a built program so calls attribute compile events to ``name``.

    ``key`` is the builder's static cache key: two programs from one
    builder with different static args legitimately compile once EACH,
    so the retrace identity is (builder, static key, call-shape
    signature) — without the key, zero-arg programs (and same-shaped
    calls of sibling programs) would collapse and false-trip RT301.
    Transparent when the sentinel is disabled except for one attribute
    check; ``__wrapped__`` exposes the raw program for tracing.
    """

    from ..exec import compiler
    from ..utils import timing
    from ..utils.cache import short_name
    short = short_name(name)
    span = "launch." + short

    def tagged(*args, **kwargs):
        # the host side of one program launch: enqueue (and, on first
        # sight, trace + compile) — `launch.<builder>` on every sink of
        # timing.span: the profiler's trace and the flight recorder
        with timing.span(span):
            was = compiler.launching(short)
            try:
                st = _state
                if st is None:
                    return program(*args, **kwargs)
                prev = getattr(_local, "builder", None)
                prev_flag = getattr(_local, "call_compiled", False)
                _local.builder = (name, key, _signature(args, kwargs))
                _local.call_compiled = False
                try:
                    return program(*args, **kwargs)
                finally:
                    if getattr(_local, "call_compiled", False):
                        with _lock:
                            st.compiles[_local.builder] += 1
                    _local.builder = prev
                    _local.call_compiled = prev_flag
            finally:
                compiler.launching(was)

    tagged.__wrapped__ = program
    tagged.__name__ = f"tagged[{name}]"
    return tagged


@contextlib.contextmanager
def transfer_scope():
    """Count sanctioned host pulls made inside the scope.

    Yields a ``Counter``; the utils.host funnel increments it.  Nested
    scopes shadow outer ones (innermost wins — per-op budgets).
    """
    prev = getattr(_local, "ledger", None)
    ledger = Counter()
    _local.ledger = ledger
    try:
        yield ledger
    finally:
        _local.ledger = prev


def check_budgets(budgets: dict | None = None) -> list:
    """Evaluate sentinel counters against declared budgets.

    Returns a list of ``(rule, builder, message)`` violations:

    * RT301 — some (builder, signature) compiled more than once;
    * RT302 — a builder built more distinct programs than its budget
      (default from the registry; 64 when undeclared).
    """
    st = _state
    out = []
    if st is None:
        return out
    from . import registry
    decls = {d.builder: d for d in registry.all_declarations()}
    for tag, n in st.compiles.items():
        name, sig = tag[0], tag[1:]
        if n > 1:
            out.append(("RT301", name,
                        f"{name} compiled {n}x for one (static key, shape "
                        f"signature) {sig!r} — jit cache is not holding"))
    if budgets is None:
        budgets = {}
    for name, n in st.builds.items():
        decl = decls.get(name)
        budget = budgets.get(name,
                             decl.retrace_budget if decl is not None else 64)
        if n > budget:
            out.append(("RT302", name,
                        f"{name} built {n} distinct programs this session "
                        f"(budget {budget}) — shape-family explosion"))
    return out
