"""Compile-lifecycle facade — the one gate between the engine and XLA.

ROADMAP item 2's COMPILE axis: before this module, compile cost was
O(tenants) (every distinct ingest row count compiled its own program
family at world 1) and compile state accumulated unboundedly in-process
— this rig's deterministic XLA:CPU ``backend_compile`` SIGSEGV under
accumulation (the reason tier-1 runs one pytest process per file) is
direct evidence that unbounded accumulation is a production outage.
The facade makes compilation **bounded, persistent and typed-failing**:

* **shape families** (:func:`family_cap`) — single-controller ingest
  buckets row capacity onto the same pow2 families the multi-rank
  distributor always used (``config.pow2ceil`` + masked validity
  tails), so N tenants with near-miss plans share ONE executable;
  bit- and order-equal because padding rides the existing pad/validity
  lanes.  Pure function of the row count → rank-uniform with no vote.
  Escape hatch ``CYLON_TPU_SHAPE_FAMILIES=0``.
* **bounded compile ledger** — a registry over live compiled programs
  per mesh fed by ``utils/cache.program_cache`` (:func:`on_insert` /
  :func:`on_hit` / :func:`on_builder_evict` / :func:`on_table_evict`),
  with an LRU eviction budget (``CYLON_TPU_COMPILE_BUDGET``): past it
  the oldest non-pinned programs are retired BEFORE the accumulation
  crash point (re-use recompiles, warm from the persistent cache where
  armed).  In multiprocess sessions the eviction count rides the
  existing count-consensus wire so every rank drops the same programs.
* **persistent layer** (``CYLON_TPU_COMPILE_CACHE_DIR``) — keeps three
  facade-owned files there with the checkpoint tier's atomic-write
  (+ bounded ``retry_io``) discipline (jax's own on-disk compilation
  cache is config.py's business and does not move with this dir): a
  warm **manifest** of successfully compiled signatures (content-hashed
  — a corrupted entry fails its hash and is DROPPED: clean miss →
  recompile, never wrong code), a **quarantine** ledger, and a per-rank
  compile-**intent** journal.
* **watchdog + crash quarantine** — the intent record is written
  BEFORE each guarded ``.lower()``/``.compile()``/first-trace and
  cleared after, so a relaunched process finds the intent its dead
  predecessor left, quarantines that signature, and raises typed
  :class:`~cylon_tpu.status.CompileQuarantinedError` instead of
  re-crashing — which subclasses the capacity fault, so the recovery
  ladder's cap-halving rung re-plans at a DIFFERENT shape.  Hung
  compiles surface as :class:`~cylon_tpu.status.CompileTimeoutError`
  via the exchange-watchdog worker-thread pattern
  (``CYLON_TPU_COMPILE_TIMEOUT_S``).

Every compile in the package rides this facade: modules import
:func:`jit` from here instead of calling ``jax.jit`` (lint rule TS117
fences raw ``jax.jit`` / ``.lower().compile()`` outside this module and
``utils/cache.py``), and AOT prewarms go through :func:`aot_compile`.

Overhead contract (the chaos soak's unarmed leg asserts it): with no
cache dir, no watchdog budget and no ``compile.build`` injector spec,
:func:`jit` programs call straight through — one list load + one
``is None``/bool check per call, ZERO filesystem writes, zero
collectives, zero host syncs.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import threading
import time
import weakref
from collections import OrderedDict

import jax

from .. import config
from ..obs import metrics
from ..status import CompileQuarantinedError, CompileTimeoutError

#: the injector site guarding every facade-routed compile
SITE = "compile.build"

#: ledger entries whose builder name starts with one of these are never
#: evicted: the consensus-wire programs (exec/recovery) are themselves
#: program_cache builders — evicting the wire would make the NEXT
#: eviction vote recompile it mid-agreement (re-entrancy), and a
#: retired wire desyncs the very mechanism that coordinates retirement
_PINNED_PREFIXES = ("cylon_tpu.exec.recovery",)

_HIT = metrics.counter(
    "compile_cache_hit_total",
    help="program_cache lookups served from a live compiled program")
_MISS = metrics.counter(
    "compile_cache_miss_total",
    help="program_cache lookups that built (compiled) a new program")
_EVICT = metrics.counter(
    "compile_cache_evict_total",
    help="live compiled programs retired (ledger budget, per-builder "
         "LRU bound, or mesh-table LRU)")
_MESH_EVICT = metrics.counter(
    "compile_mesh_table_evict_total",
    help="whole per-mesh program tables cleared by the MESH_TABLE_LIMIT "
         "LRU (previously silent in utils/cache.py)")
_SECONDS = metrics.counter(
    "compile_seconds_total",
    help="cumulative XLA backend_compile seconds (jax.monitoring)")
_EVENTS = metrics.counter(
    "compile_events_total",
    help="XLA backend_compile invocations observed (jax.monitoring) — "
         "the per-file `# COMPILE_COUNT` line tests/run_all.py greps")
_QUARANTINED = metrics.counter(
    "compile_quarantine_total",
    help="compile signatures quarantined from a predecessor's orphaned "
         "compile-intent journal")
_TIMEOUTS = metrics.counter(
    "compile_timeout_total",
    help="guarded compiles aborted typed by the compile watchdog")
_MANIFEST_DROPS = metrics.counter(
    "compile_manifest_drop_total",
    help="persistent warm-manifest entries dropped on a failed content "
         "hash (clean miss; never loads wrong code)")

#: backend-compile seconds / events by the builder whose program was being
#: launched when the compile fired (``launching``; "untagged" outside any):
#: which program compiles twice on a cold cache, which one misses a warm one
_SECONDS_BY = metrics.namespace("compile_seconds_by_builder")
_EVENTS_BY = metrics.namespace("compile_events_by_builder")

_lock = threading.RLock()
_tls = threading.local()

#: (mesh_key, builder_name, static_key) -> (weakref(per-builder LRU),
#: static_key) in insertion (≈ LRU) order; the bounded compile ledger
_LEDGER: "OrderedDict[tuple, tuple]" = OrderedDict()

#: armed tri-state: None = recompute on next probe (rearm())
_ARMED: list = [None]

#: persistent-layer state for the currently scanned dir ("" = none)
_DIR_STATE: dict = {"path": None, "quarantine": set(), "manifest": {},
                    "adopted": []}

#: signatures already guarded-compiled in THIS process (armed mode only)
_SEEN: set = set()

_LISTENER: list = [False]


def _on_compile_event(event: str, duration: float, **kw) -> None:
    if event.startswith("/jax/core/compile/backend_compile"):
        _SECONDS.inc(duration)
        _EVENTS.inc()
        builder = getattr(_tls, "launching", None) or "untagged"
        with _lock:
            _SECONDS_BY[builder] = _SECONDS_BY.get(builder, 0.0) + duration
            _EVENTS_BY[builder] = _EVENTS_BY.get(builder, 0) + 1
        from ..obs import trace
        rec = trace.recorder()
        if rec is not None:   # the event arrives when the compile ENDS
            rec.span("compile." + builder, time.perf_counter() - duration,
                     duration)


def launching(builder: str | None) -> str | None:
    """Mark this thread as launching ``builder``'s program (None = no
    longer); returns the previous mark.  ``analysis/runtime.tag_program``
    brackets every ``program_cache`` program's call with it, so a backend
    compile fired inside is attributed to its builder."""
    prev = getattr(_tls, "launching", None)
    _tls.launching = builder
    return prev


def install_listener() -> None:
    """Idempotently hook jax's compile-event monitoring into the facade
    counters.  The facade's own :func:`jit` installs it on first use;
    harnesses that want compile counts before any facade program exists
    (tests/conftest.py's per-file ``# COMPILE_COUNT`` line) call it
    directly."""
    if not _LISTENER[0]:
        _LISTENER[0] = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_event)


_install_listener = install_listener


# ---------------------------------------------------------------------------
# shape families
# ---------------------------------------------------------------------------

def family_cap(n: int) -> int:
    """The canonical row capacity for a single-controller ingest of
    ``n`` rows: the pow2-family bucket (``config.pow2ceil`` — exactly
    the buckets the multi-rank distributor and every operator output
    capacity already use) while ``CYLON_TPU_SHAPE_FAMILIES`` is armed
    (the default), else ``n`` (exact-shape placement).  Pure function
    of the row count — rank-uniform by construction, no vote needed."""
    n = int(n)
    if n <= 0 or not config.SHAPE_FAMILIES:
        return max(n, 0)
    return config.pow2ceil(n)


# ---------------------------------------------------------------------------
# armed-state plumbing
# ---------------------------------------------------------------------------

def cache_dir() -> str:
    """The facade's persistent directory (``CYLON_TPU_COMPILE_CACHE_DIR``),
    or ``""`` when the durable layer is disarmed."""
    return str(getattr(config, "COMPILE_CACHE_DIR", "") or "")


def _compute_armed() -> bool:
    if float(getattr(config, "COMPILE_TIMEOUT_S", 0) or 0) > 0:
        return True
    if cache_dir():
        return True
    try:
        from . import recovery
        return recovery.faults_declare(SITE)
    except Exception:  # noqa: BLE001 — a broken spec disarms, not crashes
        return False


def armed() -> bool:
    """True while any lifecycle feature (persistent dir, watchdog
    budget, ``compile.build`` injector spec) needs the guarded path.
    Cached; :func:`rearm` invalidates (tests / chaos reprogramming)."""
    a = _ARMED[0]
    if a is None:
        a = _ARMED[0] = _compute_armed()
    return a


def rearm() -> None:
    """Recompute the armed state and re-scan the persistent dir on next
    use — call after changing ``config.COMPILE_*`` knobs or
    ``recovery.install_faults`` specs mid-process (tests, chaos)."""
    _ARMED[0] = None
    _DIR_STATE["path"] = None


# ---------------------------------------------------------------------------
# persistent layer: manifest / quarantine / intent journal
# ---------------------------------------------------------------------------

def _atomic_json(path: str, payload) -> None:
    """Checkpoint-tier write discipline: tmp + ``os.replace`` under the
    bounded transient-OSError retry (exec/recovery.retry_io)."""
    from . import recovery

    def write():
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, sort_keys=True)
        os.replace(tmp, path)

    recovery.retry_io(write, SITE)


def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _entry_sha(sig: str, builder: str) -> str:
    return hashlib.sha1(f"{sig}|{builder}".encode()).hexdigest()[:16]


def _intent_path(d: str) -> str:
    return os.path.join(d, f"intent.rank{jax.process_index()}.json")


def _ensure_dir() -> dict | None:
    """Arm the persistent layer for the configured dir (idempotent per
    dir).  Loads the quarantine ledger, hash-validates the warm
    manifest (corrupt entries DROP — clean miss, never wrong code), and
    adopts orphaned compile intents: an intent file present at arm time
    was left by a predecessor that died mid-compile (the happy path
    always clears it), so its signature is quarantined."""
    d = cache_dir()
    if not d:
        return None
    with _lock:
        if _DIR_STATE["path"] == d:
            return _DIR_STATE
        from . import recovery
        recovery.retry_io(lambda: os.makedirs(d, exist_ok=True), SITE)
        q = _read_json(os.path.join(d, "quarantine.json")) or {}
        quarantine = set(q.get("signatures", ()))
        man = _read_json(os.path.join(d, "manifest.json")) or {}
        manifest, dropped = {}, 0
        for sig, ent in man.items() if isinstance(man, dict) else ():
            try:
                ok = ent.get("sha") == _entry_sha(sig, ent.get("builder", ""))
            except AttributeError:
                ok = False
            if ok:
                manifest[sig] = ent
            else:
                dropped += 1
        if dropped:
            _MANIFEST_DROPS.inc(dropped)
            _record("corrupt", f"manifest_drop:{dropped}")
        # adopt orphaned intents from ANY rank of the dead predecessor
        adopted = []
        try:
            names = [f for f in os.listdir(d)
                     if f.startswith("intent.rank") and f.endswith(".json")]
        except OSError:
            names = []
        for name in sorted(names):
            p = os.path.join(d, name)
            intent = _read_json(p)
            sig = (intent or {}).get("sig")
            if sig and sig not in quarantine:
                quarantine.add(sig)
                adopted.append({"sig": sig,
                                "builder": (intent or {}).get("builder", "")})
                _QUARANTINED.inc()
                _record("quarantined",
                        f"orphan_intent:{(intent or {}).get('builder', '?')}")
            try:
                os.remove(p)
            except OSError:
                pass
        if adopted:
            _atomic_json(os.path.join(d, "quarantine.json"),
                         {"signatures": sorted(quarantine)})
        _DIR_STATE.update(path=d, quarantine=quarantine, manifest=manifest,
                          adopted=adopted)
        return _DIR_STATE


def quarantine(sig: str, builder: str = "") -> None:
    """Persist ``sig`` into the quarantine ledger (tests / operators)."""
    st = _ensure_dir()
    with _lock:
        if st is None:
            _DIR_STATE["quarantine"].add(sig)
            return
        st["quarantine"].add(sig)
        _atomic_json(os.path.join(st["path"], "quarantine.json"),
                     {"signatures": sorted(st["quarantine"])})


def quarantined_signatures() -> tuple:
    with _lock:
        return tuple(sorted(_DIR_STATE["quarantine"]))


def _write_intent(label: str, sig: str) -> None:
    d = cache_dir()
    if d:
        _atomic_json(_intent_path(d),
                     {"builder": label, "sig": sig, "pid": os.getpid()})


def _clear_intent() -> None:
    d = cache_dir()
    if not d:
        return
    try:
        os.remove(_intent_path(d))
    except OSError:
        pass


def _manifest_add(label: str, sig: str, poison: bool = False) -> None:
    st = _ensure_dir()
    if st is None:
        return
    with _lock:
        ent = {"builder": label, "sha": _entry_sha(sig, label)}
        if poison:
            # the injector's ``corrupt`` kind: persist a WRONG content
            # hash — the next process's arm-time validation must drop
            # the entry (clean miss → recompile), never trust it
            ent["sha"] = "0" * 16
            _record("corrupt", "poisoned_manifest")
        st["manifest"][sig] = ent
        _atomic_json(os.path.join(st["path"], "manifest.json"),
                     st["manifest"])


def expected_warm() -> int:
    """Hash-valid warm-manifest entries adopted at arm time — the
    relaunch path's rewarm population (docs/serving.md cold/warm)."""
    st = _ensure_dir()
    return 0 if st is None else len(st["manifest"])


# ---------------------------------------------------------------------------
# the guarded compile path
# ---------------------------------------------------------------------------

def _record(kind: str, action: str) -> None:
    from . import recovery
    recovery._record(SITE, kind, action)


def _sig_hash(label: str, args, kwargs) -> str:
    """Deterministic cross-process signature of a guarded compile:
    builder label + the (shape, dtype) leaf walk the retrace sentinel
    uses — rank-uniform (shapes are SPMD-uniform) and stable across
    relaunches, so a predecessor's intent/quarantine entries match."""
    from ..analysis.runtime import _signature
    return hashlib.sha1(
        repr((label, _signature(args, kwargs))).encode()).hexdigest()[:16]


def _watchdog(label: str, sig: str, thunk, stalled: bool):
    """Run a compile thunk under the compile watchdog: the exchange
    watchdog's worker-thread + bounded-join pattern, surfacing typed
    :class:`CompileTimeoutError` instead of RankDesyncError."""
    t = float(getattr(config, "COMPILE_TIMEOUT_S", 0) or 0)
    if stalled and t <= 0:
        t = 2.0   # injected stall must surface typed even unconfigured
    if t <= 0:
        return thunk()
    box: dict = {}
    builder = getattr(_tls, "launching", None)

    def run():
        launching(builder)     # the compile fires on THIS thread
        if stalled:
            time.sleep(4 * max(t, 0.5))   # simulated hung compiler
            return
        try:
            box["value"] = thunk()
        except BaseException as e:  # noqa: BLE001 — re-raised on caller
            box["error"] = e

    th = threading.Thread(target=run, daemon=True,
                          name=f"cylon-compile-watchdog-{label}")
    th.start()
    th.join(t)
    if "error" in box:
        raise box["error"]
    if "value" not in box:
        _TIMEOUTS.inc()
        _record("stall", "watchdog")
        raise CompileTimeoutError(
            f"compile watchdog: {label} did not finish lowering/compiling "
            f"within {t:g}s — the compiler is hung", site=SITE,
            signature=sig)
    return box["value"]


def _lifecycle(label: str, thunk, args, kwargs):
    """One guarded compile: quarantine check → intent journal →
    injector probe → watchdog-bounded build → clear intent → manifest.
    Only reached for the FIRST call of each signature while armed."""
    from . import recovery
    sig = _sig_hash(label, args, kwargs)
    with _lock:
        fresh = sig not in _SEEN
    if not fresh:
        return thunk()
    st = _ensure_dir()
    with _lock:
        bad = sig in _DIR_STATE["quarantine"]
    if bad:
        _record("quarantined", "raised")
        raise CompileQuarantinedError(
            f"compile signature {sig} of {label} is quarantined: a "
            "predecessor process died mid-compile on this exact shape "
            "(orphaned compile intent) — re-plan at a different capacity "
            "instead of re-crashing", site=SITE, signature=sig)
    kind = None
    if st is not None:
        _write_intent(label, sig)
    try:
        # kill fires HERE — after the intent hit disk, the honest
        # mid-compile crash the quarantine exists for
        kind = recovery.maybe_inject(SITE, intercept=("corrupt", "stall"))
        out = _watchdog(label, sig, thunk, stalled=(kind == "stall"))
    finally:
        if st is not None:
            _clear_intent()
    with _lock:
        _SEEN.add(sig)
    if st is not None:
        _manifest_add(label, sig, poison=(kind == "corrupt"))
    return out


def _label(fun) -> str:
    mod = getattr(fun, "__module__", "") or ""
    name = getattr(fun, "__qualname__", None) \
        or getattr(fun, "__name__", None) or "jit"
    return f"{mod}.{name}" if mod else str(name)


class _Program:
    """Facade-wrapped jitted program: transparent passthrough while the
    lifecycle is unarmed (one bool check per call); armed, the first
    call of each shape signature runs the guarded compile path.
    Attribute access (``lower`` etc.) forwards to the jax program."""

    # __weakref__: jax weakrefs callables it is handed during tracing —
    # a slotted wrapper without the slot dies with "cannot create weak
    # reference" the first time a program nests inside another trace
    __slots__ = ("_fn", "_facade_label", "_pinned", "__weakref__")

    def __init__(self, fn, label: str, pinned: bool = False):
        self._fn = fn
        self._facade_label = label
        self._pinned = pinned

    def __call__(self, *args, **kwargs):
        if self._pinned or not armed():
            return self._fn(*args, **kwargs)
        return _lifecycle(self._facade_label,
                          lambda: self._fn(*args, **kwargs), args, kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def jit(fun=None, pinned: bool = False, **kw):
    """The facade's ``jax.jit``: identical signature/semantics, but the
    returned program's compiles ride the lifecycle (ledger, journal,
    watchdog, quarantine).  ``pinned=True`` marks consensus-wire
    programs (exec/recovery): they bypass the guarded path entirely —
    injecting a fault into (or evicting) the wire would break the very
    mechanism that coordinates recovery.  Usable as ``jit(fn, ...)`` or
    ``@partial``-style ``jit(static_argnums=...)`` decorator."""
    if fun is None:
        return functools.partial(jit, pinned=pinned, **kw)
    _install_listener()
    from ..utils import cache
    named = cache.named_for_device(fun, getattr(cache._building, "name", None))
    return _Program(jax.jit(named, **kw), _label(fun), pinned=pinned)


def _unwrap_program(fn):
    """Peel the retrace sentinel's ``tagged[...]`` wrapper and the cache
    layer's lazy proxy down to the facade program (or a raw jitted
    callable).  Bounded — never walks ``jax.jit``'s own ``__wrapped__``
    (that is the plain Python function, which cannot ``.lower``)."""
    from ..utils.cache import _LazyJit
    for _ in range(8):
        if isinstance(fn, _LazyJit):
            fn = fn._resolve()
        elif isinstance(fn, _Program):
            return fn
        elif (getattr(fn, "__name__", "").startswith("tagged[")
                and hasattr(fn, "__wrapped__")):
            fn = fn.__wrapped__
        else:
            break
    return fn


def aot_compile(fn, *args, **kwargs):
    """AOT ``fn.lower(*args).compile()`` under the lifecycle guard —
    the sanctioned prewarm path (TS117).  Accepts a facade
    :class:`_Program`, the cache layer's lazy proxy, a sentinel-tagged
    program, or a raw jitted callable."""
    fn = _unwrap_program(fn)
    target = fn._fn if isinstance(fn, _Program) else fn
    label = (fn._facade_label if isinstance(fn, _Program)
             else _label(target))

    def thunk():
        return target.lower(*args, **kwargs).compile()

    if not armed():
        return thunk()
    return _lifecycle(label + ".aot", thunk, args, kwargs)


# ---------------------------------------------------------------------------
# the bounded compile ledger (fed by utils/cache.program_cache)
# ---------------------------------------------------------------------------

def _prune_locked() -> None:
    dead = [k for k, (ref, key) in _LEDGER.items()
            if ref() is None or key not in (ref() or {})]
    for k in dead:
        del _LEDGER[k]


def live_programs() -> int:
    """Live compiled programs across every mesh's program tables — the
    ``compile_programs_live`` gauge read callback."""
    with _lock:
        _prune_locked()
        return len(_LEDGER)


metrics.gauge("compile_programs_live",
              help="live compiled programs across all program_cache "
                   "tables (facade ledger)", fn=live_programs)


def on_hit(mesh, name: str, key) -> None:
    """program_cache hit hook (utils/cache wrapper, outside its lock)."""
    _HIT.inc()
    ekey = (id(mesh), name, key)
    with _lock:
        if ekey in _LEDGER:
            _LEDGER.move_to_end(ekey, last=True)


def on_insert(mesh, name: str, key, lru) -> None:
    """program_cache miss/insert hook: append to the ledger and enforce
    the ``CYLON_TPU_COMPILE_BUDGET`` per-mesh bound.  Called OUTSIDE the
    cache lock (lock order: cache._lock before compiler._lock); the
    consensus vote for multiprocess eviction counts runs here too —
    never under either lock's critical build path (the wire programs
    are pinned and the TLS guard breaks re-entrancy)."""
    _MISS.inc()
    mk = id(mesh)
    with _lock:
        _LEDGER[(mk, name, key)] = (weakref.ref(lru), key)
        _LEDGER.move_to_end((mk, name, key), last=True)
    budget = int(getattr(config, "COMPILE_BUDGET", 0) or 0)
    if budget <= 0 or getattr(_tls, "in_evict", False):
        return
    with _lock:
        _prune_locked()
        over = sum(1 for k in _LEDGER if k[0] == mk) - budget
    if over <= 0:
        return
    if jax.process_count() > 1:
        from . import recovery
        _tls.in_evict = True
        try:
            # every rank inserts at the same program point (SPMD
            # builders), so the vote is symmetric; max-agree the count
            # so a straggling GC on one rank can't desync the drops
            over = recovery.count_consensus(mesh, over)
        finally:
            _tls.in_evict = False
    if over > 0:
        _evict(mk, over)


def _evict(mesh_key: int, n: int) -> None:
    """Retire the ``n`` least-recently-used non-pinned programs of one
    mesh: pop them from their per-builder LRUs (re-use recompiles).
    Lock order: cache._lock first, compiler._lock second — the same
    order the program_cache wrapper's table hook uses."""
    from ..utils import cache as _cache
    removed = 0
    with _cache._lock:
        with _lock:
            for ekey in list(_LEDGER):
                if removed >= n:
                    break
                mk, name, key = ekey
                if mk != mesh_key or \
                        name.startswith(_PINNED_PREFIXES):
                    continue
                ref, _k = _LEDGER.pop(ekey)
                lru = ref()
                if lru is not None:
                    lru.pop(key, None)
                removed += 1
    if removed:
        _EVICT.inc(removed)
        from ..utils import timing
        timing.bump("compile.ledger_evict")


def on_builder_evict(mesh, name: str, keys) -> None:
    """Per-builder LRU overflow hook: the wrapper popped ``keys`` past
    ``config.PROGRAM_CACHE_SIZE`` — keep the ledger exact and count."""
    mk = id(mesh)
    with _lock:
        for key in keys:
            _LEDGER.pop((mk, name, key), None)
    _EVICT.inc(len(keys))


def on_table_evict(mesh_key: int, n_programs: int) -> None:
    """MESH_TABLE_LIMIT hook: a whole mesh's program table was cleared
    by utils/cache (previously silent).  Called UNDER cache._lock —
    taking compiler._lock second matches the global lock order."""
    _MESH_EVICT.inc()
    if n_programs:
        _EVICT.inc(n_programs)
    with _lock:
        for ekey in [k for k in _LEDGER if k[0] == mesh_key]:
            del _LEDGER[ekey]


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

def stats() -> dict:
    """The facade's counter block — surfaced in the serving summary
    (exec/scheduler.stats) and obs.bench_detail."""
    return {
        "programs_live": live_programs(),
        "cache_hits": _HIT.value,
        "cache_misses": _MISS.value,
        "cache_evictions": _EVICT.value,
        "mesh_table_evictions": _MESH_EVICT.value,
        "compile_seconds": round(float(_SECONDS.value), 6),
        "compile_events": _EVENTS.value,
        "by_builder": {b: {"seconds": round(float(_SECONDS_BY.get(b, 0.0)),
                                            6),
                           "events": int(_EVENTS_BY[b])}
                       for b in sorted(_EVENTS_BY)},
        "quarantined": len(_DIR_STATE["quarantine"]),
        "quarantine_adoptions": _QUARANTINED.value,
        "watchdog_timeouts": _TIMEOUTS.value,
        "manifest_drops": _MANIFEST_DROPS.value,
        "expected_warm": (len(_DIR_STATE["manifest"])
                          if _DIR_STATE["path"] else 0),
    }


def reset_stats() -> None:
    """Zero the facade counters and the in-process seen-set (bench
    iterations; the persistent dir state is untouched)."""
    for c in (_HIT, _MISS, _EVICT, _MESH_EVICT, _SECONDS, _EVENTS,
              _QUARANTINED, _TIMEOUTS, _MANIFEST_DROPS):
        c.reset()
    with _lock:
        _SEEN.clear()
        _SECONDS_BY.clear()
        _EVENTS_BY.clear()
