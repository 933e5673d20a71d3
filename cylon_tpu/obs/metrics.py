"""Typed metrics registry — ONE facade over every counter in the engine.

Before this module, telemetry was scattered: four module-private
``_STATS`` dicts (exec/memory, exec/checkpoint, exec/scheduler,
exec/recovery), a phase table in utils/timing, and four bench scripts
each hand-rolling the collection.  The registry unifies them behind
typed :class:`Counter`/:class:`Gauge`/:class:`Histogram` objects with

* a **Prometheus text exposition** writer (:func:`prometheus_text`) for
  the GKE deploy's scrape endpoint,
* periodic **JSON snapshots** (``CYLON_TPU_METRICS_JSON=path`` +
  ``CYLON_TPU_METRICS_INTERVAL_S``, polled from the serving scheduler's
  baton loop — :func:`maybe_write_snapshot`),
* the shared bench-detail collector (:func:`bench_detail`) the bench
  scripts previously each hand-rolled, and
* **migration shims**: :func:`group` returns a dict-like view whose
  items are registry counters, so the exec modules' ``_STATS[k] += 1``
  call sites (and their public ``stats()`` functions) keep working
  verbatim while the values live here; :func:`namespace` is the
  dynamic-key analog for utils/timing's byte/event attribution.

Overhead contract: a counter bump is one dict-free attribute add; the
snapshot poll is one module-global load when unarmed (the same contract
as the checkpoint tier); nothing here imports jax.  Module-level
mutable counter dicts anywhere else in the package are a lint finding
(TS112, docs/trace_safety.md).
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from collections.abc import MutableMapping

__all__ = [
    "Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
    "group", "namespace", "register_collector", "snapshot",
    "prometheus_text", "write_prometheus", "maybe_write_snapshot",
    "write_snapshot", "bench_detail", "reset",
]


class Counter:
    """Monotonic event count (resettable for bench iterations)."""

    __slots__ = ("name", "help", "value")

    def __init__(self, name: str, help: str = ""):  # noqa: A002
        self.name = name
        self.help = help
        self.value = 0

    def inc(self, n: int = 1) -> None:
        self.value += n

    def set(self, v) -> None:
        """Back-compat for the ``_STATS[k] = 0`` reset idiom (the
        migration shim's __setitem__); new code should use inc/reset."""
        self.value = v

    def reset(self) -> None:
        self.value = 0


class Gauge:
    """Point-in-time value; ``fn`` makes it computed-on-read (e.g. the
    HBM ledger balance), so exposition always reads fresh."""

    __slots__ = ("name", "help", "fn", "_value")

    def __init__(self, name: str, help: str = "", fn=None):  # noqa: A002
        self.name = name
        self.help = help
        self.fn = fn
        self._value = 0

    def set(self, v) -> None:
        self._value = v

    @property
    def value(self):
        if self.fn is not None:
            try:
                return self.fn()
            except Exception:  # noqa: BLE001 — exposition must not raise
                return self._value
        return self._value

    def reset(self) -> None:
        self._value = 0


#: default histogram buckets: latency seconds, ~1ms → ~17min exponential
DEFAULT_BUCKETS = tuple(0.001 * (2 ** i) for i in range(21))

#: raw samples retained per histogram for exact quantiles; past the cap
#: percentile() falls back to bucket interpolation (documented in
#: docs/observability.md — serving benches stay far below it)
SAMPLE_CAP = 65536


class Histogram:
    """Streaming latency histogram with EXACT quantiles at bench scale.

    Bucket counts serve the Prometheus exposition; the raw samples (kept
    up to :data:`SAMPLE_CAP`) serve :meth:`percentile`, which is
    bit-consistent with ``np.percentile`` over the same observations —
    the serving bench's acceptance criterion (its previous sorted-list
    quantiles are exactly this computation).  Past the cap, quantiles
    degrade to linear interpolation inside the containing bucket (and
    :attr:`truncated` reads True so a report can say so)."""

    __slots__ = ("name", "help", "buckets", "bucket_counts", "count",
                 "sum", "_samples", "truncated")

    def __init__(self, name: str, help: str = "",  # noqa: A002
                 buckets=DEFAULT_BUCKETS):
        self.name = name
        self.help = help
        self.buckets = tuple(sorted(buckets))
        self.bucket_counts = [0] * (len(self.buckets) + 1)
        self.count = 0
        self.sum = 0.0
        self._samples: list[float] = []
        self.truncated = False

    def observe(self, x) -> None:
        x = float(x)
        self.count += 1
        self.sum += x
        import bisect
        self.bucket_counts[bisect.bisect_left(self.buckets, x)] += 1
        if len(self._samples) < SAMPLE_CAP:
            self._samples.append(x)
        else:
            self.truncated = True

    def percentile(self, p: float):
        """Quantile at percent ``p`` in [0, 100] — ``np.percentile``
        (linear interpolation) over the retained samples.

        Edge contract (regression-tested in tests/test_obs.py): ``p``
        outside [0, 100] raises typed
        :class:`~cylon_tpu.status.InvalidError`; an EMPTY histogram
        returns ``nan`` (not None — a report can carry it through
        arithmetic and JSON without type forks); a FULLY-truncated one
        (samples observed but none retained, ``SAMPLE_CAP`` exhausted
        before the first observation) returns ``nan`` too — bucket
        interpolation with zero retained samples would fabricate a
        quantile from the bucket grid alone.  Partial truncation keeps
        the documented bucket-interpolation fallback."""
        p = float(p)
        if not 0.0 <= p <= 100.0:
            from ..status import InvalidError
            raise InvalidError(
                f"percentile {p!r} outside [0, 100] on {self.name!r}")
        if not self._samples:
            return float("nan")
        if not self.truncated:
            import numpy as np
            return float(np.percentile(
                np.asarray(self._samples, float), p))
        return self._bucket_percentile(p)

    def _bucket_percentile(self, p: float) -> float:
        target = (p / 100.0) * max(self.count - 1, 0)
        seen = 0
        lo = 0.0
        for i, n in enumerate(self.bucket_counts):
            hi = self.buckets[i] if i < len(self.buckets) else lo * 2 or 1.0
            if n and seen + n > target:
                frac = (target - seen) / n
                return lo + frac * (hi - lo)
            seen += n
            lo = hi
        return lo

    def attainment(self, target) -> float | None:
        """Fraction of observations at or under ``target`` — SLO
        attainment for the serving tier's per-tenant report."""
        if self.count == 0:
            return None
        t = float(target)
        if not self.truncated:
            return sum(1 for x in self._samples if x <= t) / self.count
        under = 0
        for i, n in enumerate(self.bucket_counts):
            if i < len(self.buckets) and self.buckets[i] <= t:
                under += n
        return under / self.count

    @property
    def value(self):
        # the exposition/JSON-snapshot view: NaN quantiles (empty or
        # fully-truncated histogram — the percentile() edge contract)
        # export as None/null, which strict JSON parsers accept where a
        # literal NaN token would be rejected
        def _j(x):
            return None if x != x else x
        return {"count": self.count, "sum": round(self.sum, 6),
                "p50": _j(self.percentile(50)),
                "p99": _j(self.percentile(99))}

    def reset(self) -> None:
        self.bucket_counts = [0] * (len(self.buckets) + 1)
        self.count = 0
        self.sum = 0.0
        self._samples = []
        self.truncated = False


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_METRICS: dict[str, object] = {}
_COLLECTORS: list = []   # callables -> {section: payload} (timing phases)


def _get_or_make(name: str, cls, **kw):
    m = _METRICS.get(name)
    if m is None:
        with _LOCK:
            m = _METRICS.get(name)
            if m is None:
                m = cls(name, **kw)
                _METRICS[name] = m
    if not isinstance(m, cls):
        from ..status import InvalidError
        raise InvalidError(
            f"metric {name!r} already registered as "
            f"{type(m).__name__}, requested {cls.__name__}")
    return m


def counter(name: str, help: str = "", **labels) -> Counter:  # noqa: A002
    """``labels``: one counter a label set, registered under the
    exposition's own spelling of the series, ``name{key="value",...}``
    (keys sorted) - what :func:`snapshot` and :func:`prometheus_text`
    show it as."""
    if labels:
        name += "{" + ",".join(f'{k}="{v}"'
                               for k, v in sorted(labels.items())) + "}"
    return _get_or_make(name, Counter, help=help)


def gauge(name: str, help: str = "", fn=None) -> Gauge:  # noqa: A002
    g = _get_or_make(name, Gauge, help=help)
    if fn is not None:
        g.fn = fn
    return g


def histogram(name: str, help: str = "",  # noqa: A002
              buckets=DEFAULT_BUCKETS) -> Histogram:
    return _get_or_make(name, Histogram, help=help, buckets=buckets)


def register_collector(fn) -> None:
    """Register a callable returning ``{section: payload}`` merged into
    :func:`snapshot` — utils/timing contributes its phase table this way
    without the registry importing it."""
    if fn not in _COLLECTORS:
        _COLLECTORS.append(fn)


def reset(prefix: str = "") -> None:
    """Zero every metric (optionally only names under ``prefix``).
    Registrations survive — handles stay valid, like the exec modules'
    ``reset_stats`` contract."""
    with _LOCK:
        items = list(_METRICS.items())
    for name, m in items:
        if name.startswith(prefix):
            m.reset()


# ---------------------------------------------------------------------------
# migration shims: dict-like views backed by registry counters
# ---------------------------------------------------------------------------

class CounterGroup(MutableMapping):
    """Fixed-key dict-like view over counters ``<prefix>_<key>`` — the
    exec modules' ``_STATS`` tables migrate onto the registry by
    rebinding ``_STATS = metrics.group("ckpt", (...))``: every
    ``_STATS[k] += 1`` site, ``dict(_STATS)`` shim and ``for k in
    _STATS`` reset keeps working verbatim while the values live in (and
    export from) the registry."""

    __slots__ = ("_keys", "_counters")

    def __init__(self, prefix: str, keys):
        self._keys = tuple(keys)
        self._counters = {k: counter(f"{prefix}_{k}") for k in self._keys}

    def __getitem__(self, k):
        return self._counters[k].value

    def __setitem__(self, k, v):
        self._counters[k].set(v)

    def __delitem__(self, k):
        raise TypeError("CounterGroup keys are fixed")

    def __iter__(self):
        return iter(self._keys)

    def __len__(self):
        return len(self._keys)


def group(prefix: str, keys) -> CounterGroup:
    return CounterGroup(prefix, keys)


class Namespace(MutableMapping):
    """Dynamic-key dict-like view over counters ``<prefix>_<key>`` —
    utils/timing's byte attribution (``add_bytes``) migrates onto the
    registry through this: keys appear on first write, ``clear()``
    zeroes (registrations survive)."""

    __slots__ = ("_prefix", "_local")

    def __init__(self, prefix: str):
        self._prefix = prefix
        self._local: dict[str, Counter] = {}

    def _c(self, k) -> Counter:
        c = self._local.get(k)
        if c is None:
            c = self._local[k] = counter(f"{self._prefix}_{k}")
        return c

    def __getitem__(self, k):
        if k not in self._local:
            raise KeyError(k)
        return self._local[k].value

    def get(self, k, default=None):
        c = self._local.get(k)
        return default if c is None else c.value

    def __setitem__(self, k, v):
        self._c(k).set(v)

    def __delitem__(self, k):
        self._local.pop(k).reset()

    def __iter__(self):
        return iter(self._local)

    def __len__(self):
        return len(self._local)

    def clear(self) -> None:
        for c in self._local.values():
            c.reset()
        self._local.clear()


def namespace(prefix: str) -> Namespace:
    return Namespace(prefix)


# ---------------------------------------------------------------------------
# exposition: Prometheus text + JSON snapshots
# ---------------------------------------------------------------------------

_NAME_RE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    n = _NAME_RE.sub("_", name)
    return n if not n[:1].isdigit() else "_" + n


def prometheus_text(prefix: str = "cylon_tpu") -> str:
    """The registry in Prometheus text exposition format (counters,
    gauges, histograms with ``_bucket``/``_sum``/``_count`` series) —
    the GKE deploy serves this from a sidecar file or debug endpoint."""
    out = []
    with _LOCK:   # registrations are concurrent (serving threads)
        items = sorted(_METRICS.items())
    typed = set()
    for name, m in items:
        # a labelled counter is registered as ``family{labels}``
        family, brace, labels = name.partition("{")
        pn = f"{prefix}_{_prom_name(family)}"
        if isinstance(m, Counter):
            if pn not in typed:
                typed.add(pn)
                out.append(f"# TYPE {pn} counter")
            out.append(f"{pn}{brace}{labels} {m.value}")
        elif isinstance(m, Gauge):
            out.append(f"# TYPE {pn} gauge")
            out.append(f"{pn} {m.value}")
        elif isinstance(m, Histogram):
            out.append(f"# TYPE {pn} histogram")
            acc = 0
            for i, b in enumerate(m.buckets):
                acc += m.bucket_counts[i]
                out.append(f'{pn}_bucket{{le="{b:g}"}} {acc}')
            out.append(f'{pn}_bucket{{le="+Inf"}} {m.count}')
            out.append(f"{pn}_sum {m.sum:g}")
            out.append(f"{pn}_count {m.count}")
    return "\n".join(out) + "\n"


def write_prometheus(path: str, prefix: str = "cylon_tpu") -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(prometheus_text(prefix))
    os.replace(tmp, path)


def snapshot() -> dict:
    """Every metric's current value as one JSON-able dict, plus any
    registered collector sections (utils/timing's phase table)."""
    with _LOCK:   # registrations are concurrent (serving threads)
        items = sorted(_METRICS.items())
    out = {name: m.value for name, m in items}
    for fn in _COLLECTORS:
        try:
            out.update(fn())
        except Exception:  # noqa: BLE001 — a broken collector must not
            pass           # take the snapshot down
    return out


def write_snapshot(path: str) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump({"ts": time.time(), "metrics": snapshot()}, f)
    os.replace(tmp, path)


#: [armed_path or "" (= checked, off) or None (= env unread), next_due]
_SNAP: list = [None, 0.0]


def maybe_write_snapshot() -> bool:
    """Periodic JSON snapshot poll (``CYLON_TPU_METRICS_JSON=path``,
    interval ``CYLON_TPU_METRICS_INTERVAL_S``, default 30 s) — called
    from the serving scheduler's baton loop.  Unarmed: one list load
    after the first env read (the happy-path contract)."""
    path = _SNAP[0]
    if path is None:
        path = _SNAP[0] = os.environ.get("CYLON_TPU_METRICS_JSON", "")
    if not path:
        return False
    now = time.monotonic()
    if now < _SNAP[1]:
        return False
    _SNAP[1] = now + float(
        os.environ.get("CYLON_TPU_METRICS_INTERVAL_S", "30"))
    try:
        # the periodic write rides the recovery tier's bounded
        # transient-OSError backoff (a scrape sidecar racing the rename,
        # a briefly-full tmpfs): one flaky write no longer drops a whole
        # interval's telemetry.  Non-transient errnos re-raise
        # immediately into the warn-once fallback below.
        from ..exec.recovery import retry_io
        retry_io(lambda: write_snapshot(path), "obs.snapshot")
    except OSError as e:
        if not _SNAP_WARNED[0]:
            # warn ONCE: the operator armed this path and would
            # otherwise get zero telemetry with zero diagnostics (the
            # same silent-loss mode obs.export surfaces typed for
            # traces); later failures stay quiet — the poll runs in
            # hot loops
            _SNAP_WARNED[0] = True
            from ..utils.logging import log
            log.warning("obs: metrics snapshot to %r failed: %s "
                        "(CYLON_TPU_METRICS_JSON armed but unwritable; "
                        "further failures are silent)", path, e)
        return False
    return True


_SNAP_WARNED = [False]


def _rearm_snapshots() -> None:
    """Re-read the env on the next poll (tests; env changed mid-run)."""
    _SNAP[0] = None
    _SNAP[1] = 0.0
    _SNAP_WARNED[0] = False


_AUTOARMED = [False]


def autoarm() -> None:
    """With ``CYLON_TPU_METRICS_JSON`` set, register an atexit final
    snapshot (called at package import): entrypoints that never reach a
    periodic poll site — the serving scheduler's baton loop, the
    pipelined piece loop — still emit the end-of-run snapshot the
    scrape sidecar reads.  No env var: nothing happens."""
    if _AUTOARMED[0] or not os.environ.get("CYLON_TPU_METRICS_JSON"):
        return
    _AUTOARMED[0] = True
    import atexit

    def _final_snapshot() -> None:
        path = os.environ.get("CYLON_TPU_METRICS_JSON")
        if path:
            try:
                write_snapshot(path)
            except OSError:
                pass   # exit path: never raise
    atexit.register(_final_snapshot)


# ---------------------------------------------------------------------------
# the shared bench-detail collector
# ---------------------------------------------------------------------------

#: the spill-counter selection (exec/memory.stats keys) — the
#: disk-tier pair (``disk_events``/``bytes_to_disk``) rides along so a
#: bench number always says whether it was achieved HBM-resident,
#: host-spilled, or out-of-core (docs/robustness.md "Disk tier & scan
#: pushdown")
BENCH_SPILL_KEYS = ("spill_events", "bytes_spilled", "peak_ledger_bytes",
                    "donated_bytes_reused", "disk_events", "bytes_to_disk")
#: the durable-checkpoint counters every bench JSON carries
BENCH_CKPT_KEYS = ("checkpoint_events", "bytes_checkpointed",
                   "resume_fast_forwarded_pieces", "resume_resharded_pieces",
                   "resume_world_mismatch")
#: the compile-lifecycle counters (exec/compiler.stats) every bench JSON
#: carries — a bench number always says how many executables were live,
#: how much wall-clock went to XLA, and whether the run re-used or
#: rebuilt its program family (docs/robustness.md "Compile lifecycle")
BENCH_COMPILE_KEYS = ("programs_live", "cache_hits", "cache_misses",
                      "cache_evictions", "compile_seconds")
#: the data-integrity audit counters (exec/integrity.stats) every bench
#: JSON carries — a bench number always says whether the audit tier was
#: armed (nonzero fingerprint checks ⇒ its ≤10% overhead is included in
#: the measurement) and whether it fired (docs/robustness.md "Integrity
#: audit tier")
BENCH_AUDIT_KEYS = ("conservation_checks", "fingerprint_checks",
                    "violations")


def bench_detail(*, spill_keys=BENCH_SPILL_KEYS, ckpt_keys=BENCH_CKPT_KEYS,
                 compile_keys=BENCH_COMPILE_KEYS,
                 audit_keys=BENCH_AUDIT_KEYS,
                 events: str | None = "drain", plan=None) -> dict:
    """The counter block every bench script previously hand-rolled:
    recovery events (``events="drain"`` empties the log, as a driver
    wants; ``"keep"`` reads without draining; ``None`` omits),
    the selected spill-tier counters (exec/memory.stats) and the
    selected checkpoint counters (exec/checkpoint.stats).  Key names
    are exactly the stats() keys — the bench JSONs' schema is asserted
    stable in tests/test_obs.py.

    ``plan``: a :class:`~cylon_tpu.obs.plan.QueryPlan` (or an already
    rendered dict) adds a ``plan`` section — the EXPLAIN/ANALYZE tree
    the bench drivers emit alongside the phase table (absent by
    default, so unprofiled schemas are unchanged)."""
    from ..exec import checkpoint, compiler, memory, recovery
    out: dict = {}
    if events == "drain":
        out["recovery_events"] = recovery.drain_events()
    elif events == "keep":
        out["recovery_events"] = recovery.recovery_events()
    mem = memory.stats()
    out.update({k: mem[k] for k in spill_keys})
    ck = checkpoint.stats()
    out.update({k: ck[k] for k in ckpt_keys})
    if compile_keys:
        comp = compiler.stats()
        out["compile"] = {k: comp[k] for k in compile_keys}
    if audit_keys:
        from ..exec import integrity
        au = integrity.stats()
        out["audit"] = {k: au[k] for k in audit_keys}
    if plan is not None:
        out["plan"] = plan.to_dict() if hasattr(plan, "to_dict") else plan
    return out
