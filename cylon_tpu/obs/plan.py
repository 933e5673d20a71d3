"""Query plan profiler — EXPLAIN / EXPLAIN ANALYZE for the engine.

Every distributed operator entry point (relational/join, groupby, sort,
setops, repart, exec/pipeline, stream/) pushes a typed :class:`PlanNode`
onto a QUERY-SCOPED context while it runs — operator name, keys, the
route it chose (broadcast vs hash vs skew-split vs pipelined), chunk
counts, piece caps, spill/donation flags.  With no profile active the
whole facade is one thread-local load per operator call: no node, no
allocation, no timing, no device work (the PR 10 overhead contract,
asserted in tests/test_explain.py) — beside the operator call's own
boundary span ``cylon.op.<op>`` (``utils/timing.span``: one
``TraceAnnotation``, no clock read with nothing armed; tests/test_obs.py),
which is how a trace or the flight recorder's ring knows where an
operator call begins and ends.

:func:`explain` runs a query and returns the static tree;
:func:`explain_analyze` additionally attaches measurements per node:

* **seconds** — a node-scoped ``utils/timing`` attribution scope (the
  same mechanism as the serving tier's per-session scopes, PR 7): each
  node's scope is innermost while the node runs, so node phase tables
  are SELF times (exclusive of children) by construction, and their
  per-region sums reconcile with the process-global phase table — the
  invariant ``QueryPlan.reconcile`` checks and tests assert.  The
  ``.block`` suffix convention (``timing.sync_region``) splits each
  node into dispatch vs block seconds.
* **rows in/out** — from the host-known valid-count sidecars (no sync).
* **bytes/rows exchanged** — recorded by ``parallel/shuffle.exchange``
  into the innermost node; with the comm matrix armed
  (``CYLON_TPU_COMM_MATRIX=1``, obs/comm) the per-(src,dst) matrix
  accumulates alongside.
* **events** — spill/recovery/checkpoint counter deltas over the node's
  window (inclusive of children; the registry counters are global).
* **heavy hitters** — a Misra-Gries top-K sketch (obs/sketch) over
  sampled key values, piggybacking on the sort-splitter sampling
  machinery (``relational/common.sample_keys``, an evenly-spaced
  per-shard device sample like ``relational/sort._sample_fn``), with an
  estimated max-rank share — the ROADMAP item 2 detection baseline.

The ONLY sanctioned way to create plan nodes is this module's
:func:`node` context manager (plus :func:`annotate` for attributes
discovered mid-operator).  A direct ``push_node``/``pop_node`` call in
``relational/``, ``exec/`` or ``stream/`` is lint rule **TS113**
(docs/trace_safety.md): an unbalanced push leaves every later query's
tree reparented under a dead node.
"""

from __future__ import annotations

import contextlib
import threading

from ..utils import timing as _timing

__all__ = ["PlanNode", "QueryPlan", "node", "annotate", "active",
           "current", "explain", "explain_analyze", "record_exchange",
           "profile_keys", "key_profile", "render_tree"]

#: default Misra-Gries capacity for per-node key profiles
SKETCH_K = 16

_TLS = threading.local()


def _profile():
    return getattr(_TLS, "profile", None)


def active() -> bool:
    """A query profile is collecting on this thread (one TLS load)."""
    return getattr(_TLS, "profile", None) is not None


class PlanNode:
    """One operator invocation in a query's plan tree."""

    __slots__ = ("op", "attrs", "children", "rows_in", "rows_out",
                 "rows_exchanged", "bytes_exchanged", "exchanges",
                 "phases", "dispatch_s", "block_s", "seconds", "events",
                 "heavy", "_scope", "_scope_cm", "_ev0")

    def __init__(self, op: str, attrs: dict):
        self.op = op
        self.attrs = dict(attrs)
        self.children: list[PlanNode] = []
        self.rows_in = None
        self.rows_out = None
        self.rows_exchanged = 0
        self.bytes_exchanged = 0
        self.exchanges: list[dict] = []
        self.phases = None          # self-time region table (analyze)
        self.dispatch_s = None
        self.block_s = None
        self.seconds = None         # self seconds (exclusive of children)
        self.events = None          # counter deltas (inclusive window)
        self.heavy = None           # Misra-Gries key profile
        self._scope = None
        self._scope_cm = None
        self._ev0 = None

    def __bool__(self) -> bool:
        return True

    def set(self, **kw) -> None:
        """Set measured fields (``rows_in``/``rows_out``) or extend
        ``attrs`` — the operator-facing write API."""
        for k, v in kw.items():
            if k in ("rows_in", "rows_out"):
                setattr(self, k, int(v))
            else:
                self.attrs[k] = v

    def annotate(self, **attrs) -> None:
        self.attrs.update(attrs)

    # -- reporting --------------------------------------------------------
    def total_seconds(self) -> float:
        """Inclusive seconds: self + children."""
        own = self.seconds or 0.0
        return own + sum(c.total_seconds() for c in self.children)

    def total_bytes_exchanged(self) -> int:
        return self.bytes_exchanged \
            + sum(c.total_bytes_exchanged() for c in self.children)

    def static_dict(self) -> dict:
        """The measurement-free tree — two runs of the same query must
        produce IDENTICAL static dicts (asserted in tests)."""
        return {"op": self.op,
                "attrs": {k: self.attrs[k] for k in sorted(self.attrs)},
                "children": [c.static_dict() for c in self.children]}

    def to_dict(self) -> dict:
        out = {"op": self.op,
               "attrs": {k: self.attrs[k] for k in sorted(self.attrs)}}
        if self.rows_in is not None:
            out["rows_in"] = self.rows_in
        if self.rows_out is not None:
            out["rows_out"] = self.rows_out
        if self.rows_exchanged:
            out["rows_exchanged"] = self.rows_exchanged
            out["bytes_exchanged"] = self.bytes_exchanged
        if self.seconds is not None:
            out["self_s"] = round(self.seconds, 6)
            out["dispatch_s"] = round(self.dispatch_s, 6)
            out["block_s"] = round(self.block_s, 6)
            out["total_s"] = round(self.total_seconds(), 6)
        if self.phases:
            out["phases"] = self.phases
        if self.events:
            out["events"] = self.events
        if self.heavy is not None:
            out["heavy_hitters"] = self.heavy
        out["children"] = [c.to_dict() for c in self.children]
        return out


class _NoopNode:
    """The unarmed stand-in: falsy, swallows every write."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def set(self, **kw) -> None:
        pass

    def annotate(self, **attrs) -> None:
        pass


_NOOP = _NoopNode()


class QueryPlan:
    """The result of :func:`explain` / :func:`explain_analyze`."""

    def __init__(self, mode: str):
        self.mode = mode            # "explain" | "analyze"
        self.roots: list[PlanNode] = []
        self.result = None          # the profiled callable's return value
        self.global_phases: dict = {}
        self.global_seconds = 0.0   # the phase table's unrounded total
        self.comm: dict | None = None

    def static_dict(self) -> dict:
        return {"mode": "explain",
                "roots": [r.static_dict() for r in self.roots]}

    def to_dict(self) -> dict:
        out = {"mode": self.mode,
               "roots": [r.to_dict() for r in self.roots]}
        if self.mode == "analyze":
            out["global_phases"] = self.global_phases
            out["reconcile"] = self.reconcile()
        if self.comm is not None:
            out["comm_matrix"] = self.comm
        return out

    def render(self) -> str:
        return render_tree(self.to_dict())

    def reconcile(self) -> dict:
        """The analyze invariant: per-region seconds summed over every
        node's SELF table must equal the process-global phase table
        accumulated over the run (both tables saw the identical region
        durations; only the grouping differs, so equality holds to fp
        summation order: the totals are sums of UNROUNDED seconds,
        rounded once — the tables' own entries are rounded to 1e-4 each).
        Regions fired outside any node land in ``unattributed_s``."""
        per_name: dict = {}
        node_s = 0.0

        def walk(n: PlanNode):
            nonlocal node_s
            node_s += n.seconds or 0.0
            for k, v in (n.phases or {}).items():
                per_name[k] = per_name.get(k, 0.0) + v["s"]
            for c in n.children:
                walk(c)

        for r in self.roots:
            walk(r)
        glob_s = self.global_seconds
        return {"node_s": round(node_s, 6),
                "phase_s": round(glob_s, 6),
                "unattributed_s": round(glob_s - node_s, 6),
                "per_phase_node_s": {k: round(v, 6)
                                     for k, v in sorted(per_name.items())}}


# ---------------------------------------------------------------------------
# the context-manager facade (the ONLY sanctioned push/pop caller — TS113)
# ---------------------------------------------------------------------------

def push_node(op: str, attrs: dict, prof: QueryPlan) -> PlanNode:
    """INTERNAL — create a node, attach it to the current parent and make
    it current.  Only :func:`node` may call this (lint rule TS113): an
    unbalanced push corrupts every later query's tree."""
    n = PlanNode(op, attrs)
    stack = getattr(_TLS, "stack", None)
    if stack is None:
        stack = _TLS.stack = []
    if stack:
        stack[-1].children.append(n)
    else:
        prof.roots.append(n)
    stack.append(n)
    return n


def pop_node(n: PlanNode) -> None:
    """INTERNAL — the balanced inverse of :func:`push_node` (TS113)."""
    stack = getattr(_TLS, "stack", None)
    if stack and stack[-1] is n:
        stack.pop()


def current() -> PlanNode | None:
    stack = getattr(_TLS, "stack", None)
    return stack[-1] if stack else None


def annotate(**attrs) -> None:
    """Merge attributes into the CURRENT node (route decisions made deep
    inside an operator, where the node handle is out of scope).  No-op
    without an active profile."""
    if getattr(_TLS, "profile", None) is None:
        return
    n = current()
    if n is not None:
        n.annotate(**attrs)


def _event_counters() -> tuple:
    from ..exec import recovery
    from . import metrics
    return (metrics.counter("memory_spill_events").value,
            metrics.counter("ckpt_checkpoint_events").value,
            len(recovery.recovery_events()))


class _NodeCtx:
    """The per-operator context manager.  Always: the operator call is a
    ``cylon.op.<op>`` boundary span of the program's own
    (``utils/timing.span``: one ``TraceAnnotation`` with nothing armed, no
    clock read), nested operators open their own and a reader takes the
    outermost; it closes whatever the operator raised.  With a profile
    active besides: push + (analyze mode) a node-scoped attribution scope
    whose table becomes the node's self-time phase breakdown."""

    __slots__ = ("_op", "_attrs", "_node", "_prof", "_span", "_live")

    def __init__(self, op: str, attrs: dict):
        self._op = op
        self._attrs = attrs
        self._node = None
        self._prof = None
        self._span = None

    def span_args(self, **args) -> None:
        """Arguments of the open ``cylon.op.<op>`` span that the operator
        learns while it runs (``rows_out`` of a filter): on the profiler's
        span and in the flight recorder's, like those given at entry
        (``utils/timing.set_args``, which the phase regions use too: the
        sort's ``recv_max`` / ``recv_cap``)."""
        _timing.set_args(self._live, **args)

    def __enter__(self):
        self._span = _timing.span("op." + self._op)
        self._live = self._span.__enter__()
        prof = getattr(_TLS, "profile", None)
        if prof is None:
            return _NOOP
        self._prof = prof
        n = self._node = push_node(self._op, self._attrs, prof)
        if prof.mode == "analyze":
            n._scope_cm = _timing.attribution_scope(f"plan:{self._op}")
            n._scope = n._scope_cm.__enter__()
            n._ev0 = _event_counters()
        return n

    def __exit__(self, exc_type, exc, tb):
        try:
            if self._node is not None:
                self._close_node(exc_type, exc, tb)
        finally:
            self._span.__exit__(exc_type, exc, tb)
        return False

    def _close_node(self, exc_type, exc, tb) -> None:
        n = self._node
        if n._scope_cm is not None:
            n._scope_cm.__exit__(exc_type, exc, tb)
            sc, n._scope, n._scope_cm = n._scope, None, None
            n.phases = sc.snapshot()
            n.seconds = sc.total_seconds()
            dispatch, block = _timing.split_snapshot(n.phases)
            n.dispatch_s = sum(dispatch.values())
            n.block_s = sum(block.values())
            ev1 = _event_counters()
            n.events = {k: max(b - a, 0) for k, (a, b) in zip(
                ("spill_events", "checkpoint_events", "recovery_events"),
                zip(n._ev0, ev1))}
            # a session (serving) scope enclosing the whole profile must
            # not lose this node's seconds to the shadowing node scope —
            # absorb each node's SELF table into it exactly once
            outer = getattr(self._prof, "_outer", None)
            if outer is not None:
                outer.absorb(sc)
        pop_node(n)


def node(op: str, **attrs) -> _NodeCtx:
    """Open a plan node for one operator invocation::

        with plan.node("join", how=how, on=tuple(left_on)) as pn:
            ...
            if pn:
                pn.set(rows_out=out.row_count)

    Yields the :class:`PlanNode` (truthy) with a profile active, or a
    falsy no-op stand-in otherwise — call sites guard their bookkeeping
    on ``if pn:`` so the unarmed path computes nothing."""
    return _NodeCtx(op, attrs)


# ---------------------------------------------------------------------------
# exchange + key-profile recording (called from the engine)
# ---------------------------------------------------------------------------

def record_exchange(counts, row_bytes: int, site: str = "exchange",
                    tiers: dict | None = None) -> None:
    """Attach one exchange's totals to the innermost plan node, and —
    ONLY with the comm matrix explicitly armed — accumulate its
    per-(src,dst) matrix.  Called by ``parallel/shuffle.exchange`` only
    when a profile is active or the comm matrix is armed (the caller
    guards, so the happy path never reaches here).  A profile alone must
    NOT touch the comm module's cumulative state: an unarmed
    explain/explain_analyze would otherwise leave exchanges behind that
    a later ARMED session's report() serves, breaking its
    totals-equal-the-exchange-counters invariant (and, cross-rank, its
    byte-identity check when ranks profiled different queries before
    arming — regression test in tests/test_explain.py).

    ``tiers`` (multi-slice topologies only, cylon_tpu/topo): the
    engine-computed tier attribution — per-rank slice ids, the route
    that carried the exchange, and each tier's padded wire volume — fed
    through to :func:`cylon_tpu.obs.comm.record`'s ICI/DCN split."""
    import numpy as np
    from . import comm
    rows = int(np.asarray(counts).sum())
    nbytes = rows * int(row_bytes)
    if comm.armed():
        comm.record(counts, row_bytes, site=site, tiers=tiers)
    n = current()
    if n is not None:
        n.rows_exchanged += rows
        n.bytes_exchanged += nbytes
        ent = {"site": site, "rows": rows, "bytes": nbytes}
        if tiers is not None:
            ent["route"] = tiers["route"]
        n.exchanges.append(ent)


def profile_keys(pn, table, key_names, k: int = SKETCH_K) -> None:
    """Sample ``table``'s key columns (the sort-splitter sampling path:
    evenly spaced per-shard positions, shard-weighted) and attach a
    Misra-Gries heavy-hitter profile to node ``pn``.  Analyze-mode
    operators call this with their (falsy-when-unarmed) node, so the
    unarmed path is one truthiness check."""
    if not pn:
        return
    prof = _profile()
    if prof is None or prof.mode != "analyze" \
            or not getattr(prof, "keys_enabled", True):
        return
    pn.heavy = key_profile(table, key_names, k=k)


def key_profile(table, key_names, k: int = SKETCH_K,
                m: int | None = None) -> dict | None:
    """Standalone heavy-hitter profile of ``table``'s key columns —
    what a driver reports for a Zipf key column.  Returns
    None for empty tables.  ``est_max_rank_share`` is the estimated
    fraction of rows the hottest rank would receive under plain hash
    partitioning: the top key's share plus a uniform spread of the
    rest — the imbalance ROADMAP item 2's splitter will be judged
    against.  ``est_rows_per_rank`` places each tracked key on its
    ACTUAL partition (``ops/hashing.partition_of`` over the sampled
    routing hash — the exact shuffle predicate) and spreads the
    untracked residue uniformly: the per-rank row histogram the CURRENT
    partitioner would produce, which is what ``scripts/explain.py``
    diffs against a split plan's balanced layout to answer "why this
    plan" (docs/skew.md)."""
    import numpy as np

    from .sketch import MisraGries
    from ..ops.hashing import partition_of
    from ..relational.common import sample_keys

    key_names = [key_names] if isinstance(key_names, str) else list(key_names)
    sampled = sample_keys(table, key_names, m=m, with_hashes=True)
    if sampled is None:
        return None
    values, weights, total_rows, hashes = sampled
    mg = MisraGries(k=k)
    mg.update(values, weights)
    w = table.env.world_size
    shares = mg.shares()
    heavy = [{"key": kv, "share": round(sh, 6), "err": round(err, 6)}
             for kv, sh, err in shares if sh > max(err, 1.0 / (2 * k))]
    top = shares[0][1] if shares else 0.0
    covered = min(sum(sh for _, sh, _ in shares), 1.0)
    # identity -> routing hash (first sampled occurrence); tracked keys
    # land on partition_of(hash), the residue spreads uniformly
    id2hash = {}
    for v, h in zip(values.tolist(), hashes.tolist()):
        id2hash.setdefault(v, int(h))
    per_rank = np.full(w, (1.0 - covered) / w * total_rows)
    for kv, sh, _err in shares:
        h = id2hash.get(kv)
        if h is None:           # decayed out of the sample window
            per_rank += sh * total_rows / w
        else:
            per_rank[partition_of(h, w)] += sh * total_rows
    return {
        "keys": key_names,
        "sampled": int(len(values)),
        "rows": int(total_rows),
        "k": k,
        "heavy": heavy,
        "max_key_share": round(top, 6),
        "est_max_rank_share": round(top + max(1.0 - covered, 0.0) / w, 6),
        "est_rows_per_rank": [int(round(x)) for x in per_rank],
    }


# ---------------------------------------------------------------------------
# explain / explain_analyze
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _query_profile(mode: str):
    if getattr(_TLS, "profile", None) is not None:
        from ..status import InvalidError
        raise InvalidError("a query profile is already active on this "
                           "thread — explain/explain_analyze do not nest")
    prof = QueryPlan(mode)
    if mode == "analyze":
        from ..utils import timing
        prof._outer = timing._scope()
    _TLS.profile = prof
    _TLS.stack = []
    try:
        yield prof
    finally:
        _TLS.profile = None
        _TLS.stack = []


def explain(fn, *args, **kwargs) -> QueryPlan:
    """Run ``fn(*args, **kwargs)`` with plan collection on: returns the
    STATIC tree (operators, keys, routes, chunking) — no timing scopes,
    no sampling, no counter reads.  The query still executes (plans are
    discovered by running, not parsed)."""
    with _query_profile("explain") as prof:
        prof.result = fn(*args, **kwargs)
    return prof


def explain_analyze(fn, *args, reset_timings: bool = True,
                    profile_keys: bool = True,
                    family: str | None = None, **kwargs) -> QueryPlan:
    """:func:`explain` plus measurements: arms ``config.BENCH_TIMINGS``
    for the duration (restoring the caller's flags), resets the global
    phase table (``reset_timings=False`` to accumulate instead), runs
    the query under per-node attribution scopes, and snapshots the
    global phase table for :meth:`QueryPlan.reconcile`.  With the comm
    matrix armed the report is attached as ``comm_matrix``.

    ``profile_keys=False`` skips the per-node heavy-hitter sampling —
    the one ANALYZE feature that adds device programs and mid-query
    host pulls of its own.  A driver's profiled iteration uses this so
    its phase split stays comparable with an unprofiled
    iteration's and the async-mode
    one-designated-block contract holds.

    ``family`` names the query's admission SHAPE FAMILY: after the run
    the observed peak-ledger bytes are recorded against it
    (:func:`cylon_tpu.exec.scheduler.note_family_peak`), and serving
    sessions submitted with the same ``shape_family`` are admitted at
    ``min(declared, observed_peak x safety_factor)`` — ANALYZE history
    replacing the conservative declared maximum (docs/serving.md)."""
    from .. import config
    from ..utils import timing
    from . import comm

    prev = config.BENCH_TIMINGS
    config.BENCH_TIMINGS = True
    if reset_timings:
        timing.reset()
    if comm.armed():
        comm.reset()
    try:
        with _query_profile("analyze") as prof:
            prof.keys_enabled = bool(profile_keys)
            prof.result = fn(*args, **kwargs)
            prof.global_phases = timing.snapshot()
            prof.global_seconds = timing.total_seconds()
    finally:
        config.BENCH_TIMINGS = prev
    if comm.armed():
        prof.comm = comm.report()
    if family is not None:
        from ..exec import memory, scheduler
        scheduler.note_family_peak(
            family, int(memory.stats()["peak_ledger_bytes"]))
    return prof


# ---------------------------------------------------------------------------
# rendering (shared with scripts/explain.py)
# ---------------------------------------------------------------------------

def _node_line(d: dict) -> str:
    bits = [d["op"]]
    attrs = d.get("attrs") or {}
    if attrs:
        bits.append("[" + " ".join(f"{k}={attrs[k]}"
                                   for k in sorted(attrs)) + "]")
    rio = []
    if "rows_in" in d:
        rio.append(f"rows={d['rows_in']}")
    if "rows_out" in d:
        rio.append(f"out={d['rows_out']}")
    if d.get("bytes_exchanged"):
        rio.append(f"xchg={d['bytes_exchanged']}B")
    if "total_s" in d:
        rio.append(f"self={d['self_s']:.4f}s total={d['total_s']:.4f}s "
                   f"(dispatch {d['dispatch_s']:.4f} / "
                   f"block {d['block_s']:.4f})")
    if rio:
        bits.append("(" + ", ".join(rio) + ")")
    hh = d.get("heavy_hitters")
    if hh and hh.get("heavy"):
        top = hh["heavy"][0]
        bits.append(f"hot[{top['key']}≈{top['share']:.1%}]")
    if hh and hh.get("est_rows_per_rank"):
        # the "why this plan" number (docs/skew.md): the hottest rank's
        # estimated row share under the CURRENT partitioner — what a
        # split plan's balanced layout is judged against
        per = hh["est_rows_per_rank"]
        tot = sum(per) or 1
        hot_r = max(range(len(per)), key=per.__getitem__)
        bits.append(f"rank_max[r{hot_r}≈{per[hot_r] / tot:.1%} of rows]")
    return " ".join(bits)


def render_tree(plan_dict: dict) -> str:
    """ASCII tree of a :meth:`QueryPlan.to_dict` payload (also consumed
    by scripts/explain.py on saved JSON)."""
    lines = [f"query plan ({plan_dict.get('mode', 'explain')})"]

    def walk(d, prefix, last):
        lines.append(prefix + ("└─ " if last else "├─ ") + _node_line(d))
        kids = d.get("children") or []
        for i, c in enumerate(kids):
            walk(c, prefix + ("   " if last else "│  "),
                 i == len(kids) - 1)

    roots = plan_dict.get("roots") or []
    for i, r in enumerate(roots):
        walk(r, "", i == len(roots) - 1)
    rec = plan_dict.get("reconcile")
    if rec:
        lines.append(f"phases: node {rec['node_s']}s / global "
                     f"{rec['phase_s']}s (unattributed "
                     f"{rec['unattributed_s']}s)")
    return "\n".join(lines)
