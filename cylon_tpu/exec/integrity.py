"""End-to-end data-integrity audit tier — THE facade (lint rule TS118).

Every at-rest byte in the engine is sha256-verified (spill pages, disk
tier, checkpoint pages, the compile cache), but data IN FLIGHT — through
:func:`cylon_tpu.parallel.shuffle.exchange`, the two-hop topo route,
skew-split stitches and piece-loop partials — historically had no
runtime integrity story: a wrong-route bug, a miscounted sidecar or a
corrupted buffer produced a silently wrong answer, which the "never a
wrong answer" contract forbids.  This module is that story, in three
layers, each inert until armed:

1. **Conservation laws (always on).**  Every exchange already pulls the
   (W, W) count sidecar to the host; :func:`conserve_exchange` asserts
   rows-sent == rows-received per (src, dst) — non-negative counts,
   column sums equal to the returned per-destination vector, the grand
   total equal to the logical row total — and reconciles the running
   totals against the ``exchange_rows_total``/``exchange_bytes_total``
   registry counters.  Pure host arithmetic on an already-pulled array:
   zero extra device work, zero syncs, zero collectives.  The two-hop
   route adds :func:`conserve_hops` over its hop count matrices.

2. **Order-invariant fingerprints (``CYLON_TPU_AUDIT=1``).**  A
   registered jaxpr-gated builder (:func:`_fingerprint_fn`) computes a
   64-bit content fingerprint per mesh: a commutative XOR mix of
   per-row hashes over every key+payload lane (validity bits included,
   padding rows masked to the XOR identity), reduced within each shard
   and folded across the mesh with one ``all_gather`` — so the
   fingerprint is REPLICATED and invariant to row order and row
   placement.  Verified at stage boundaries: post-exchange
   (:func:`verify_exchange` — fingerprint conservation, inputs XOR ==
   outputs XOR), post-stitch for skew-split plans, per absorbed stream
   batch (:func:`audit_table`), and recorded into checkpoint manifests
   (:func:`table_fingerprint`) so a resume audits adopted foreign
   pieces beyond their page shas.  In multiprocess sessions every
   fingerprint rides the double-polarity consensus wire
   (:func:`cylon_tpu.exec.recovery.fingerprint_consensus`) before any
   raise/proceed decision — the rank-coherence invariant.

3. **Recovery.**  A violation raises typed :class:`DataIntegrityError`
   (``site=``, ``phase=``) through the classify path; the ladder's
   ``Code.IntegrityFault`` rung recomputes the affected stage ONCE
   (mirroring the disk-corruption rung) and escalates to a typed abort
   on repeat — corruption degrades to recompute, never a wrong answer.

Overhead contract: the unarmed happy path is the always-on host math
plus one cached env read — zero extra collectives, zero host syncs,
zero writes (asserted by ``scripts/chaos_soak.py --audit``); the armed
path is one extra compiled program + one host pull + one 4-round vote
per audited boundary (≤10 % on the default pipelined CPU config,
``bench_detail``'s ``audit`` block carries the counts).

TS118: fingerprint computation and ``DataIntegrityError`` raises are
THIS module's exclusive business — call sites in ``relational/``,
``parallel/`` and ``topo/`` invoke the verb-named wrappers here
(``conserve_*``, ``verify_*``, ``audit_*``, ``flip_one``) and never
hash, vote or raise themselves (docs/trace_safety.md).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ctx.context import ROW_AXIS
from ..obs import metrics as _metrics
from ..ops import hashing
from ..status import DataIntegrityError
from ..utils.cache import jit, program_cache

shard_map = jax.shard_map

_STATS = _metrics.group("audit", (
    "conservation_checks", "fingerprint_checks", "fingerprint_votes",
    "violations", "rows_reconciled", "bytes_reconciled",
    "reconcile_resyncs", "manifest_fps", "manifest_audits",
    "corruptions_injected"))


def stats() -> dict:
    return dict(_STATS)


def reset_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0
    # an audit-stats reset is NOT a registry reset: re-seed the
    # reconcile mirror from the live exchange counters, else the next
    # conservation check would see them "running ahead" and raise
    _STATS["rows_reconciled"] = _metrics.counter(
        "exchange_rows_total").value
    _STATS["bytes_reconciled"] = _metrics.counter(
        "exchange_bytes_total").value


# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------

#: [None = env unread, else the cached bool] — one list load on the
#: unarmed happy path (the same contract as metrics' snapshot poll)
_ARMED: list = [None]


def armed() -> bool:
    """True while ``CYLON_TPU_AUDIT=1`` arms the fingerprint layer.
    Cached after the first read; :func:`rearm` re-reads (tests, and the
    multihost driver arming legs mid-process)."""
    a = _ARMED[0]
    if a is None:
        a = _ARMED[0] = os.environ.get("CYLON_TPU_AUDIT", "") not in ("", "0")
    return a


def rearm() -> None:
    _ARMED[0] = None


# ---------------------------------------------------------------------------
# layer 1: conservation laws — pure host math on the count sidecar
# ---------------------------------------------------------------------------

def conserve_exchange(counts, per_dest, total: int, row_bytes: int, *,
                      site: str = "shuffle.recv",
                      phase: str = "post_exchange") -> None:
    """Always-on conservation check over one exchange's (W, W) count
    sidecar: every row some source rank sent must be received by exactly
    the destination the sidecar names.  Raises typed
    :class:`DataIntegrityError` on violation (classified: the ladder
    recomputes the stage once).  Also reconciles the running logical
    totals against the ``exchange_rows_total``/``exchange_bytes_total``
    registry counters — a route that moves rows without accounting them
    (or accounts rows it never moved) surfaces here instead of silently
    skewing the comm model.  A registry reset between exchanges (bench
    iterations) re-syncs instead of raising: only the counters running
    AHEAD of the audited exchanges is a drift."""
    _STATS["conservation_checks"] += 1
    c = np.asarray(counts)
    pd = np.asarray(per_dest)
    bad = None
    if c.ndim != 2 or c.shape[0] != c.shape[1]:
        bad = f"count sidecar shape {c.shape} is not (W, W)"
    elif (c < 0).any():
        s, d = np.argwhere(c < 0)[0]
        bad = f"negative count {int(c[s, d])} at (src={s}, dst={d})"
    elif not np.array_equal(c.sum(axis=0), pd):
        col = c.sum(axis=0)
        d = int(np.argwhere(col != pd)[0][0])
        bad = (f"rows-received mismatch at dst={d}: sidecar column sum "
               f"{int(col[d])} != delivered {int(pd[d])}")
    elif int(c.sum()) != int(total):
        bad = (f"rows-sent total {int(c.sum())} != logical row total "
               f"{int(total)}")
    if bad is not None:
        _STATS["violations"] += 1
        raise DataIntegrityError(
            f"exchange conservation law violated at {site}: {bad}",
            site=site, phase=phase)
    _STATS["rows_reconciled"] += int(total)
    _STATS["bytes_reconciled"] += int(total) * int(row_bytes)
    rows_seen = _metrics.counter("exchange_rows_total").value
    bytes_seen = _metrics.counter("exchange_bytes_total").value
    if (_STATS["rows_reconciled"] == rows_seen
            and _STATS["bytes_reconciled"] == bytes_seen):
        return
    if (rows_seen < _STATS["rows_reconciled"]
            or bytes_seen < _STATS["bytes_reconciled"]):
        # the exchange counters went backwards relative to the audit
        # mirror: a registry reset happened between exchanges — re-sync
        _STATS["rows_reconciled"] = rows_seen
        _STATS["bytes_reconciled"] = bytes_seen
        _STATS["reconcile_resyncs"] += 1
        return
    _STATS["violations"] += 1
    raise DataIntegrityError(
        f"exchange counter reconciliation failed at {site}: "
        f"exchange_rows_total={rows_seen} / exchange_bytes_total="
        f"{bytes_seen} ran ahead of the audited sidecar totals "
        f"({_STATS['rows_reconciled']} rows / "
        f"{_STATS['bytes_reconciled']} B) — a route moved or counted "
        "rows outside the audited exchange path",
        site=site, phase=phase)


def conserve_hops(counts, c1, c2, *, site: str = "topo.exchange",
                  phase: str = "post_exchange") -> None:
    """The two-hop route's conservation identities over its derived hop
    count matrices (docs/topology.md): hop 1 sends exactly what each
    source holds, hop 2 delivers exactly what each destination is owed,
    and every row hop 1 parks at a gateway leaves on hop 2."""
    _STATS["conservation_checks"] += 1
    c = np.asarray(counts)
    a = np.asarray(c1)
    b = np.asarray(c2)
    bad = None
    if (a < 0).any() or (b < 0).any():
        bad = "negative hop count"
    elif not np.array_equal(a.sum(axis=1), c.sum(axis=1)):
        bad = "hop-1 row sums != sidecar row sums (rows lost before ICI)"
    elif not np.array_equal(b.sum(axis=0), c.sum(axis=0)):
        bad = "hop-2 column sums != sidecar column sums (rows lost on DCN)"
    elif not np.array_equal(a.sum(axis=0), b.sum(axis=1)):
        bad = "gateway imbalance: hop-1 arrivals != hop-2 departures"
    if bad is not None:
        _STATS["violations"] += 1
        raise DataIntegrityError(
            f"two-hop conservation law violated at {site}: {bad}",
            site=site, phase=phase)


# ---------------------------------------------------------------------------
# layer 2: order-invariant content fingerprints (armed)
# ---------------------------------------------------------------------------

#: per-row hash seed and the two finalization tweaks that split the one
#: u32 chain into independent lo/hi output lanes (64 fingerprint bits)
_FP_SEED = 0x243F6A88
_FP_LO = 0xA5A5A5A5
_FP_HI = 0x3C3C3C3C


def _audit_lanes(a):
    """Bit-exact u32 lanes for the fingerprint: unlike the routing hash
    (:func:`cylon_tpu.ops.hashing._u32_lanes`) nothing is canonicalized
    or downcast — a flipped sign bit on -0.0 or a low-mantissa f64 flip
    must change the fingerprint."""
    dt = a.dtype
    if dt == jnp.bool_:
        return [a.astype(jnp.uint32)]
    if jnp.issubdtype(dt, jnp.floating):
        if dt.itemsize == 8:
            pair = jax.lax.bitcast_convert_type(a, jnp.uint32)
            return [pair[..., 0], pair[..., 1]]
        if dt.itemsize < 4:
            a = a.astype(jnp.float32)
        return [jax.lax.bitcast_convert_type(a, jnp.uint32)]
    return hashing._u32_lanes(a)


def _xor_fold(x):
    """XOR-reduce over axis 0 — the commutative mix that makes the
    fingerprint order- and placement-invariant."""
    return jax.lax.reduce(x, np.uint32(0),
                          lambda p, q: jax.lax.bitwise_xor(p, q), (0,))


@program_cache()
def _fingerprint_fn(mesh: Mesh, w: int, n_arrs: int, mask_kind: str):
    """Order-invariant 64-bit mesh fingerprint over ``n_arrs`` row-major
    arrays: one u32 avalanche chain per row across every lane of every
    array (2-D lane matrices contribute each column), finalized twice
    (lo/hi tweaks) for 64 output bits, masked to the XOR identity on
    invalid rows, XOR-folded per shard, all_gathered and folded across
    the mesh — the (2,) uint32 result is REPLICATED, so every process
    of a multihost session holds the identical fingerprint.

    ``mask_kind``: ``"prefix"`` — the first operand is the replicated
    (W,) valid-count vector, valid rows are each shard's dense prefix
    (tables, exchange outputs); ``"targets"`` — the first operand is the
    sharded target-rank array, valid rows are those with a real
    destination (``tgt < W`` — padding carries the trash target W)."""

    def per_shard(sel, *arrs):
        cap = arrs[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        if mask_kind == "prefix":
            mask = jnp.arange(cap) < sel[my]
        else:
            mask = sel < w
        h = jnp.full((cap,), jnp.uint32(_FP_SEED))
        gold = jnp.uint32(hashing._GOLD)
        for a in arrs:
            if a.ndim == 2:
                slices = [a[:, j] for j in range(a.shape[1])]
            else:
                slices = [a]
            for s in slices:
                for lane in _audit_lanes(s):
                    h = hashing._mix32(
                        h ^ (lane + gold + (h << jnp.uint32(6))
                             + (h >> jnp.uint32(2))))
        lo = jnp.where(mask, hashing._mix32(h ^ jnp.uint32(_FP_LO)),
                       jnp.uint32(0))
        hi = jnp.where(mask, hashing._mix32(h ^ jnp.uint32(_FP_HI)),
                       jnp.uint32(0))
        part = jnp.stack([_xor_fold(lo), _xor_fold(hi)]).reshape(1, 2)
        return _xor_fold(jax.lax.all_gather(part, ROW_AXIS).reshape(-1, 2))

    sel_spec = P() if mask_kind == "prefix" else P(ROW_AXIS)
    specs = (sel_spec,) + (P(ROW_AXIS),) * n_arrs
    # replication checking can't infer the post-gather XOR fold is
    # replicated (lax.reduce has no rep rule); the value IS — every
    # shard folds the identical gathered matrix — so disable the check
    # (the jaxpr gate still asserts the program's collective set)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                         out_specs=P(), check_vma=False))


def _pull_fp(pair_dev) -> int:
    """Host pull of the replicated (2,) fingerprint — the audit's one
    sync point, run under the exchange watchdog so an injected (or real)
    peer hang at ``audit.verify`` surfaces typed instead of blocking."""
    from . import recovery
    from ..utils.host import host_array
    stalled = recovery.injected("audit.verify") == "stall"
    pair = recovery.exchange_watchdog("audit.verify",
                                      lambda: host_array(pair_dev),
                                      stalled=stalled)
    return (int(pair[1]) << 32) | int(pair[0])


def partition_fingerprint(mesh: Mesh, arrays, *, prefix_counts=None,
                          targets=None) -> int:
    """64-bit order-invariant fingerprint of the valid rows of
    ``arrays`` (data and validity arrays alike — pass both so a flipped
    validity bit changes the fingerprint).  Exactly one of
    ``prefix_counts`` (host (W,) valid counts) / ``targets`` (sharded
    target-rank array, pre-exchange inputs) selects the row mask."""
    arrs = tuple(arrays)
    if targets is not None:
        sel, mask_kind = targets, "targets"
    else:
        sel = np.asarray(prefix_counts, np.int32)
        mask_kind = "prefix"
    w = int(mesh.devices.size)
    out = _fingerprint_fn(mesh, w, len(arrs), mask_kind)(sel, *arrs)
    return _pull_fp(out)


def table_fingerprint(table) -> int | None:
    """Fingerprint of a Table's content — every column's data and
    validity lanes in sorted column-name order, masked to each shard's
    valid prefix.  Order- and placement-invariant, so the fingerprint
    survives resharding: a foreign checkpoint piece re-blocked onto a
    different world fingerprints identically (the resume-audit
    property).  Returns None in serial (mesh-less) sessions."""
    mesh = getattr(table.env, "mesh", None)
    if mesh is None:
        return None
    arrs = []
    for name in sorted(table.columns):
        col = table.columns[name]
        arrs.append(col.data)
        if col.validity is not None:
            arrs.append(col.validity)
    return partition_fingerprint(mesh, arrs,
                                 prefix_counts=table.valid_counts)


def verify_exchange(mesh: Mesh, tgt, cols, outs, per_dest, *,
                    site: str = "shuffle.recv",
                    phase: str = "post_exchange") -> None:
    """Armed post-exchange fingerprint conservation: the XOR fingerprint
    of the valid INPUT rows (those with a real destination) must equal
    the fingerprint of the delivered OUTPUT rows — the exchange moves
    rows verbatim and preserves the multiset, whatever route carried
    them (flat, multi-round, two-hop).  The output fingerprint is voted
    over the consensus wire first (multiprocess), so the raise/proceed
    decision below is rank-uniform by construction."""
    fp_in = partition_fingerprint(mesh, cols, targets=tgt)
    fp_out = partition_fingerprint(mesh, outs, prefix_counts=per_dest)
    _STATS["fingerprint_checks"] += 1
    from . import recovery
    recovery.fingerprint_consensus(mesh, fp_out)
    _STATS["fingerprint_votes"] += 1
    if fp_in != fp_out:
        _STATS["violations"] += 1
        raise DataIntegrityError(
            f"fingerprint conservation violated at {site}: inputs "
            f"{fp_in:#018x} != outputs {fp_out:#018x} — a received "
            "buffer was mutated in flight",
            site=site, phase=phase)


def audit_table(table, *, site: str, phase: str) -> int | None:
    """Armed stage-boundary audit of a whole table (post-stitch output,
    absorbed stream batch, completed piece): compute the replicated
    fingerprint and vote it rank-coherently.  Returns the fingerprint
    (None in serial sessions) so callers can record it (checkpoint
    manifests)."""
    fp = table_fingerprint(table)
    if fp is None:
        return None
    _STATS["fingerprint_checks"] += 1
    from . import recovery
    recovery.fingerprint_consensus(getattr(table.env, "mesh", None), fp)
    _STATS["fingerprint_votes"] += 1
    return fp


def audit_restored_table(table, recorded_fp, *, site: str = "ckpt.audit",
                         phase: str = "resume") -> None:
    """Resume audit: recompute a restored checkpoint piece's content
    fingerprint and compare against the manifest-recorded one — catches
    corruption that page shas cannot (a piece whose pages were rewritten
    sha-consistently, or a stitch/re-block bug in foreign adoption).
    Mismatch raises typed :class:`DataIntegrityError`; the checkpoint
    layer degrades it exactly like a sha miss — recompute, never
    adopt."""
    if recorded_fp is None or not armed():
        return
    fp = table_fingerprint(table)
    if fp is None:
        return
    _STATS["manifest_audits"] += 1
    if int(fp) != int(recorded_fp):
        _STATS["violations"] += 1
        raise DataIntegrityError(
            f"checkpoint piece content fingerprint mismatch at {site}: "
            f"manifest recorded {int(recorded_fp):#018x}, restored "
            f"content fingerprints to {fp:#018x} — refusing to adopt",
            site=site, phase=phase)


def manifest_fingerprint(table) -> int | None:
    """The fingerprint recorded into a checkpoint manifest entry at
    save time (armed sessions only — unarmed saves record nothing and
    unarmed resumes skip the audit, keeping the happy path write-free)."""
    if not armed():
        return None
    fp = table_fingerprint(table)
    if fp is not None:
        _STATS["manifest_fps"] += 1
    return fp


# ---------------------------------------------------------------------------
# the exchange.corrupt drill: flip ONE element of a delivered buffer
# ---------------------------------------------------------------------------

@program_cache()
def _flip_fn(mesh: Mesh, ndim: int, kind: str):
    """Flip element (0, …) of ONE shard's received buffer — the
    ``exchange.corrupt`` injector's device-side single-element
    corruption (``xor``: bit 0 of an integer/bool lane; ``add``: +1 on a
    float lane).  Non-selected shards pass through bit-identically."""

    def per_shard(s_star, a):
        my = jax.lax.axis_index(ROW_AXIS)
        hit = my == s_star[0]
        idx = (0,) * ndim
        if kind == "xor":
            one = (jnp.asarray(True) if a.dtype == jnp.bool_
                   else jnp.ones((), a.dtype))
            flipped = a[idx] ^ one
        else:
            flipped = a[idx] + jnp.ones((), a.dtype)
        return a.at[idx].set(jnp.where(hit, flipped, a[idx]))

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(P(), P(ROW_AXIS)),
                         out_specs=P(ROW_AXIS)))


def flip_one(mesh: Mesh, arrays, per_dest):
    """Corrupt exactly one element of one delivered column, on the shard
    holding the most rows (guaranteed a VALID row, so the flip is never
    masked out of the fingerprint).  Returns the new array tuple; a
    zero-row exchange is returned untouched."""
    pd = np.asarray(per_dest)
    if pd.size == 0 or int(pd.max()) <= 0:
        return tuple(arrays)
    s_star = np.asarray([int(pd.argmax())], np.int32)
    arrays = list(arrays)
    i = next((j for j, a in enumerate(arrays)
              if np.dtype(a.dtype) == np.bool_
              or np.issubdtype(np.dtype(a.dtype), np.integer)), 0)
    a = arrays[i]
    kind = ("add" if np.issubdtype(np.dtype(a.dtype), np.floating)
            else "xor")
    arrays[i] = _flip_fn(mesh, int(np.ndim(a)), kind)(s_star, a)
    _STATS["corruptions_injected"] += 1
    return tuple(arrays)


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry) — the jaxpr
# pass verifies the fingerprint builder's SPMD invariants: exactly one
# all_gather (the replication fold), no other collective; the flip
# builder is pure-local.
# ---------------------------------------------------------------------------

def _trace_fingerprint(mesh):
    w, cap, S = _decl_shapes(mesh)
    prefix = _unwrap(_fingerprint_fn(mesh, w, 3, "prefix"))
    targets = _unwrap(_fingerprint_fn(mesh, w, 1, "targets"))

    def both(vc, a, m, v, tgt, b):
        # prefix-masked table walk (i64 + 2-D u32 lane matrix + validity)
        # and target-masked exchange-input walk in one jaxpr
        return prefix(vc, a, m, v), targets(tgt, b)

    return jax.make_jaxpr(both)(
        S((w,), np.int32), S((w * cap,), np.int64),
        S((w * cap, 2), np.uint32), S((w * cap,), np.bool_),
        S((w * cap,), np.int32), S((w * cap,), np.float64))


def _trace_flip(mesh):
    w, cap, S = _decl_shapes(mesh)
    f1 = _unwrap(_flip_fn(mesh, 1, "xor"))
    f2 = _unwrap(_flip_fn(mesh, 2, "add"))

    def both(s, a, b):
        return f1(s, a), f2(s, b)

    return jax.make_jaxpr(both)(S((1,), np.int32), S((w * cap,), np.int64),
                                S((w * cap, 2), np.float64))


from ..analysis.registry import (declare_builder, decl_shapes as _decl_shapes,  # noqa: E402
                                 unwrap as _unwrap)

declare_builder(f"{__name__}._fingerprint_fn", _trace_fingerprint,
                collectives={"all_gather"}, tags=("integrity",),
                retrace_budget=64)
declare_builder(f"{__name__}._flip_fn", _trace_flip, tags=("integrity",))
