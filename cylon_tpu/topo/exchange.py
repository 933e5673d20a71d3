"""The hierarchical two-hop exchange engine (docs/topology.md).

Runs one logical hash/range exchange — identical inputs and outputs to
the flat engine in :mod:`cylon_tpu.parallel.shuffle` — as two grouped
collectives on a two-tier fabric:

* **hop 1 (ICI)**: a slice-local all-to-all (``lax.all_to_all`` with
  ``axis_index_groups`` = the slice blocks) routes every row to its
  destination's *gateway-local bucket*: the in-slice rank whose local
  index matches the final destination's (:func:`model.gateway_of`).
  The row's final target rides along as one int32 sidecar lane.
* **hop 2 (DCN)**: a cross-slice all-to-all between same-local ranks
  (groups = the local-index columns) delivers each (src-slice,
  dst-slice) payload in ONE aggregated message per local index —
  O(rows) bytes over DCN once, instead of the flat plan's
  O(rows × peers) small padded messages: each rank's DCN partner count
  drops from ``(S-1)·R`` to ``S-1`` (cross-slice message count exactly
  1/R of the flat plan's — the acceptance instrument,
  :func:`tier_traffic`), and the padded cross-slice wire volume drops
  toward 1/R wherever the count matrix is concentrated
  (order-preserving repartition/sort bands, low-cardinality keys) —
  cross-slice payload itself is route-invariant, as it must be.

**Order preservation** (the bit/order-equality contract): with the
slice-major layout, hop 1's receive order at gateway ``(s, j)`` is
(local source ``i`` ascending, source position ascending); restricted
to rows bound for one final rank ``(D, j)`` that order survives hop 2's
stable per-target sort, and hop 2's receive order at ``(D, j)`` is
(source slice ``s`` ascending, hop-1 position ascending) — composing to
exactly (global source rank ``s·R + i``, source position), the flat
exchange's contract (table.cpp:182-190 in the reference; proof sketch
in docs/topology.md).  No position sidecar, no final re-sort: the
composition is order-preserving by construction.

Both hops' count matrices are pure host arithmetic on the ALREADY
PULLED global count sidecar (:func:`hop_counts`) — the two-hop route
adds zero host syncs and zero device pulls over the flat plan.

This module is part of the ``cylon_tpu/topo`` plan facade (lint rule
TS116): callers route through :func:`two_hop` with a plan the facade
voted; the gateway math and hop programs are not callable decisions
elsewhere.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import config
from ..ctx.context import ROW_AXIS
from ..utils.cache import jit, program_cache

shard_map = jax.shard_map


# ---------------------------------------------------------------------------
# host math: per-hop count matrices from the global sidecar
# ---------------------------------------------------------------------------

def hop_counts(counts: np.ndarray, n_slices: int) -> tuple:
    """(C1, C2): the two hops' (W, W) count matrices from the logical
    exchange's global count matrix ``C`` — pure host numpy, no device
    work (part of the TS116 facade: the gateway assignment is encoded
    here and nowhere else).

    ``C1[(s,i), (s,j)] = Σ_D C[(s,i), (D,j)]`` — source ``(s,i)``'s rows
    bound for ANY rank with local index ``j`` go to the in-slice
    gateway ``(s,j)``; every C1 cell is slice-local (ICI).

    ``C2[(s,j), (D,j)] = Σ_i C[(s,i), (D,j)]`` — gateway ``(s,j)``
    forwards slice ``s``'s aggregated payload for ``(D,j)``; every C2
    cell connects same-local ranks (diagonal ``D = s`` stays ICI, the
    rest crosses DCN exactly once).

    Row sums of C1 = C's row sums, column sums of C2 = C's column sums,
    and C1's column sums = C2's row sums — the conservation identities
    tests/test_topo.py asserts."""
    c = np.asarray(counts, np.int64)
    w = c.shape[0]
    s_, r_ = int(n_slices), w // int(n_slices)
    c4 = c.reshape(s_, r_, s_, r_)           # [s, i, D, j]
    c1 = np.zeros((w, w), np.int64)
    c2 = np.zeros((w, w), np.int64)
    m1 = c4.sum(axis=2)                      # [s, i, j]
    m2 = c4.sum(axis=1)                      # [s, D, j]
    for s in range(s_):
        c1[s * r_:(s + 1) * r_, s * r_:(s + 1) * r_] = m1[s]
        for d in range(s_):
            c2[s * r_ + np.arange(r_), d * r_ + np.arange(r_)] = m2[s, d]
    return c1, c2


def hop_block(counts_hop: np.ndarray, total: int, w: int,
              group: int) -> tuple[int, int]:
    """(block, rounds) for one grouped hop — the flat engine's sizing
    rule with the per-rank cell count ``w·group`` replacing ``w²``:
    block ≈ 2× the uniform stream, floored for tiny tables, and rounds
    bound peak send memory at ``group·block`` under skew."""
    max_c = int(counts_hop.max()) if counts_hop.size else 1
    uniform = -(-int(total) // max(w * group, 1))
    cap = config.pow2ceil(max(2 * uniform, 8192))
    block = config.pow2ceil(min(max(max_c, 1), cap))
    rounds = -(-max_c // block) if max_c else 1
    return block, max(rounds, 1)


# ---------------------------------------------------------------------------
# device programs
# ---------------------------------------------------------------------------

@program_cache()
def _hop1_targets_fn(mesh: Mesh, w: int, n_slices: int):
    """Final target → hop-1 gateway target (pure-local): destination
    ``d``'s rows bucket on the in-slice rank ``my_slice·R + d % R``;
    the trash destination ``w`` passes through."""
    r_ = w // n_slices

    def per_shard(tgt):
        my = jax.lax.axis_index(ROW_AXIS)
        base = (my // r_) * r_
        g = base + jnp.clip(tgt, 0, w - 1) % r_
        return jnp.where(tgt < w, g.astype(jnp.int32), jnp.int32(w))

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(P(ROW_AXIS),),
                             out_specs=P(ROW_AXIS)))


@program_cache()
def _hop2_targets_fn(mesh: Mesh, w: int, cap: int):
    """Hop-2 targets from the hop-1-delivered final-target sidecar:
    live rows keep their carried target, receive-buffer padding (zeros)
    masks to the trash destination via the hop-1 valid counts."""

    def per_shard(vc, tgt):
        my = jax.lax.axis_index(ROW_AXIS)
        mask = jnp.arange(cap, dtype=jnp.int32) < vc[my]
        return jnp.where(mask, jnp.clip(tgt, 0, w - 1), jnp.int32(w))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(P(), P(ROW_AXIS)),
                             out_specs=P(ROW_AXIS)))


@program_cache()
def _tier_round_fn(mesh: Mesh, w: int, n_slices: int, hop: int,
                   block: int, out_cap: int, rounds: int = 1):
    """The grouped exchange round engine — the flat ``_round_fn`` with
    the all-to-all restricted to a tier's groups:

    * ``hop == 1`` (ICI): groups are the slice blocks
      ``[sR .. sR+R)``; a target's slot index within my group is its
      local index ``tgt % R`` (targets are in-slice by construction of
      :func:`_hop1_targets_fn`).
    * ``hop == 2`` (DCN): groups are the local-index columns
      ``[j, R+j, ...]``; a target's slot index is its slice ``tgt // R``.

    Send buffers are ``G·block`` rows (G = group size) — the grouped
    collective moves G·block per rank per round instead of the flat
    engine's W·block, which is where the ~1/R cross-slice wire
    reduction comes from.  The round body IS the flat engine's
    (``shuffle.exchange_rounds``: segment copies into the send blocks,
    the all-to-all, segment copies to the final place), handed the
    group's members as destinations and sources: they ascend in GLOBAL
    rank order for both tiers, so the receive order composes to the
    flat contract."""
    from ..parallel import shuffle as shf
    r_ = w // n_slices
    g = r_ if hop == 1 else n_slices
    if hop == 1:
        groups = [[s * r_ + i for i in range(r_)] for s in range(n_slices)]
    else:
        groups = [[s * r_ + j for s in range(n_slices)] for j in range(r_)]

    def per_shard(counts, outs, srt):
        my = jax.lax.axis_index(ROW_AXIS)
        if hop == 1:
            members = (my // r_) * r_ + jnp.arange(g, dtype=jnp.int32)
        else:
            members = jnp.arange(g, dtype=jnp.int32) * r_ + (my % r_)
        return shf.exchange_rounds(counts, outs, srt, block=block,
                                   rounds=rounds, members=members,
                                   groups=groups)

    return shf.round_program(mesh, per_shard)


# ---------------------------------------------------------------------------
# the orchestrator
# ---------------------------------------------------------------------------

def two_hop(mesh: Mesh, plan, tgt, counts: np.ndarray, cols: tuple,
            out_cap: int, prep: HopPrep | None = None):
    """Run one logical exchange through the two-tier route per the
    VOTED plan: hop-1 slice-local alignment (final target riding as a
    sidecar lane), hop-2 aggregated cross-slice delivery.  Inputs and
    outputs match the flat engine's phase B exactly — ``(outs tuple,
    per-dest valid counts)`` with identical values, order and (pow2)
    capacities, which is what makes the route transparent to every
    operator riding ``shuffle_table`` (docs/topology.md).

    ``counts`` is the logical (W, W) sidecar the caller already pulled;
    both hop matrices derive from it on the host (:func:`hop_counts`) —
    no extra pulls, no extra syncs."""
    from ..parallel import shuffle as shf
    from ..utils import timing

    w = counts.shape[0]
    s_ = plan.n_slices
    p = prep if prep is not None else HopPrep(plan, counts)
    c1, c2 = p.c1, p.c2
    block1, rounds1, block2, rounds2 = (p.block1, p.rounds1, p.block2,
                                        p.rounds2)
    cap1 = p.cap1

    timing.bump("exchange.two_hop")
    if rounds1 > 1 or rounds2 > 1:
        timing.bump("exchange.multiround")

    # hop 1: slice-local alignment over ICI, final target as sidecar
    tgt1 = _hop1_targets_fn(mesh, w, s_)(tgt)
    c1_i = np.asarray(c1, np.int32)
    cols1 = tuple(cols) + (tgt,)
    srt1 = shf.sort_by_target(mesh, w, tgt1, cols1)
    outs1 = tuple(shf._alloc_fn(mesh, cap1, str(c.dtype), c.shape[1:])()
                  for c in cols1)
    outs1 = _tier_round_fn(mesh, w, s_, 1, block1, cap1,
                           max(rounds1, 1))(c1_i, outs1, srt1)

    # hop 2: aggregated cross-slice delivery over DCN
    vc1 = np.asarray(p.per_gw, np.int32)
    tgt2 = _hop2_targets_fn(mesh, w, cap1)(vc1, outs1[-1])
    c2_i = np.asarray(c2, np.int32)
    srt2 = shf.sort_by_target(mesh, w, tgt2, outs1[:-1])
    outs = tuple(shf._alloc_fn(mesh, out_cap, str(c.dtype), c.shape[1:])()
                 for c in cols)
    outs = _tier_round_fn(mesh, w, s_, 2, block2, out_cap,
                          max(rounds2, 1))(c2_i, outs, srt2)
    return outs, counts.sum(axis=0).astype(np.int64)


class HopPrep:
    """One logical exchange's derived two-hop schedule — both hop count
    matrices, their block/round sizing and the gateway capacity —
    computed ONCE per exchange (``hop_counts`` is O(W²) host numpy with
    per-slice Python loops, and a guarded multi-slice exchange would
    otherwise derive it three times: guard, tier counters, dispatch)."""

    __slots__ = ("c1", "c2", "block1", "rounds1", "block2", "rounds2",
                 "per_gw", "cap1")

    def __init__(self, plan, counts: np.ndarray):
        w = counts.shape[0]
        total = int(counts.sum()) if counts.size else 0
        self.c1, self.c2 = hop_counts(counts, plan.n_slices)
        self.block1, self.rounds1 = hop_block(self.c1, total, w,
                                              plan.ranks_per_slice)
        self.block2, self.rounds2 = hop_block(self.c2, total, w,
                                              plan.n_slices)
        #: per-gateway received rows (hop-1 column sums) — also hop 2's
        #: valid-count sidecar
        self.per_gw = self.c1.sum(axis=0)
        #: hop-1 gateway receive capacity (pow2): a gateway buckets its
        #: whole slice's traffic for one local index
        self.cap1 = config.pow2ceil(int(self.per_gw.max())
                                    if self.per_gw.size else 1)
        # always-on conservation laws over the derived hop matrices
        # (exec/integrity — the audit facade owns the typed raise):
        # host math on arrays this constructor just built, zero device
        # work, checked ONCE per exchange at derivation time
        from ..exec import integrity as _integrity
        _integrity.conserve_hops(counts, self.c1, self.c2)


def prepare(plan, counts: np.ndarray) -> HopPrep:
    """Derive the two-hop schedule for one exchange (see
    :class:`HopPrep`) — the caller threads it through the guard sizing,
    the tier accounting and :func:`two_hop`."""
    return HopPrep(plan, counts)


def recv_guard_bytes(plan, prep: HopPrep, out_cap: int,
                     row_bytes: int) -> int:
    """The hierarchical route's peak RECEIVE allocation in BYTES, for
    the flat engine's pre-allocation guard: the hop-1 gateway buffers
    (payload + the 4-byte int32 final-target sidecar lane) are still
    alive — as hop 2's inputs — while the final ``out_cap`` buffers are
    allocated and filled, so the peak is the SUM of the tiers, not
    their max (parallel/shuffle.exchange)."""
    return prep.cap1 * (int(row_bytes) + 4) + out_cap * int(row_bytes)


def tier_traffic(plan, counts: np.ndarray, row_bytes: int, route: str,
                 prep: HopPrep | None = None,
                 flat_block_rounds: tuple | None = None) -> dict:
    """Per-tier link traffic of one logical exchange — the PADDED wire
    volume and the (src, dst, round) MESSAGE count each tier's
    interconnect actually carries, per route (docs/topology.md "What
    the two-hop route buys").

    Stated plainly: cross-slice PAYLOAD is route-invariant — every row
    bound for a remote slice crosses DCN exactly once whichever route
    carries it — so the two-hop win is (a) the DCN **message count**,
    W·(S−1) aggregated transfers per round instead of the flat plan's
    W·(W−R) small ones — exactly 1/R, each rank keeping S−1 DCN
    partners instead of (S−1)·R (the α-term of the α·messages +
    β·bytes cost model, which is what "O(rows × peers) small messages"
    costs on a real fabric) — and (b) the padded **wire bytes** in
    concentrated-count regimes (order-preserving repartition/sort
    bands, low-cardinality keys), where the flat plan pads every one of
    its W−R cross-slice cells per rank to the global block while the
    aggregated hop-2 cells stay near their payload.

    ``route == "flat"``: the one-hop engine's W² cells at its block
    (``flat_block_rounds`` takes the (block, rounds) the flat engine
    already computed instead of re-deriving them); hierarchical: hop 1
    (all ICI) + hop 2 (diagonal ICI, rest DCN) at the ``prep``
    schedule's group blocks."""
    w = counts.shape[0]
    s_, r_ = plan.n_slices, plan.ranks_per_slice
    total = int(counts.sum()) if counts.size else 0
    rb = int(row_bytes)
    if route == "flat":
        if flat_block_rounds is not None:
            block, rounds = flat_block_rounds
        else:
            from ..parallel.shuffle import exchange_block_cap
            max_c = int(counts.max()) if counts.size else 1
            block = config.pow2ceil(min(max(max_c, 1),
                                        exchange_block_cap(total, w)))
            rounds = -(-max_c // block) if max_c else 1
        rounds = max(int(rounds), 1)
        return {"wire_ici": w * r_ * block * rounds * rb,
                "wire_dcn": w * (w - r_) * block * rounds * rb,
                "msgs_ici": w * r_ * rounds,
                "msgs_dcn": w * (w - r_) * rounds}
    p = prep if prep is not None else HopPrep(plan, counts)
    return {"wire_ici": (w * r_ * p.block1 * p.rounds1
                         + w * 1 * p.block2 * p.rounds2) * rb,  # h2 diag
            "wire_dcn": w * (s_ - 1) * p.block2 * p.rounds2 * rb,
            "msgs_ici": w * r_ * p.rounds1 + w * 1 * p.rounds2,
            "msgs_dcn": w * (s_ - 1) * p.rounds2}


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry) — the jaxpr
# pass verifies the two-hop engine's SPMD invariants: the grouped
# all_to_all must stay UNCONDITIONAL (multi-round runs under a
# static-trip fori_loop → scan, identical on every rank: allowed; never
# cond/while — rank-divergent group participation deadlocks both
# tiers), and the target/sidecar programs are pure-local.
# docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _trace_tier_round(mesh):
    w, cap, S = _decl_shapes(mesh)
    n_slices = 2 if w % 2 == 0 and w >= 4 else 1
    if n_slices == 1:   # degenerate rig: nothing hierarchical to trace
        return jax.make_jaxpr(lambda x: x)(S((w,), np.int32))
    block, out_cap = cap // 4, 2 * cap
    i32 = np.int32
    hop1 = _unwrap(_tier_round_fn(mesh, w, n_slices, 1, block, out_cap, 3))
    hop2 = _unwrap(_tier_round_fn(mesh, w, n_slices, 2, block, out_cap, 1))

    def both(counts, outs, srt):
        a = hop1(counts, outs, srt)
        b = hop2(counts, outs, srt)
        return a, b

    args = (S((w, w), i32), (S((w * out_cap,), np.int64),),
            (S((w * cap,), np.int64),))
    return jax.make_jaxpr(both)(*args)


def _trace_hop1_targets(mesh):
    w, cap, S = _decl_shapes(mesh)
    n_slices = 2 if w % 2 == 0 and w >= 4 else 1
    if n_slices == 1:
        return jax.make_jaxpr(lambda x: x)(S((w,), np.int32))
    fn = _unwrap(_hop1_targets_fn(mesh, w, n_slices))
    return jax.make_jaxpr(fn)(S((w * cap,), np.int32))


def _trace_hop2_targets(mesh):
    w, cap, S = _decl_shapes(mesh)
    fn = _unwrap(_hop2_targets_fn(mesh, w, cap))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w * cap,), np.int32))


from ..analysis.registry import (declare_builder, decl_shapes as _decl_shapes,  # noqa: E402
                                 unwrap as _unwrap)

declare_builder(f"{__name__}._tier_round_fn", _trace_tier_round,
                collectives={"all_to_all"}, tags=("shuffle", "topo"))
declare_builder(f"{__name__}._hop1_targets_fn", _trace_hop1_targets,
                tags=("shuffle", "topo"))
declare_builder(f"{__name__}._hop2_targets_fn", _trace_hop2_targets,
                tags=("shuffle", "topo"))
