"""The shuffle engine: padded ICI all-to-all under ``shard_map``.

TPU-native replacement for the reference's entire async messaging stack —
the generic ``AllToAll`` state machine (net/ops/all_to_all.hpp:78), the
Arrow-aware ``ArrowAllToAll`` buffer streamer (arrow/arrow_all_to_all.hpp:93),
the per-backend channels (net/mpi/mpi_channel.cpp Isend/Irecv 8-int headers,
ucx/gloo equivalents) and the table serializer (serialize/table_serialize.hpp).
~6k LoC of hand-rolled messaging collapse into one XLA collective; the
complexity moves into static-shape capacity planning (SURVEY.md §7 hard-part
1):

  phase A (device): rows → target ranks, per-(src,dst) count matrix
  host:             pick pow2 block capacity c and output capacity
  phase B (device): stable-sort rows by target, so each destination's rows
                    are ONE contiguous run → copy the runs' windows into
                    the (W·c) send blocks → ``lax.all_to_all`` over the
                    mesh axis → copy each received block's valid prefix
                    to its final place (order-preserving: received order
                    is (source rank, source position), the same contract
                    as the reference's order-preserving all-to-all,
                    table.cpp:182-190).  Both placements are W segment
                    copies at offsets the count matrix gives: the
                    exchange's programs hold no XLA scatter.

The count matrix doubles as the row-count sidecar the reference sends in its
buffer headers.  All collectives ride ICI (mesh axis) — no host round-trip of
table payloads; only the O(W²) count matrix crosses to the host.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .. import config
from ..obs import comm as _comm, metrics as _metrics, plan as _plan
from ..topo import model as _topo
from ..utils import timing
from ..utils.cache import jit, program_cache
from ..utils.stages import stage
from ..ctx.context import ROW_AXIS
from ..ops import hashing, pack

shard_map = jax.shard_map


# ---------------------------------------------------------------------------
# Phase A: target computation + count matrix
# ---------------------------------------------------------------------------

@program_cache()
def _hash_targets_fn(mesh: Mesh, w: int, nkeys: int, with_valids: bool):
    def per_shard(vc, *keys):
        cap = keys[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        mask = jnp.arange(cap) < vc[my]
        datas = list(keys[:nkeys])
        valids = list(keys[nkeys:]) if with_valids else None
        h = hashing.hash_rows(datas, valids)
        tgt = hashing.partition_targets(h, w)
        return jnp.where(mask, tgt, jnp.int32(w))

    nargs = nkeys * 2 if with_valids else nkeys
    specs = (P(),) + tuple(P(ROW_AXIS) for _ in range(nargs))
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=P(ROW_AXIS)))


def hash_targets(mesh: Mesh, key_datas, key_valids, valid_counts: np.ndarray):
    """Global (W·cap,) int32 target-rank array; padding rows get target W
    (the trash destination dropped by the exchange)."""
    w = valid_counts.shape[0]
    with_valids = any(v is not None for v in key_valids)
    args = list(key_datas)
    if with_valids:
        cap_total = key_datas[0].shape[0]
        # numpy sidecars: jit places them per the shard_map specs on the
        # mesh; eager jnp.* would create on the default backend
        args += [v if v is not None else np.ones(cap_total, bool)
                 for v in key_valids]
    vc = np.asarray(valid_counts, np.int32)
    return _hash_targets_fn(mesh, w, len(key_datas), with_valids)(vc, *args)


@program_cache()
def _count_fn(mesh: Mesh, w: int):
    def per_shard(tgt):
        # dense compare-and-reduce, the form ops/groupby._seg_apply takes
        # for few segments: a segment_sum into W+1 segments is a
        # colliding scatter-add (~72 ns a row on a v5e, PERF.md)
        dest = jnp.arange(w, dtype=tgt.dtype)
        hit = (tgt[None, :] == dest[:, None]).astype(jnp.int32)
        return jnp.sum(hit, axis=1, dtype=jnp.int32).reshape(1, w)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(P(ROW_AXIS),),
                             out_specs=P(ROW_AXIS)))


def count_targets(mesh: Mesh, tgt) -> np.ndarray:
    """(W, W) host count matrix: C[s, d] = rows rank s sends to rank d.
    The host pull is the exchange's first cross-rank synchronization
    point, so it runs under the exchange watchdog: a peer that never
    produces its counts surfaces as a typed RankDesyncError instead of an
    infinite block (exec/recovery, ``CYLON_TPU_WATCHDOG_S``)."""
    w = mesh.devices.size
    from ..exec.recovery import exchange_watchdog
    from ..utils.host import host_array
    counts_dev = _count_fn(mesh, w)(tgt)
    return exchange_watchdog("exchange.counts",
                             lambda: host_array(counts_dev))


@program_cache()
def _skew_targets_fn(mesh: Mesh, w: int, k_heavy: int, nkeys: int):
    """Targets for a skew-split probe side: heavy-HASH rows spread evenly
    over all ranks (round-robin by global position) instead of hashing —
    the build side's rows with the same hashes are replicated, so any rank
    can join them.  Multi-column and float keys work uniformly (hash_rows
    canonicalizes).  Reference analog: sampled heavy-key handling,
    SURVEY.md §7 hard-part 4."""

    def per_shard(vc, heavy_hashes, *args):
        datas = list(args[:nkeys])
        valids = list(args[nkeys:])
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        mask = jnp.arange(cap) < vc[my]
        h = hashing.hash_rows(datas, valids)
        tgt = hashing.partition_targets(h, w)
        is_heavy = jnp.zeros(cap, bool)
        for j in range(k_heavy):
            is_heavy = is_heavy | (h == heavy_hashes[j])
        spread = ((my * cap + jnp.arange(cap, dtype=jnp.int32)) % w).astype(
            jnp.int32)
        tgt = jnp.where(is_heavy, spread, tgt)
        return jnp.where(mask, tgt, jnp.int32(w))

    specs = (P(), P()) + (P(ROW_AXIS),) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=P(ROW_AXIS)))


def skew_targets(mesh: Mesh, key_datas, key_valids,
                 valid_counts: np.ndarray, heavy_hashes: np.ndarray):
    """Per-row targets with heavy key hashes spread round-robin.
    ``key_valids`` entries must be real arrays (callers pass all-ones for
    non-nullable columns so null folding matches the detection pass)."""
    w = valid_counts.shape[0]
    vc = np.asarray(valid_counts, np.int32)
    fn = _skew_targets_fn(mesh, w, len(heavy_hashes), len(key_datas))
    hv = np.asarray(heavy_hashes, np.uint32)
    return fn(vc, hv, *key_datas, *key_valids)


@program_cache()
def _skew_split_targets_fn(mesh: Mesh, w: int, k: int, nkeys: int,
                           need_nf: tuple, narrow: tuple):
    """Targets for the adaptive skew-split probe side (the plan facade,
    relational/skew.py — lint rule TS115): light rows hash as usual;
    rows equal (in sort-OPERAND space) to one of the K heavy tuples are
    salted by their WITHIN-KEY arrival index STRIDED over the key's
    contiguous rank group — global row j of the key goes to member
    ``j mod fanout``.  The strided (round-robin) salt keeps every
    member's rows an order-preserving SUBSEQUENCE of the key's global
    (source rank, source position) order — the property the stitch's
    bit/order-equality contract stands on — while spreading EVERY
    source's heavy rows evenly over the whole group, so the exchange's
    per-(src,dst) cells stay uniform-sized and single-round (a
    contiguous-chunk salt would map each source's heavy block onto one
    or two members and quadruple the padded exchange's rounds;
    docs/skew.md).  Pure-local: the plan sidecars are replicated host
    arrays; no collective."""
    from ..ops import pack

    def per_shard(vc, srcoff, fan, start, *args):
        datas = list(args[:nkeys])
        valids = list(args[nkeys:2 * nkeys])
        tup = args[2 * nkeys:]
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        mask = jnp.arange(cap) < vc[my]
        h = hashing.hash_rows(datas, valids)
        base = hashing.partition_targets(h, w)
        ko_t = pack.key_operands(list(tup[:nkeys]), list(tup[nkeys:]),
                                 need_null_flags=need_nf, narrow32=narrow)
        ko_r = pack.key_operands(datas, valids, need_null_flags=need_nf,
                                 narrow32=narrow)
        _gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        eq = eq & mask[:, None]
        heavy = jnp.any(eq, axis=1)
        kidx = jnp.argmax(eq, axis=1).astype(jnp.int32)
        # born-wide int64 (JX203): within-key indices are GLOBAL row
        # counts — a single heavy key can exceed int32 at target scale
        eqi = eq.astype(jnp.int64)
        loc = jnp.cumsum(eqi, axis=0) - eqi          # within-shard index
        # one-hot select, not take_along_axis: jax 0.9 widens that
        # call's (cap, 1) int32 index to int64 under x64 (JX203)
        pick = jnp.arange(eq.shape[1], dtype=jnp.int32)[None, :] \
            == kidx[:, None]
        loc_k = jnp.sum(jnp.where(pick, loc, 0), axis=1)
        j = srcoff[my, kidx] + loc_k
        # fan arrives born-wide int64 (K,) so the row-scale modulus never
        # widens an int32 lane (JX203)
        ordn = (j % fan[kidx]).astype(jnp.int32)
        tgt_h = (start[kidx] + ordn) % w
        tgt = jnp.where(heavy, tgt_h, base)
        return jnp.where(mask, tgt, jnp.int32(w))

    specs = (P(), P(), P(), P()) + (P(ROW_AXIS),) * (2 * nkeys) \
        + (P(),) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=P(ROW_AXIS)))


def skew_split_targets(mesh: Mesh, key_datas, key_valids,
                       valid_counts: np.ndarray, k: int, need_nf: tuple,
                       narrow: tuple, tuple_args: tuple,
                       src_off: np.ndarray, fanout: np.ndarray,
                       start: np.ndarray):
    """Per-row targets for a skew-split probe exchange — called ONLY by
    the plan facade (relational/skew.py, lint rule TS115), which owns
    every sidecar's derivation.  ``key_valids`` entries must be real
    arrays (all-ones for non-nullable columns)."""
    w = valid_counts.shape[0]
    vc = np.asarray(valid_counts, np.int32)
    fn = _skew_split_targets_fn(mesh, w, int(k), len(key_datas), need_nf,
                                narrow)
    return fn(vc, np.asarray(src_off, np.int64),
              np.asarray(fanout, np.int64),
              np.asarray(start, np.int32), *key_datas, *key_valids,
              *tuple_args)


# ---------------------------------------------------------------------------
# Phase B: padded exchange, multi-round + order-preserving placement
#
# Send-buffer memory is W·block per column.  Under key skew (an all-to-one
# distribution) counts.max() approaches the whole shard, which would inflate
# device memory by ~W× per column (round-1 VERDICT red flag).  The exchange
# therefore runs in R = ceil(max_count / block) rounds with ``block`` capped
# near the uniform-case size: round r moves the rows whose within-(src,dst)
# position is in [r·block, (r+1)·block), and the receiver copies each
# round's blocks STRAIGHT to their final (source-rank, source-position)
# place — no end-of-exchange compaction or re-sort, and peak extra memory
# stays at W·block ≈ one shard's worth regardless of skew.
#
# Rows are stable-sorted by target once (``_prep_fn``), so both placements
# are segment copies at offsets the replicated count matrix gives
# (``send_fill``, ``recv_place``) — never an XLA scatter, which runs at
# 56–82 ns a row on a v5e where a copy runs at memory speed (PERF.md §6,
# PR 29).  ``exchange_rounds`` is the one round body of the flat engine and
# of the two-hop route's grouped hops (topo/exchange._tier_round_fn).
# ---------------------------------------------------------------------------

_RIDE = _metrics.counter("exchange_dispatches", path="ride")
_PERM = {why: _metrics.counter("exchange_dispatches", path="perm",
                               reason=why)
         for why in ("not_32bit", "over_operand_budget")}


def ride_rule(cols) -> tuple[int, str | None]:
    """How an exchange's rows get from source order to destination order —
    the ONE rule, read from the arrays' shapes and dtypes:
    ``(sort_operands, why_not)``.  The rows RIDE ``_prep_fn``'s sort by
    target as its payload operands (a ``(cap, L)`` lane matrix is ``L`` of
    them, a 1-D array one) where every array is 32-bit and the sort stays
    within ``pack.SORT_OPERAND_BUDGET`` (a sort's compile time grows with
    its operands): a moved lane costs ~1.1 ns a row there and ~3–4 through
    XLA's row gather (PERF.md §6, PR 46).  Otherwise (``why_not``:
    ``not_32bit`` — an array that is not 32-bit lanes: a float64 side
    array —, ``over_operand_budget`` — a table past six lanes) the sort is
    the 2-operand ``(target, position)`` one and the arrays are gathered at
    its permutation."""
    if any(np.dtype(c.dtype).itemsize != 4 or c.ndim > 2 for c in cols):
        return 2, "not_32bit"
    ops = 1 + sum(c.shape[1] if c.ndim == 2 else 1 for c in cols)
    if ops > pack.SORT_OPERAND_BUDGET:
        return 2, "over_operand_budget"
    return ops, None


@program_cache()
def _prep_fn(mesh: Mesh, w: int, ride: bool):
    """Per shard: the arrays of ``cols`` in the stable order of the rows by
    destination, computed once and read by every round.  Destination
    ``d``'s rows are the run ``[offs[d], offs[d] + C[my, d])`` of it
    (``offs`` = exclusive prefix of the count matrix's row ``my``); padding
    rows (target ``w``) sort last and belong to no run.  ``ride``
    (:func:`ride_rule`): the arrays' lanes are the payload operands of the
    ONE sort by ``(target, position)``, which moves them exactly as the
    permutation would, and the program holds no gather; else the sort
    gives the permutation and each array is gathered at it."""

    def per_shard(tgt, cols):
        if not ride:
            idx = jnp.arange(tgt.shape[0], dtype=jnp.int32)
            _tgt_s, perm = jax.lax.sort((tgt, idx), num_keys=1,
                                        is_stable=True)
            with stage("gather_rows"):
                return tuple(col[perm] for col in cols)
        lanes = [lane for col in cols for lane in
                 ([col] if col.ndim == 1 else
                  [col[:, j] for j in range(col.shape[1])])]
        cap = tgt.shape[0]
        bits = max(cap - 1, 1).bit_length()
        if (w + 1) << bits <= 1 << 32:
            # (target, position) in ONE word: the keys are unique, so the
            # order is the stable one with no tie-break operand (XLA:TPU
            # gives a stable sort an iota of its own: 2 + L operands)
            key = (tgt.astype(jnp.uint32) << bits) \
                | jnp.arange(cap, dtype=jnp.uint32)
            srt = jax.lax.sort((key, *lanes), num_keys=1, is_stable=False)
        else:
            srt = jax.lax.sort((tgt, *lanes), num_keys=1, is_stable=True)
        srt = iter(srt[1:])
        return tuple(next(srt) if col.ndim == 1 else
                     jnp.stack([next(srt) for _ in range(col.shape[1])],
                               axis=1) for col in cols)

    def fn(tgt, cols):
        specs = (P(ROW_AXIS),) * len(cols)
        return shard_map(per_shard, mesh=mesh, in_specs=(P(ROW_AXIS), specs),
                         out_specs=specs)(tgt, cols)

    return jit(fn)


def sort_by_target(mesh: Mesh, w: int, tgt, cols: tuple) -> tuple:
    """The arrays of ``cols`` target-sorted (``_prep_fn``) the way
    :func:`ride_rule` says, counted in ``exchange_dispatches{path=…}``:
    what every round body takes — the flat engine's and each hop's of the
    two-hop route."""
    _ops, why = ride_rule(cols)
    (_RIDE if why is None else _PERM[why]).inc()
    return _prep_fn(mesh, w, why is None)(tgt, tuple(cols))


def _excl_prefix(c):
    return jnp.cumsum(c) - c


def _rows(arr, start, n: int):
    """``arr[start : start + n]`` along the row axis, ``start`` traced."""
    return jax.lax.dynamic_slice_in_dim(arr, start, n, axis=0)


def send_fill(sorted_rows, starts, block: int):
    """The send buffer of one round: block ``i`` is the window
    ``sorted_rows[starts[i] : starts[i] + block]`` of the target-sorted
    rows (``starts[i]`` = the run's offset + the round's ``lo``).  Slots
    past the run's end carry a neighbour's rows, not zeros: ``recv_place``
    reads only each block's valid prefix, so no mask pass is spent on them.

    ``block`` rows of padding behind the rows let a window start anywhere
    in ``[0, cap]`` (a run may end at ``cap``, and ``block`` may exceed
    ``cap`` by config.pow2ceil's step): XLA clamps a slice's start so the
    slice fits, silently, and here that only happens to a window past
    ``cap`` — a later round of a stream that has ended, which holds no
    valid row."""
    with stage("exchange_place"):
        pad = jnp.zeros((block,) + sorted_rows.shape[1:], sorted_rows.dtype)
        padded = jnp.concatenate([sorted_rows, pad])
        return jnp.concatenate([_rows(padded, starts[i], block)
                                for i in range(starts.shape[0])])


def recv_place(out, recv, starts, left, block: int):
    """Copy the received blocks' valid prefixes to their final place:
    block ``i`` of ``recv`` holds source ``i``'s rows ``lo .. lo+block`` for
    me, of which the first ``clip(left[i], 0, block)`` exist (``left`` =
    the stream's count − ``lo``), and they land at ``out[starts[i]:]``
    (``starts[i]`` = rows of earlier sources + ``lo``).  Everything else
    of ``out`` stays as it was: the zeros ``_alloc_fn`` wrote past a
    destination's valid count (repart._rebuild and
    integrity.verify_exchange rely on "a permutation + zero padding") and
    the rows of earlier rounds and sources.

    A window is ``block`` wide whatever the valid prefix, so it may pass
    ``out``'s end (and, in a round past a short stream's end, start beyond
    it).  XLA would clamp such a slice's start silently; the clamp is
    taken here instead and the block's rows are read shifted by the same
    amount (from ``recv`` behind ``block`` rows of padding)."""
    out_cap = out.shape[0]
    if block > out_cap:
        raise ValueError(f"exchange block {block} exceeds the receive "
                         f"capacity {out_cap}")
    with stage("exchange_place"):
        q = jnp.arange(block, dtype=jnp.int32).reshape(
            (block,) + (1,) * (out.ndim - 1))
        recv_p = jnp.concatenate(
            [jnp.zeros((block,) + recv.shape[1:], recv.dtype), recv])
        for i in range(starts.shape[0]):
            n = jnp.clip(left[i], 0, block)
            at = jnp.clip(starts[i], 0, out_cap - block)
            shift = jnp.clip(starts[i] - at, 0, block)
            rows = _rows(recv_p, (i + 1) * block - shift, block)
            keep = (q >= shift) & (q < shift + n)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, jnp.where(keep, rows, _rows(out, at, block)), at,
                axis=0)
        return out


def exchange_rounds(counts, outs, srt, *, block: int, rounds: int,
                    members, groups=None):
    """Per shard, inside ``shard_map``: every round of one exchange of the
    target-sorted arrays ``srt`` (:func:`sort_by_target`) over the group
    of ranks I trade with — ``members`` are its global ranks, ascending
    (all ``w`` ranks for the flat engine, a tier's group for a two-hop
    hop), ``groups`` the matching ``axis_index_groups``.

    ``rounds > 1`` (skewed counts: some (src,dst) stream exceeds the
    block) runs ALL rounds inside one compiled program via
    ``lax.fori_loop`` — one dispatch total; the all_to_all stays
    unconditional (a static trip count lowers to a scan, identical on
    every rank: tests/test_trace_safety.py, JX201)."""
    my = jax.lax.axis_index(ROW_AXIS)
    offs = _excl_prefix(counts[my])[members]
    recv_counts = counts[members, my]
    roffs = _excl_prefix(recv_counts)

    def one_round(r, outs):
        lo = r * jnp.int32(block)
        new_outs = []
        for out, rows in zip(outs, srt):
            send = send_fill(rows, offs + lo, block)
            recv = jax.lax.all_to_all(send, ROW_AXIS, split_axis=0,
                                      concat_axis=0, tiled=True,
                                      axis_index_groups=groups)
            new_outs.append(recv_place(out, recv, roffs + lo,
                                       recv_counts - lo, block))
        return tuple(new_outs)

    if rounds == 1:
        return one_round(jnp.int32(0), tuple(outs))
    return jax.lax.fori_loop(0, rounds, one_round, tuple(outs))


def round_program(mesh: Mesh, per_shard):
    """The jitted ``(counts, outs, srt) -> outs`` program around a
    per-shard round body; ``outs`` is donated (the receive buffers are
    updated in place)."""

    def fn(counts, outs, srt):
        specs = (P(ROW_AXIS),) * len(srt)
        sm = shard_map(per_shard, mesh=mesh, in_specs=(P(), specs, specs),
                       out_specs=specs)
        return sm(counts, outs, srt)

    return jit(fn, donate_argnums=(1,))


@program_cache()
def _round_fn(mesh: Mesh, w: int, block: int, out_cap: int,
              rounds: int = 1):
    """The exchange round engine: fill the send blocks from the
    target-sorted rows, all-to-all, place the received blocks
    (``exchange_rounds`` over all ``w`` ranks).  ``out_cap`` is the
    receive buffers' row count: part of the program's identity only."""

    def per_shard(counts, outs, srt):
        return exchange_rounds(counts, outs, srt, block=block, rounds=rounds,
                               members=jnp.arange(w, dtype=jnp.int32))

    return round_program(mesh, per_shard)


@program_cache()
def _alloc_fn(mesh: Mesh, out_cap: int, dtype: str, extra_shape: tuple):
    def per_shard():
        return jnp.zeros((out_cap,) + extra_shape, jnp.dtype(dtype))

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(),
                             out_specs=P(ROW_AXIS)))


def exchange_block_cap(total: int, w: int) -> int:
    """Per-(src,dst) block bound: ~2× the uniform-case stream size, floored
    so tiny tables stay single-round."""
    uniform = -(-int(total) // max(w * w, 1))
    return config.pow2ceil(max(2 * uniform, 8192))


def exchange(mesh: Mesh, tgt, counts: np.ndarray, cols: tuple,
             guard: bool = False, owner: str = "shuffle.recv"):
    """Run the (possibly multi-round) padded all-to-all for every array in
    ``cols`` (payload-agnostic: callers pre-pack laneable columns into one
    (cap, L) u32 lane matrix — relational/repart._flatten_for_exchange —
    so the per-round fill/all_to_all/place chain runs once per ARRAY,
    and a whole table is typically one matrix + f64 side arrays).

    ``owner`` names the ledger registration of the guarded receive
    buffers — streaming ingest appends pass ``stream.recv`` so the
    serving tier's budget decisions can tell long-lived ingest state from
    transient query shuffles (cylon_tpu/stream, docs/streaming.md).

    Returns (new_cols tuple, new_valid_counts np (W,)).  Capacities are
    bucketed (config.pow2ceil) so the family of compiled programs stays
    small; rounds bound peak send-buffer memory under skew (note: the
    caller's packed matrix is a full-shard copy that lives for the whole
    exchange alongside the source table — the W·block bound applies to the
    per-round send/recv buffers).
    """
    # host.exchange_plan: block / round / capacity arithmetic on the count
    # sidecar, the receive guard and the always-on totals - host work
    # between the count pull and the exchange's first launch
    with timing.span("host.exchange_plan"):
        w = counts.shape[0]
        max_c = int(counts.max()) if counts.size else 1
        total = int(counts.sum()) if counts.size else 1
        block = config.pow2ceil(min(max(max_c, 1),
                                    exchange_block_cap(total, w)))
        rounds = -(-max_c // block) if max_c else 1
        per_dest = counts.sum(axis=0)
        # the fullest destination's rows set EVERY chip's receive capacity
        recv_max = int(per_dest.max()) if per_dest.size else 0
        out_cap = config.pow2ceil(recv_max)

        # topology route (cylon_tpu/topo, docs/topology.md): on a
        # multi-slice fabric phase B goes hierarchical — a slice-local ICI
        # alignment hop, then ONE aggregated cross-slice DCN hop — bit- and
        # order-equal to the flat plan by the slice-major layout.  The
        # route choice is deterministic from the cached topology plan
        # (rank-uniform by construction), and on a single-slice topology
        # ``hier_plan`` is one cached lookup returning None: the flat path
        # below is byte-identical to the pre-topology engine — zero extra
        # collectives, zero host syncs (the chaos --multislice unarmed-leg
        # contract).
        hplan = _topo.hier_plan(mesh)
        hprep = None
        if hplan is not None:
            # derive the two-hop schedule (hop count matrices, blocks,
            # gateway capacity) ONCE per exchange — the guard sizing, tier
            # accounting and dispatch below all read this object
            from ..topo import exchange as _topo_exchange
            hprep = _topo_exchange.prepare(hplan, counts)

        # Receive-side memory guard (accelerators only; ``guard=True`` from
        # hash-shuffle callers): the multi-round protocol bounds SEND
        # buffers, but the receiving shard still materializes every row
        # routed to it (out_cap is per-DEST).  A catastrophic route (skew
        # the heavy-key split didn't model, e.g. hash clustering) is known
        # from the COUNT SIDECAR before any allocation — raising an
        # OOM-shaped error here FAILS FAST AND CLEAN instead of submitting a
        # doomed multi-GB alloc, which this rig never recovers from (a real
        # device OOM poisons the process, docs/DESIGN.md).  Receive
        # concentration is not curable downstream — the streaming pipeline
        # shuffles the same full tables — so the REMEDY is the heavy-key
        # split (on by default); this guard is the backstop for routes the
        # split didn't model.  CPU meshes skip it (host RAM is typically far
        # above any HBM-sized budget); sort/repartition exchanges are
        # unguarded likewise.
        on_accel = mesh.devices.flat[0].platform != "cpu" \
            or config.EXCHANGE_RECV_GUARD_CPU
        row_bytes = sum(int(np.dtype(c.dtype).itemsize)
                        * int(np.prod(c.shape[1:], dtype=np.int64))
                        for c in cols)
        if guard:
            # The raise/proceed decision is itself rank-coherent: every rank
            # evaluates its local predicate (deterministic from the replicated
            # count sidecar, OR a rank-selective injected fault) and any
            # consensus runs BEFORE phase B's first collective is dispatched:
            # "no rank-local control flow after a collective has been
            # entered" (docs/robustness.md).  A rank whose guard did not fire
            # still raises when any peer's did, so no rank ever enters the
            # exchange alone.  The consensus poll itself runs ONLY when the
            # predicate can differ from OK somewhere — over_budget is
            # rank-uniform (replicated counts) and `armed` is rank-uniform by
            # construction (recovery.probe) — so the un-injected happy path
            # adds no collective and no host sync to the exchange.
            from ..exec import recovery, scheduler
            if hplan is not None:
                # two-hop peak receive: the hop-1 gateway buffers (payload
                # + the int32 final-target sidecar lane) are still alive —
                # as hop 2's inputs — while the final buffers fill, so the
                # guard sizes against the SUM of the tiers (deterministic
                # host math on the replicated sidecar)
                need = _topo_exchange.recv_guard_bytes(hplan, hprep, out_cap,
                                                       row_bytes)
            else:
                need = out_cap * row_bytes
            # HBM-ledger consult (exec/memory): the predicted receive is an
            # allocation ON TOP of the resident balance the ledger tracks —
            # and unlike the static receive budget, ledger pressure is
            # CURABLE: cold spillable owners (packed piece sources — sink
            # partials and receive buffers are accounting-only) evict to
            # host BEFORE the allocation.  Routed through the serving tier's
            # facade (scheduler.free_pressure, lint rule TS109); still
            # single-controller only (the underlying try_free no-ops in
            # multiprocess sessions, where eviction is taken exclusively on
            # the consensus'd admission path), and the raise/consensus
            # predicate below stays EXACTLY the replicated count-sidecar
            # one: a ledger balance read is rank-uniform only up to GC
            # release timing, so gating the consensus poll on it would risk
            # the very desync this guard exists to prevent.
            scheduler.free_pressure(need)
            over_budget = bool(
                on_accel
                and need > config.EXCHANGE_RECV_BUDGET_BYTES)
            kind, armed = recovery.probe("shuffle.recv_guard")
            local_fault = over_budget or kind is not None
            if ((over_budget or armed)
                    and recovery.guard_consensus(mesh, local_fault)):
                from ..status import PredictedResourceExhausted
                if kind is not None and kind != "predicted":
                    # rank-selective simulation of a non-guard fault at this
                    # site (e.g. device_oom): raise the REQUESTED kind; peer
                    # ranks raise the predicted shape below and the ladder's
                    # code consensus re-aligns the branches
                    raise recovery.make_fault(kind, "shuffle.recv_guard")
                hop1 = ("" if hplan is None else
                        f" (two-hop route: {out_cap} final rows + "
                        f"{hprep.cap1} gateway rows incl. the target "
                        "sidecar — both tiers live at once)")
                raise PredictedResourceExhausted(
                    f"RESOURCE_EXHAUSTED (predicted): exchange receive "
                    f"allocation {need} B at {row_bytes} B/row{hop1} exceeds "
                    f"CYLON_TPU_EXCHANGE_RECV_BUDGET "
                    f"({config.EXCHANGE_RECV_BUDGET_BYTES} B); one "
                    "destination "
                    "shard would materialize the bulk of the table",
                    site="shuffle.recv_guard")

        # always-on exchange totals (host arithmetic on the already-pulled
        # count sidecar — no device work, no sync): the registry counters
        # the armed comm matrix's row/column sums must reconcile against
        # (obs/comm, docs/observability.md).  The counters record the
        # LOGICAL exchange — each row delivered once — whichever route
        # carried it, so flat and hierarchical runs of the same workload
        # stay comparable; the tier counters below say which interconnect
        # the journey used.
        _metrics.counter("exchange_rows_total").inc(total)
        _metrics.counter("exchange_bytes_total").inc(total * row_bytes)
        _metrics.counter("exchange_count").inc()
        # how the rows reach destination order (ride_rule; on the two-hop
        # route hop 2's: hop 1 sorts one operand more, the target sidecar)
        sort_ops, no_ride = ride_rule(cols)
        # how UNEVEN it was: the fullest destination's rows and the capacity
        # bucket they set (every chip's receive buffers, and so every later
        # whole-shard program's shape, are sized by the fullest chip)
        _metrics.counter("exchange_recv_max_rows_total").inc(recv_max)
        _metrics.counter("exchange_recv_cap_rows_total").inc(out_cap)
        route = "two_hop" if hplan is not None else "flat"
        topo_t = _topo.topology(mesh)
        tiers = None
        if topo_t.n_slices > 1:
            # always-on per-tier counters on MULTI-SLICE topologies only
            # (host numpy on the replicated sidecar; single-slice rigs skip
            # on one cached field load): payload rows/bytes split by which
            # tier the row's journey crosses, plus the PADDED wire volume
            # and (src, dst, round) message count each tier's links carry —
            # the DCN message count is the two-hop route's exactly-1/R
            # acceptance instrument (docs/topology.md, bench --slices).
            from ..topo import exchange as _topo_exchange
            ici_rows, dcn_rows = _topo.tier_split(counts, topo_t)
            traffic = _topo_exchange.tier_traffic(
                topo_t, counts, row_bytes, route, prep=hprep,
                flat_block_rounds=(block, rounds) if hplan is None else None)
            _metrics.counter("exchange_ici_rows_total").inc(ici_rows)
            _metrics.counter("exchange_dcn_rows_total").inc(dcn_rows)
            _metrics.counter("exchange_ici_bytes_total").inc(
                ici_rows * row_bytes)
            _metrics.counter("exchange_dcn_bytes_total").inc(
                dcn_rows * row_bytes)
            _metrics.counter("exchange_ici_wire_bytes_total").inc(
                traffic["wire_ici"])
            _metrics.counter("exchange_dcn_wire_bytes_total").inc(
                traffic["wire_dcn"])
            _metrics.counter("exchange_ici_messages_total").inc(
                traffic["msgs_ici"])
            _metrics.counter("exchange_dcn_messages_total").inc(
                traffic["msgs_dcn"])
            tiers = {"slice_ids": topo_t.slice_ids(), "route": route,
                     **traffic}
        if _comm.armed() or _plan.active():
            # per-(src,dst) matrix + plan-node attribution (armed runs /
            # active EXPLAIN ANALYZE only — the happy path skips on two
            # cached loads)
            _plan.record_exchange(counts, row_bytes, site=owner, tiers=tiers)
    # the exchange's host side on every sink of timing.span (profiler
    # trace, flight recorder): what it moved, and how long the host took
    # to enqueue its programs (docs/observability.md)
    with timing.span("exchange." + route, rows=total,
                     bytes=total * row_bytes, recv_max=recv_max,
                     recv_cap=out_cap, block=block, rounds=rounds,
                     site=owner, path="perm" if no_ride else "ride",
                     sort_operands=sort_ops):
        if hplan is not None:
            # the voted hierarchical route (cylon_tpu/topo/exchange): the
            # plan hash is consensus-adopted BEFORE the first hierarchical
            # collective (one set lookup after the first exchange), then
            # phase B runs as slice-local ICI alignment + one aggregated
            # cross-slice DCN hop — bit- and order-equal to the flat
            # branch below (docs/topology.md)
            _topo.ensure_adopted(mesh, hplan)
            outs, _pd = _topo_exchange.two_hop(mesh, hplan, tgt, counts,
                                               tuple(cols), out_cap,
                                               prep=hprep)
        else:
            if rounds > 1:
                # countable path marker (tests/test_fuzz.py regime tier):
                # the multi-round protocol actually engaged
                timing.bump("exchange.multiround")
            counts_i = np.asarray(counts, np.int32)
            srt = sort_by_target(mesh, w, tgt, cols)
            outs = tuple(_alloc_fn(mesh, out_cap, str(c.dtype),
                                   c.shape[1:])() for c in cols)
            # all rounds run in ONE compiled program (fori_loop if
            # rounds>1)
            fn = _round_fn(mesh, w, block, out_cap, max(rounds, 1))
            outs = fn(counts_i, outs, srt)
    with timing.span("host.exchange_close"):
        # integrity audit tier (exec/integrity, docs/robustness.md): the
        # corruption drill first (so the audit below is what catches it),
        # then the always-on conservation laws — pure host math on the
        # already-pulled sidecar, zero device work — then, ARMED only
        # (CYLON_TPU_AUDIT=1), fingerprint conservation across the route:
        # the XOR content fingerprint of the valid input rows must equal
        # the delivered outputs', whichever route carried them
        from ..exec import integrity as _integrity, recovery as _recovery
        if _recovery.maybe_inject("exchange.corrupt",
                                  intercept=("corrupt",)) == "corrupt":
            _recovery._record("exchange.corrupt", "corrupt", "flipped")
            outs = _integrity.flip_one(mesh, outs, per_dest)
        _integrity.conserve_exchange(counts, per_dest, total, row_bytes,
                                     site=owner)
        if _integrity.armed():
            _integrity.verify_exchange(mesh, tgt, cols, outs, per_dest,
                                       site=owner)
        if guard:
            # HBM-ledger accounting of the receive allocation (exec/memory):
            # one registration PER buffer, each anchored to its own array, so
            # the balance tracks exactly the buffers still alive (the lane
            # matrix usually dies at rebuild; f64 side arrays live on as the
            # table's columns).  Non-spillable — an exchange output has no
            # cheap re-entry path.
            from ..exec import memory
            for arr in outs:
                memory.register(owner, (arr,), anchor=arr)
    return outs, per_dest.astype(np.int64)


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry) — the jaxpr pass
# verifies the exchange engine's SPMD invariants.  The high-value check is
# _round_fn: its all_to_all must stay UNCONDITIONAL — the multi-round path
# runs it under a static-trip-count fori_loop (lowered to scan, identical
# on every rank: allowed), never under cond/while (rank-divergent
# participation deadlocks the mesh).  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _trace_round(mesh):
    w, cap, S = _decl_shapes(mesh)
    block, out_cap, rounds = cap // 4, 2 * cap, 3
    fn = _unwrap(_round_fn(mesh, w, block, out_cap, rounds))
    one = _unwrap(_round_fn(mesh, w, cap, out_cap, 1))
    i32 = np.int32

    def both(counts, outs, srt):
        # single-round and scan-wrapped multi-round paths in one walk
        a = one(counts, outs, srt)
        b = fn(counts, outs, srt)
        return a, b

    args = (S((w, w), i32), (S((w * out_cap,), np.int64),),
            (S((w * cap,), np.int64),))
    return jax.make_jaxpr(both)(*args)


def _trace_hash_targets(mesh):
    w, cap, S = _decl_shapes(mesh)
    fn = _unwrap(_hash_targets_fn(mesh, w, 1, True))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w * cap,), np.int64),
                              S((w * cap,), np.bool_))


def _trace_count(mesh):
    w, cap, S = _decl_shapes(mesh)
    fn = _unwrap(_count_fn(mesh, w))
    return jax.make_jaxpr(fn)(S((w * cap,), np.int32))


def _trace_skew_targets(mesh):
    w, cap, S = _decl_shapes(mesh)
    fn = _unwrap(_skew_targets_fn(mesh, w, 2, 1))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((2,), np.uint32),
                              S((w * cap,), np.int64),
                              S((w * cap,), np.bool_))


def _trace_skew_split_targets(mesh):
    w, cap, S = _decl_shapes(mesh)
    fn = _unwrap(_skew_split_targets_fn(mesh, w, 2, 1, (True,), (False,)))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w, 2), np.int64),
                              S((2,), np.int64), S((2,), np.int32),
                              S((w * cap,), np.int64),
                              S((w * cap,), np.bool_),
                              S((2,), np.int64), S((2,), np.bool_))


def _trace_prep(mesh):
    w, cap, S = _decl_shapes(mesh)
    ride = _unwrap(_prep_fn(mesh, w, True))
    perm = _unwrap(_prep_fn(mesh, w, False))

    def both(tgt, lanes, side):
        # the riding sort, and the permutation with its gathers
        return ride(tgt, (lanes, tgt)), perm(tgt, (lanes, side))

    return jax.make_jaxpr(both)(S((w * cap,), np.int32),
                                S((w * cap, 2), np.uint32),
                                S((w * cap,), np.float64))


from ..analysis.registry import (declare_builder, decl_shapes as _decl_shapes,  # noqa: E402
                                 unwrap as _unwrap)

declare_builder(f"{__name__}._round_fn", _trace_round,
                collectives={"all_to_all"}, tags=("shuffle",))
declare_builder(f"{__name__}._hash_targets_fn", _trace_hash_targets,
                tags=("shuffle",))
declare_builder(f"{__name__}._count_fn", _trace_count, tags=("shuffle",))
declare_builder(f"{__name__}._skew_targets_fn", _trace_skew_targets,
                tags=("shuffle", "skew"))
declare_builder(f"{__name__}._skew_split_targets_fn",
                _trace_skew_split_targets, tags=("shuffle", "skew"))
declare_builder(f"{__name__}._prep_fn", _trace_prep, tags=("shuffle",))
