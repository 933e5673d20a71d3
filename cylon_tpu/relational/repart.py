"""Shuffle / repartition / slice / head / tail / concat.

TPU-native equivalents of the reference's redistribution operators:
``Shuffle`` (table.cpp:1298), ``Repartition`` (table.cpp:1481 — allgather row
counts -> compute send ranges -> order-preserving all-to-all, index math in
repartition.hpp:32-129), ``Slice``/``DistributedSlice`` (indexing/slice.cpp:31)
and ``DistributedHead/Tail`` (table.hpp:512-527), ``Merge``/concat.

Order preservation falls out of the exchange engine's (source rank, source
position) receive order (parallel/shuffle.py) exactly as in the reference's
``all_to_all_arrow_tables_preserve_order`` (table.cpp:182-190): each source
sends every destination a contiguous global range, so rank-major receive
order reconstructs global order.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.table import Table
from ..ctx.context import CylonEnv
from ..ops import sort as sortk
from ..parallel import shuffle
from ..status import InvalidError
from ..obs import metrics as _metrics
from ..utils.host import host_array
from ..utils.stages import stage
from .common import ROW, REP, build_table, col_arrays, live_mask, \
    unify_dictionaries_many

shard_map = jax.shard_map


# ---------------------------------------------------------------------------
# column flattening for the exchange engine
# ---------------------------------------------------------------------------

@lru_cache(maxsize=config.PROGRAM_CACHE_SIZE)
def _pack_cols_fn(spec):
    from ..ops import lanes

    def fn(datas, valids):
        return lanes.pack_lanes(spec, list(datas), list(valids))

    return jit(fn)


@lru_cache(maxsize=config.PROGRAM_CACHE_SIZE)
def _unpack_cols_fn(spec):
    from ..ops import lanes

    def fn(mat):
        datas, valids = lanes.unpack_lanes(spec, mat)
        return (tuple(d for d in datas if d is not None),
                tuple(v for v in valids if v is not None))

    return jit(fn)


def _flatten_for_exchange(table: Table):
    """Table columns -> the exchange/collective payload tuple + a rebuild
    recipe.

    Every laneable column (data AND bit-packed validity — 32 nullable
    columns per u32 lane) packs into ONE (cap, L) u32 lane matrix via
    :mod:`cylon_tpu.ops.lanes`, so whatever moves the payload (all_to_all
    rounds, allgather, bcast) issues one collective/scatter chain per
    ROUND, not per column; host-known ``Column.bounds``
    (:func:`~.common.fits_int32`) narrow int64 columns to one lane.  f64
    columns (not laneable on TPU) travel as side arrays.  The matrix is a
    full-shard copy that lives until the move completes — the exchange's
    W·block memory bound applies to its per-round buffers, not to this
    staging copy."""
    from .common import table_lane_spec
    items = list(table.columns.items())
    cols = [c for _, c in items]
    spec = table_lane_spec(cols)
    flat = []
    if spec.n_lanes:
        flat.append(_pack_cols_fn(spec)(tuple(c.data for c in cols),
                                        tuple(c.validity for c in cols)))
    for c, cl in zip(cols, spec.cols):
        if not cl.lanes:
            flat.append(c.data)
    recipe = (spec, tuple((name, c.type, c.dictionary, c.bounds)
                          for name, c in items))
    return tuple(flat), recipe


def _rebuild(recipe, new_flat, valid_counts, env: CylonEnv) -> Table:
    spec, metas = recipe
    if spec.n_lanes:
        datas, valids = _unpack_cols_fn(spec)(new_flat[0])
        side = list(new_flat[1:])
    else:
        datas, valids = (), ()
        side = list(new_flat)
    datas, valids = list(datas), list(valids)
    cols = {}
    di = vi = si = 0
    for (name, t, dc, b), cl in zip(metas, spec.cols):
        if cl.lanes:
            d = datas[di]
            di += 1
        else:
            d = side[si]
            si += 1
        v = None
        if cl.valid_bit >= 0:
            v = valids[vi]
            vi += 1
        # exchanged rows are a permutation + zero padding of the input values
        nb = (min(b[0], 0), max(b[1], 0)) if b is not None else None
        cols[name] = Column(d, t, v, dc, bounds=nb)
    return Table(cols, env, np.asarray(valid_counts, np.int64))


# ---------------------------------------------------------------------------
# hash shuffle (reference Shuffle, table.cpp:1298)
# ---------------------------------------------------------------------------

def shuffle_table(table: Table, key_names,
                  owner: str = "shuffle.recv") -> Table:
    """Redistribute rows so equal keys land on the same shard (hash
    partitioning, reference MapToHashPartitions + ArrowAllToAll).
    ``owner`` labels the receive buffers' ledger registration —
    streaming appends pass ``stream.recv`` (cylon_tpu/stream)."""
    env = table.env
    # every distributed op shuffles, so this is the serving tier's
    # coarse interleave point for monolithic (non-pipelined) plans —
    # a no-op outside a scheduler (docs/serving.md)
    from ..exec import scheduler
    scheduler.maybe_yield()
    if env.world_size == 1:
        return table
    from ..obs import plan as _plan
    from ..utils import timing
    with _plan.node("shuffle", keys=tuple(key_names), owner=owner) as pn:
        if pn:
            pn.set(rows_in=table.row_count, rows_out=table.row_count)
        keys = [table.column(n) for n in key_names]
        datas, valids = col_arrays(keys)
        tgt = shuffle.hash_targets(env.mesh, datas, valids,
                                   table.valid_counts)
        counts = shuffle.count_targets(env.mesh, tgt)
        # the lane pack / unpack programs are plain ``jit`` calls (no
        # ``launch.*`` span): their enqueue is host work of the turn
        with timing.span("host.exchange_pack"):
            flat, recipe = _flatten_for_exchange(table)
        # hash shuffles run under join/groupby/setops OOM fallbacks: the
        # receive-budget guard may preempt a doomed allocation
        new_flat, new_valid = shuffle.exchange(env.mesh, tgt, counts, flat,
                                               guard=True, owner=owner)
        with timing.span("host.exchange_unpack"):
            return _rebuild(recipe, new_flat, new_valid, env)


def exchange_by_targets(table: Table, tgt, counts: np.ndarray,
                        owner: str = "shuffle.recv") -> Table:
    """Exchange with caller-computed per-row targets (range partition etc.)."""
    flat, recipe = _flatten_for_exchange(table)
    out = shuffle.exchange(table.env.mesh, tgt, counts, flat, owner=owner)
    return _rebuild(recipe, *out, table.env)


# ---------------------------------------------------------------------------
# repartition (reference table.cpp:1481, repartition.hpp:94 index math)
# ---------------------------------------------------------------------------
@program_cache()
def _range_targets_fn(mesh: Mesh, cap: int):
    def per_shard(vc, offs, bounds, _probe):
        w = vc.shape[0]
        my = jax.lax.axis_index(shuffle.ROW_AXIS)
        # int32 iota for the mask only; gpos below stays int64 — GLOBAL
        # row positions legitimately exceed int32 at multi-billion rows
        mask = jnp.arange(cap, dtype=jnp.int32) < vc[my]
        gpos = offs[my] + jnp.arange(cap, dtype=jnp.int64)
        # bounds[d] = last global row index destined to d; first d with
        # bounds[d] >= gpos owns the row (empty destinations skip naturally)
        t = jnp.searchsorted(bounds, gpos, side="left").astype(jnp.int32)
        t = jnp.clip(t, 0, w - 1)
        return jnp.where(mask, t, jnp.int32(w))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, REP, ROW), out_specs=ROW))


def _order_preserving_targets(table: Table, dest_counts: np.ndarray):
    """Per-row destination ranks assigning global row i to the destination
    whose cumulative range contains i (reference DivideRowsEvenly /
    RowIndicesToAll, repartition.hpp:32-129)."""
    env = table.env
    vc = table.valid_counts
    offs = np.concatenate([[0], np.cumsum(vc)[:-1]]).astype(np.int64)
    bounds = np.cumsum(dest_counts).astype(np.int64) - 1
    probe = next(iter(table.columns.values())).data
    fn = _range_targets_fn(env.mesh, table.capacity)
    # sidecars stay numpy: jit places them per the shard_map specs on the
    # env's mesh; an eager jnp.asarray would land on the default backend
    return fn(np.asarray(vc, np.int32), offs, bounds, probe)


def even_partition_counts(total: int, w: int) -> np.ndarray:
    """The default order-preserving split: ``total`` global rows divided
    as evenly as possible over ``w`` partitions, earlier partitions
    taking the remainder — the host side of the
    :func:`_order_preserving_targets` index math (reference
    ``DivideRowsEvenly``, repartition.hpp:32).  Shared by
    :func:`repartition` and the elastic checkpoint re-shard path
    (``exec/checkpoint.py``), which re-blocks committed host pages onto
    a different-world mesh through the SAME split so a resharded resume
    lands on the exact distribution a fresh :func:`repartition` would
    produce."""
    total, w = int(total), int(w)
    base = total // w
    extra = total - base * w
    return np.asarray([base + (1 if i < extra else 0) for i in range(w)],
                      np.int64)


@program_cache()
def _pos_targets_fn(mesh: Mesh, cap: int):
    """Destination ranks from CALLER-COMPUTED global row positions (the
    skew stitch, relational/skew.py): destination d owns global positions
    [dof[d], dof[d] + dest[d]) of the even order-preserving layout.
    Padding rows (and the caller's ``total`` sentinel) route to the trash
    destination W.  Same index math as :func:`_range_targets_fn`, with
    ``pos`` replacing the contiguous ``offs[my] + iota`` range."""

    def per_shard(vc, bounds, pos):
        w = bounds.shape[0]
        my = jax.lax.axis_index(shuffle.ROW_AXIS)
        mask = jnp.arange(cap, dtype=jnp.int32) < vc[my]
        t = jnp.searchsorted(bounds, pos, side="left").astype(jnp.int32)
        t = jnp.clip(t, 0, w - 1)
        return jnp.where(mask, t, jnp.int32(w))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW), out_specs=ROW))


@program_cache()
def _sort_flat_by_pos_fn(mesh: Mesh, cap: int, n_arrs: int):
    """Per-shard stable reorder of exchanged payload arrays by their
    received global positions: the exchange delivers (source rank, source
    position) order, but the stitch's positions interleave sources — one
    local sort puts every destination shard into global-position order.
    Padding slots (zeros from the exchange's receive buffers) sort last
    via the int64-max sentinel.  Pure-local; no collective."""
    big = jnp.int64(np.iinfo(np.int64).max)

    def per_shard(vc, pos, *arrs):
        my = jax.lax.axis_index(shuffle.ROW_AXIS)
        live = jnp.arange(cap, dtype=jnp.int32) < vc[my]
        key = jnp.where(live, pos, big)
        idx = jnp.arange(cap, dtype=jnp.int32)
        _, perm = jax.lax.sort((key, idx), num_keys=1, is_stable=True)
        return tuple(a[perm] for a in arrs)

    specs = (REP,) + (ROW,) * (1 + n_arrs)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=(ROW,) * n_arrs))


def place_by_global_pos(table: Table, pos, total: int) -> Table:
    """Redistribute ``table``'s rows onto the even order-preserving layout
    (:func:`even_partition_counts`) by their caller-computed GLOBAL row
    positions ``pos`` (device int64, the table's row layout; padding rows
    must carry the ``total`` sentinel).  Positions must be a permutation
    of [0, total).  The receiving shard locally sorts its rows by position
    (the exchange's (src, pos) receive order interleaves sources), so the
    result reads back in exactly position order — the merge half of the
    skew-split stitch's bit/order-equality contract
    (relational/skew.stitch_join_output, docs/skew.md)."""
    env = table.env
    w = env.world_size
    total = int(total)
    if total == 0 or not table.column_count:
        return table
    from ..utils import timing
    dest = even_partition_counts(total, w)
    bounds = np.cumsum(dest).astype(np.int64) - 1
    vc32 = np.asarray(table.valid_counts, np.int32)
    cap = max(table.capacity, 1)
    with timing.region("place.targets"):
        tgt = _pos_targets_fn(env.mesh, cap)(vc32, bounds, pos)
        counts = shuffle.count_targets(env.mesh, tgt)
    with timing.region("place.exchange"):
        flat, recipe = _flatten_for_exchange(table)
        new_flat, new_valid = shuffle.exchange(env.mesh, tgt, counts,
                                               flat + (pos,))
    if not np.array_equal(np.asarray(new_valid, np.int64), dest):
        raise InvalidError(
            f"place_by_global_pos: received counts {list(new_valid)} do "
            f"not match the even layout {list(dest)} — positions are not "
            "a permutation of the claimed total")
    with timing.region("place.sort"):
        out_cap = new_flat[0].shape[0] // w
        fn = _sort_flat_by_pos_fn(env.mesh, out_cap, len(new_flat) - 1)
        sorted_flat = fn(np.asarray(new_valid, np.int32), new_flat[-1],
                         *new_flat[:-1])
    return _rebuild(recipe, sorted_flat, new_valid, env)


def repartition(table: Table, rows_per_partition=None) -> Table:
    """Redistribute preserving global row order; default = even split."""
    from ..obs import plan as _plan
    env = table.env
    w = env.world_size
    total = table.row_count
    if rows_per_partition is None:
        dest = even_partition_counts(total, w)
    else:
        dest = np.asarray(rows_per_partition, np.int64)
        if dest.shape != (w,) or dest.sum() != total:
            raise InvalidError(
                f"rows_per_partition must hold {w} counts summing to {total}")
    if w == 1 or not table.column_count:
        return table
    if np.array_equal(dest, table.valid_counts):
        return table
    with _plan.node("repartition", order_preserving=True) as pn:
        if pn:
            pn.set(rows_in=total, rows_out=total)
        tgt = _order_preserving_targets(table, dest)
        # count matrix is fully determined host-side: source s's global
        # range [offs, offs+vc) intersected with each destination range
        soff = np.concatenate([[0], np.cumsum(table.valid_counts)[:-1]])
        dof = np.concatenate([[0], np.cumsum(dest)[:-1]])
        counts = np.zeros((w, w), np.int64)
        for s in range(w):
            lo, hi = soff[s], soff[s] + table.valid_counts[s]
            for d in range(w):
                counts[s, d] = max(
                    0, min(hi, dof[d] + dest[d]) - max(lo, dof[d]))
        return exchange_by_targets(table, tgt, counts)


@program_cache()
def _repad_fn(mesh: Mesh, cap: int, new_cap: int):
    def per_shard(d):
        if new_cap <= cap:
            return d[:new_cap]
        pad = jnp.zeros((new_cap - cap,) + d.shape[1:], d.dtype)
        return jnp.concatenate([d, pad])

    return jit(shard_map(per_shard, mesh=mesh, in_specs=ROW,
                             out_specs=ROW))


def repad_table(table: Table, new_cap: int) -> Table:
    """Change per-shard capacity without moving rows (valid prefixes must fit
    the new capacity)."""
    cap = table.capacity
    if new_cap == cap:
        return table
    if int(table.valid_counts.max(initial=0)) > new_cap:
        raise InvalidError(f"valid rows exceed new capacity {new_cap}")
    fn = _repad_fn(table.env.mesh, cap, new_cap)
    cols = {}
    for n, c in table.columns.items():
        d = fn(c.data)
        v = fn(c.validity) if c.validity is not None else None
        # pad rows are zeros -> widen bounds to include 0
        b = c.bounds
        if b is not None and new_cap > cap:
            b = (min(b[0], 0), max(b[1], 0))
        cols[n] = Column(d, c.type, v, c.dictionary, bounds=b)
    return Table(cols, table.env, table.valid_counts)


# ---------------------------------------------------------------------------
# slice / head / tail (reference indexing/slice.cpp:31, table.hpp:512-527)
# ---------------------------------------------------------------------------

@program_cache()
def _compact_range_fn(mesh: Mesh, cap: int, out_cap: int, spec):
    from ..ops import lanes

    def per_shard(vc, offs, lo, hi, datas, valids):
        my = jax.lax.axis_index(shuffle.ROW_AXIS)
        mask = jnp.arange(cap) < vc[my]
        gpos = offs[my] + jnp.arange(cap, dtype=jnp.int64)
        keep = mask & (gpos >= lo) & (gpos < hi)
        idx, _total = sortk.compact_by_flag(keep, out_cap)
        # ONE lane-matrix gather for all columns (+ f64 side gathers)
        return lanes.gather_columns(spec, list(datas), list(valids), idx)

    return jit(shard_map(
        per_shard, mesh=mesh,
        in_specs=(REP, REP, REP, REP, ROW, ROW), out_specs=(ROW, ROW)))


def slice_table(table: Table, offset: int, length: int) -> Table:
    """Global-order row range [offset, offset+length) (distribution-preserving
    like the reference's DistributedSlice — each rank keeps its overlap)."""
    env = table.env
    vc = table.valid_counts
    offs = np.concatenate([[0], np.cumsum(vc)[:-1]]).astype(np.int64)
    lo, hi = int(offset), int(offset) + int(length)
    kept = np.clip(np.minimum(offs + vc, hi) - np.maximum(offs, lo), 0, None)
    out_cap = config.pow2ceil(int(kept.max()) if kept.size else 1)
    cols = list(table.columns.items())
    datas = tuple(c.data for _, c in cols)
    valids = tuple(c.validity for _, c in cols)
    from .common import table_lane_spec
    fn = _compact_range_fn(env.mesh, table.capacity, out_cap,
                           table_lane_spec([c for _, c in cols]))
    out_d, out_v = fn(np.asarray(vc, np.int32), offs,
                      np.int64(lo), np.int64(hi), datas, valids)
    names = [n for n, _ in cols]
    types = [c.type for _, c in cols]
    dicts = [c.dictionary for _, c in cols]
    return build_table(names, out_d, out_v, types, dicts, kept, env)


def head(table: Table, n: int) -> Table:
    return slice_table(table, 0, n)


def tail(table: Table, n: int) -> Table:
    total = table.row_count
    n = min(n, total)
    return slice_table(table, total - n, n)


# ---------------------------------------------------------------------------
# row filter (reference: compute.pyx filter path — table[bool_mask])
# ---------------------------------------------------------------------------

def path_counters(family: str) -> tuple:
    """``(windowed, {reason: plain})``: one count a materialize dispatch from
    sorted kept positions, by the path its rows took and - where XLA's
    gather moved them - the test that said so: the words of
    ``fused.window_rule``, a table with no u32 lane to stack, a shard
    capacity off the DMA tiling, or a measured tile span past the window."""
    return _metrics.counter(family, path="windowed"), {
        why: _metrics.counter(family, path="plain", reason=why)
        for why in ("not_tpu", "density_below_floor",
                    "segment_space_small", "laneless_only",
                    "unsupported_shape", "span_overflow")}


def _last_kept(srt, n_kept):
    """Position of the shard's last kept row in the sorted take index
    ``srt`` (0 where it keeps nothing): what the padding slots ride at."""
    return jnp.where(n_kept > 0, srt[jnp.maximum(n_kept - 1, 0)],
                     jnp.int32(0))


@program_cache()
def _filter_count_fn(mesh: Mesh, cap: int):
    """``(meta, srt)``: per shard ``meta = [kept rows, widest tile span]``
    - ONE pulled array - and the kept rows' positions in source order, the
    fill ``cap`` behind them: ``ops/groupby.grouped_starts``' one-operand
    unstable sort (the k-th kept row is the k-th smallest kept position),
    handed on the device to whichever materialize program runs.  The span
    is :func:`~cylon_tpu.ops.pallas_gather.max_tile_span`'s, so the host
    knows whether the window serves before a row is moved."""
    from ..ops import groupby as groupbyk, pallas_gather as pg

    def per_shard(vc, flag):
        mask = live_mask(vc, cap)
        n_kept = jnp.sum(flag & mask).astype(jnp.int32)
        with stage("compact"):      # not the grouped reduce's segment_starts
            srt = groupbyk.grouped_starts.__wrapped__(
                flag, mask, jnp.int32(cap), cap)
            span = pg.max_tile_span(srt, _last_kept(srt, n_kept))
        return jnp.stack([n_kept, span]), srt

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW),
                             out_specs=(ROW, ROW)))


@program_cache()
def _filter_mat_fn(mesh: Mesh, cap: int, out_cap: int, spec, window: int):
    """Rows at the take index ``srt[:out_cap]``, padding slots clamped to
    the last kept position (monotone, inside the last real tile's window;
    ``valid_counts`` masks them).  ``window`` > 0: the u32 lanes stacked as
    ROWS and moved by the windowed Pallas take - a filter keeps rows in
    source order -, f64 side columns by XLA's gather at the same index;
    0: everything by ``lanes.gather_columns`` (XLA's gather)."""
    from ..ops import lanes, pallas_gather as pg

    def per_shard(kept, srt, datas, valids):
        n_kept = kept[jax.lax.axis_index(shuffle.ROW_AXIS)]
        with stage("compact"):
            idx = jnp.minimum(srt[:out_cap], _last_kept(srt, n_kept))
        if not window:
            # ONE lane-matrix gather for all columns (+ f64 side gathers)
            return lanes.gather_columns(spec, list(datas), list(valids), idx)
        mat_t = lanes.pack_lane_rows(spec, list(datas), list(valids), 8)
        out_d, out_v = lanes.unpack_lane_rows(
            spec, pg.take_rows_t(mat_t, idx, window))
        out_d = list(out_d)
        for i, d in lanes.gather_laneless(spec, datas, idx).items():
            out_d[i] = d
        return tuple(out_d), out_v

    if mesh is None:        # the body alone: take_kept
        return per_shard
    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


def take_kept(out_cap: int, spec, window: int):
    """:func:`_filter_mat_fn`'s per-shard body ``(kept, srt, datas, valids)
    -> (datas, valids)`` for a program that makes its source first (the set
    operators').  It stays nested up there: Mosaic's kernel text embeds its
    frames, and hoisting it would compile every windowed filter cold."""
    return _filter_mat_fn.__wrapped__(None, 0, out_cap, spec, window)


def kept_positions(flag, src_pos, fill: int):
    """:func:`_filter_count_fn`'s ``(meta, srt)`` for a caller that knows
    its kept rows' SOURCE positions: the flagged ``src_pos`` (distinct, so
    ties are among the fill alone and the sort is unstable) ascending,
    ``fill`` - the source's capacity - behind them."""
    from ..ops import pallas_gather as pg
    with stage("compact"):
        n_kept = jnp.sum(flag, dtype=jnp.int32)
        srt = jax.lax.sort(jnp.where(flag, src_pos, jnp.int32(fill)),
                           is_stable=False)
        span = pg.max_tile_span(srt, _last_kept(srt, n_kept))
    return jnp.stack([n_kept, span]), srt


def filter_window(mesh: Mesh, cap: int, out_cap: int, n_lanes: int,
                  density: float, max_span: int) -> tuple:
    """``(window, why)`` of one filter's materialize dispatch, from what the
    host knows when it dispatches: the grouped reduce's rule on the
    output's capacity and the kept density of the fullest shard
    (``fused.window_rule``: TPU, density >= 0.10, ``out_cap`` >= 2^20),
    then what the kernel can take (a u32 lane to stack, the DMA tiling)
    and the widest tile span the count program measured.  0 = XLA's
    gather, ``why`` the test that said so."""
    from ..ops import pallas_gather as pg
    from . import fused
    window, why = fused.window_rule(mesh, out_cap, density)
    if not window:
        return 0, why
    if not n_lanes:
        return 0, "laneless_only"
    if cap % 128 or not pg.supported(cap, out_cap, n_lanes, window):
        return 0, "unsupported_shape"
    if max_span > window:
        return 0, "span_overflow"
    return window, ""


_FILTER_PATHS = path_counters("filter_dispatches")


def materialize_kept(mesh: Mesh, meta, srt, cap: int, live, n_lanes: int,
                     paths: tuple, program, *source,
                     thinning: bool = False) -> tuple:
    """The host half of a count -> materialize pair whose count program kept
    :func:`_filter_count_fn`'s contract: pull ``meta``, size the output
    (``config.pow2ceil`` of the fullest shard's count; a capacity outside
    that family bounds its own output), ask :func:`filter_window` with the
    kept density of the fullest shard (``live``: the source's live rows a
    shard), count the path in ``paths`` (:func:`path_counters`), launch
    ``program(out_cap, window)`` on ``(counts, srt, *source)``.
    ``thinning``: the kept rows are FIRST OCCURRENCES (the set operators'),
    which thin out along their source, so the rule is asked with the
    density of the widest tile the count program measured where that is
    lower.  Returns ``((datas, valids), counts, said)``, ``said`` the plan
    node's ``path`` / ``window`` / ``density`` / ``max_tile_span``."""
    from ..ops import pallas_gather as pg
    meta = host_array(meta).astype(np.int64).reshape(-1, 2)
    counts, max_span = meta[:, 0], int(meta[:, 1].max())
    out_cap = min(config.pow2ceil(int(counts.max())), cap)
    full = int(counts.argmax())
    density = float(counts[full]) / max(int(live[full]), 1)
    window, why = filter_window(
        mesh, cap, out_cap, n_lanes,
        min(density, pg.TILE / max_span) if thinning else density, max_span)
    windowed, plain = paths
    (plain[why] if why else windowed).inc()
    said = {"path": "plain" if why else "windowed", "window": window,
            "density": round(density, 6), "max_tile_span": max_span}
    out = program(out_cap, window)(counts.astype(np.int32), srt, *source)
    return out, counts, said


def filter_table(table: Table, flag) -> Table:
    """Keep rows whose boolean flag is set (flag: device bool array with the
    table's row layout).  Row order preserved; distribution keeps each row on
    its shard (like the reference's local filter ops).  Plan node ``filter``
    (``cylon.op.filter``: ``columns``, ``rows_in``, ``rows_out``; ``path``,
    ``window``, ``density``, ``max_tile_span``).  The kept rows are a
    subset, so every column keeps its host-known bounds (widened to 0, which
    the output's padding rows may hold).

    Two programs, one pull: the count program sorts the kept positions and
    returns the counts with the widest tile span; :func:`materialize_kept`
    moves the rows at that index - by the windowed Pallas take where
    :func:`filter_window` says the window serves (``filter_dispatches``
    counts the paths), by XLA's gather elsewhere."""
    from ..obs import plan as _plan
    from .common import build_table, table_lane_spec
    env = table.env
    cap = max(table.capacity, 1)
    vc = np.asarray(table.valid_counts, np.int32)
    items = list(table.columns.items())
    ctx = _plan.node("filter", columns=len(items))
    with ctx as pn:
        meta, srt = _filter_count_fn(env.mesh, cap)(vc, flag)
        cols = [c for _, c in items]
        spec = table_lane_spec(cols)
        (out_d, out_v), counts, said = materialize_kept(
            env.mesh, meta, srt, cap, vc, spec.n_lanes, _FILTER_PATHS,
            lambda out_cap, window: _filter_mat_fn(env.mesh, cap, out_cap,
                                                   spec, window),
            *col_arrays(cols))
        rows = {"rows_in": int(vc.sum()), "rows_out": int(counts.sum())}
        ctx.span_args(**rows)
        if pn:
            pn.set(**rows, **said)
        return build_table(
            [n for n, _ in items], out_d, out_v, [c.type for c in cols],
            [c.dictionary for c in cols], counts, env,
            bounds=[None if c.bounds is None else
                    (min(c.bounds[0], 0), max(c.bounds[1], 0))
                    for c in cols])


# ---------------------------------------------------------------------------
# concat (reference Merge/concat, frame.py:2295)
# ---------------------------------------------------------------------------

@program_cache()
def _concat_fn(mesh: Mesh, caps: tuple, out_cap: int, with_valid: tuple):
    """Per-shard append of k tables' live prefixes: each table's FULL padded
    block is block-copied (``dynamic_update_slice`` — contiguous, ~1 ns/row
    vs ~15 ns/row for the scatter this replaces) at its shard's running
    offset, in ascending table order so a block's trailing padding lands in
    the NEXT table's region and is overwritten by its copy.  The scratch
    buffer is ``out_cap + max(caps)`` so the last block never clamps; the
    result is its ``out_cap`` prefix.  Output padding rows are whatever the
    last block's padding held — callers rely on the valid-prefix contract,
    never on zeroed padding."""
    k = len(caps)
    pad_cap = out_cap + max(caps)

    def per_shard(vcs, datas_by_t, valids_by_t):
        my = jax.lax.axis_index(shuffle.ROW_AXIS)
        off = jnp.zeros((), jnp.int32)
        ncols = len(datas_by_t[0])
        outs = [jnp.zeros((pad_cap,), datas_by_t[0][c].dtype)
                for c in range(ncols)]
        outv = [jnp.zeros((pad_cap,), bool) if with_valid[c] else None
                for c in range(ncols)]
        for t in range(k):
            cap_t = caps[t]
            for c in range(ncols):
                outs[c] = jax.lax.dynamic_update_slice(
                    outs[c], datas_by_t[t][c], (off,))
                if with_valid[c]:
                    v = valids_by_t[t][c]
                    v = v if v is not None else jnp.ones(cap_t, bool)
                    outv[c] = jax.lax.dynamic_update_slice(outv[c], v, (off,))
            off = off + vcs[t][my].astype(jnp.int32)
        return (tuple(o[:out_cap] for o in outs),
                tuple(v[:out_cap] if v is not None else None for v in outv))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW), out_specs=(ROW, ROW)))


def concat_tables(tables: list[Table]) -> Table:
    """Row-wise concatenation. Per-shard append order follows input order
    (the reference's per-rank local Merge has the same per-partition
    semantics)."""
    if not tables:
        raise InvalidError("concat of zero tables")
    if len(tables) == 1:
        return tables[0]
    env = tables[0].env
    names = tables[0].column_names
    for t in tables[1:]:
        if t.column_names != names:
            raise InvalidError(f"concat schema mismatch: {t.column_names} vs {names}")
    # unify string dictionaries / promote numerics column-wise
    from ..core.dtypes import LogicalType
    from .common import promote_key_pair
    col_sets = []
    for n in names:
        cs = [t.column(n) for t in tables]
        if cs[0].type == LogicalType.STRING:
            cs = unify_dictionaries_many(cs)
        elif all(c.type == LogicalType.LIST for c in cs):
            # merge the passthrough value stores; later tables' codes
            # shift by the cumulative store length
            from ..core.column import PassthroughValues
            vals = [c.dictionary.values for c in cs]
            offs = np.cumsum([0] + [len(v) for v in vals[:-1]])
            merged = PassthroughValues(np.concatenate(vals)
                                       if vals else np.zeros(0, object))
            hi = max(len(merged) - 1, 0)
            cs = [Column(c.data + int(o), LogicalType.LIST, c.validity,
                         merged, bounds=(0, hi))
                  for c, o in zip(cs, offs)]
        elif all(c.type == LogicalType.DECIMAL for c in cs):
            # ONE pass to the common scale: pairwise promotion would leave
            # middle columns at a stale scale while the output dictionary
            # takes the final (largest) one — silent corruption, since
            # decimals share int64 storage
            from .common import rescale_decimals_many
            cs = rescale_decimals_many(cs)
        elif len({c.type for c in cs}) == 1:
            pass
        else:
            for i in range(1, len(cs)):
                cs[0], cs[i] = promote_key_pair(cs[0], cs[i])
            # pairwise promotion converges on cs[0]'s final type; bring
            # every middle column to it in a second sweep (mixed numeric
            # middles otherwise keep a stale dtype)
            final = cs[0].type
            cs = [c if c.type == final else promote_key_pair(cs[0], c)[1]
                  for c in cs]
        col_sets.append(cs)
    w = env.world_size
    vcs = [t.valid_counts for t in tables]
    new_valid = np.sum(vcs, axis=0)
    out_cap = config.pow2ceil(int(new_valid.max()) if w else 1)
    caps = tuple(t.capacity for t in tables)
    with_valid = tuple(any(cs[i].validity is not None for i in range(len(tables)))
                       for cs in col_sets)
    datas_by_t = tuple(tuple(col_sets[c][t].data for c in range(len(names)))
                       for t in range(len(tables)))
    valids_by_t = tuple(tuple(col_sets[c][t].validity for c in range(len(names)))
                        for t in range(len(tables)))
    fn = _concat_fn(env.mesh, caps, out_cap, with_valid)
    vcs_host = tuple(np.asarray(v, np.int32) for v in vcs)
    out_d, out_v = fn(vcs_host, datas_by_t, valids_by_t)
    types = [cs[0].type for cs in col_sets]
    dicts = [cs[0].dictionary for cs in col_sets]
    # merged bounds (∪ {0}: output padding may expose any block's padding)
    bounds = []
    for cs in col_sets:
        bs = [c.bounds for c in cs]
        bounds.append((min(min(b[0] for b in bs), 0),
                       max(max(b[1] for b in bs), 0))
                      if all(b is not None for b in bs) else None)
    return build_table(names, out_d, out_v, types, dicts, new_valid, env,
                       bounds=bounds)


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry) — pure-local
# shard programs (the exchange rides parallel/shuffle.py); no collective
# may appear.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _trace_range_targets(mesh):
    w = int(mesh.devices.size)
    cap = 1024
    S = jax.ShapeDtypeStruct
    fn = _unwrap(_range_targets_fn(mesh, cap))
    # dtypes mirror the production caller (_order_preserving_targets):
    # int32 valid counts, int64 offsets/bounds — the gate must verify the
    # dtype specialization that actually runs
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w,), np.int64),
                              S((w,), np.int64), S((w * cap,), np.int64))


def _trace_pos_targets(mesh):
    w = int(mesh.devices.size)
    cap = 1024
    S = jax.ShapeDtypeStruct
    fn = _unwrap(_pos_targets_fn(mesh, cap))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w,), np.int64),
                              S((w * cap,), np.int64))


def _trace_sort_flat_by_pos(mesh):
    w = int(mesh.devices.size)
    cap = 1024
    S = jax.ShapeDtypeStruct
    fn = _unwrap(_sort_flat_by_pos_fn(mesh, cap, 2))
    return jax.make_jaxpr(fn)(S((w,), np.int32), S((w * cap,), np.int64),
                              S((w * cap, 3), np.uint32),
                              S((w * cap,), np.float64))


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._range_targets_fn", _trace_range_targets,
                tags=("repart", "shuffle"))
declare_builder(f"{__name__}._pos_targets_fn", _trace_pos_targets,
                tags=("repart", "skew"))
declare_builder(f"{__name__}._sort_flat_by_pos_fn", _trace_sort_flat_by_pos,
                tags=("repart", "skew"))
