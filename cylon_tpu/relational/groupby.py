"""Table-level groupby-aggregate: local + distributed.

TPU-native equivalent of the reference's groupby engines: the two-phase
``DistributedHashGroupBy`` (groupby/groupby.cpp:33 — associative ops combine
locally, shuffle the much smaller per-group intermediates, combine again;
non-associative ops shuffle raw rows first) and the MapReduce engine's
six-stage flow (mapreduce/mapreduce.hpp:56-76).  Group identity is a dense
rank (ops/pack.py) instead of a hash map; aggregations are XLA segment
reductions (ops/groupby.py).

The intermediate "table" between phases reuses the ordinary shuffle engine —
intermediates are just columns keyed by the group keys, exactly how the
reference ships ``MapReduceKernel`` intermediates through ArrowAllToAll.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.dtypes import LogicalType, from_numpy_dtype, physical_np_dtype
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..obs import metrics as _metrics
from ..ops import groupby as gbk
from ..ops import pack
from ..utils.stages import stage, staged
from ..status import InvalidError
from ..utils import timing
from ..utils.host import host_array
from .common import (PAD_L, REP, ROW, BoundedCache, col_arrays, fold_liveness,
                     live_mask, multi_shard, narrow32_flags, note_liveness)
from .repart import shuffle_table

shard_map = jax.shard_map

_VALID_OPS = gbk.ASSOCIATIVE | gbk.NON_ASSOCIATIVE

#: callsite-signature -> what the site's last dispatch settled on:
#: (segment bucket, windowed gather allowed, window) - written and read by
#: :func:`dispatch_at_bucket` alone
_SEG_CACHE = BoundedCache()

#: optimistic first-dispatch segment space for large-cap groupbys with no
#: hysteresis prediction yet: small enough that the dense one-hot regime
#: stays at its ~9 ns/row flat cost — scatter-heavy
#: programs at multi-10M shapes have pathological XLA:TPU compile times
#: (observed 50+ min), while the dense form compiles in seconds.  A
#: mispredict (more groups than this) is detected via the returned
#: n_groups and re-dispatched at the true bucket
#: (:func:`dispatch_at_bucket`).
_FIRST_SEG_CAP = 512

#: always empty: ``benchmark/lib/checks.py`` still reads it (ROADMAP D10)
_PAD_CACHE = BoundedCache()


class PendingReduce:
    """A DISPATCHED (not yet pulled) grouped reduce: the device program is
    enqueued, :meth:`resolve` pulls its meta sidecar and hands back the
    result.  A range-partitioned pipeline consumes one fused groupby per
    piece and each meta pull is a host round trip; begin/resolve lets the
    consumer enqueue piece i+1's program BEFORE pulling piece i's meta —
    one-deep software pipelining of dispatch against pull (the
    reference's ops-DAG keeps pieces in flight the same way,
    cpp/src/cylon/ops/execution/execution.hpp:43 RoundRobin)."""

    __slots__ = ("_fn",)

    def __init__(self, fn):
        self._fn = fn

    def resolve(self):
        return self._fn()


def dispatch_at_bucket(cache, sig, cap_full: int, call, read_meta,
                       window=None, phase: str = "") -> PendingReduce:
    """THE dispatch of a grouped reduce: every reduction, scatter and
    gather of the program runs over ``seg_cap`` slots, and the true group
    count is usually far below the row capacity ``cap_full``.  Enqueues
    ``call(seg_cap, win)`` at the predicted segment bucket — what
    ``cache[sig]`` remembers of this callsite if that is under
    ``cap_full``, :data:`_FIRST_SEG_CAP` on first sight of a larger
    capacity (most groupbys have far fewer groups than rows, and a
    multi-10M-segment program takes XLA:TPU pathologically long to
    compile), else ``cap_full`` — and returns a handle whose ``resolve()``
    pulls ``read_meta(outputs) -> (n_groups per shard, win_ok)`` once per
    dispatch and re-dispatches until the program fits: at the true bucket
    when ``n_groups`` (counted from the group ids themselves, so a
    mispredict is always seen) passes ``seg_cap``, and without the window
    for good after a span overflow (``win_ok`` false: the windowed
    gather's output is garbage).  ``window(seg_cap, n_groups) -> (window,
    why, density)`` (:func:`_density_window`) picks the windowed Pallas
    gather for a re-dispatch from the measured counts (None, or window 0:
    XLA's gather); a first-sight dispatch never has one.  The site's
    memory is ``cache[sig] = (bucket, windowed allowed, window)``;
    ``resolve()`` returns ``(outputs, n_groups)``.  Registry counters
    (``grouped_reduce_windowed_dispatches``, ``..._window_overflows``, a
    plain one's reason) and the node's ``phase``: :func:`_note_settled`."""
    def enqueue(seg_cap, win):
        if win:
            _metrics.counter("grouped_reduce_windowed_dispatches").inc()
        return call(seg_cap, win)

    bucket, allowed, win = cache.get(sig) or (None, True, 0)
    if bucket is not None and bucket < cap_full:
        seg_cap = bucket
    elif bucket is None and cap_full > _FIRST_SEG_CAP:
        seg_cap = _FIRST_SEG_CAP
    else:
        seg_cap = cap_full
    res = enqueue(seg_cap, win)     # ENQUEUED; meta not pulled yet

    def resolve():
        nonlocal res, seg_cap, allowed, win
        while True:
            n_groups, win_ok = read_meta(res)
            bucket = min(config.pow2ceil(int(n_groups.max())
                                         if n_groups.size else 1), cap_full)
            win_ok = win_ok or not win
            if bucket <= seg_cap and win_ok:
                break
            if not win_ok:
                _metrics.counter("grouped_reduce_window_overflows").inc()
            allowed = allowed and win_ok
            seg_cap = max(seg_cap, bucket)
            win = window(seg_cap, n_groups)[0] if window and allowed else 0
            res = enqueue(seg_cap, win)
        cache.put(sig, (bucket, allowed, win))
        _note_settled(bucket, seg_cap, allowed, win, window, n_groups, phase)
        return res, n_groups

    return PendingReduce(resolve)


def _meta_out(n_groups, win_ok, use_window: int):
    """A shard's meta as ONE output, so a dispatch costs one host pull (a
    second transfer is another round trip): ``n_groups`` and, from a
    program asked for the windowed gather, whether it stayed inside its
    spans (``win_ok`` None: no kernel ran).  A program without the window
    has nothing to overflow and keeps the one-number output, and with it
    the program text, it always had."""
    if not use_window:
        return n_groups.reshape(1)
    ok = jnp.ones((), bool) if win_ok is None else win_ok
    return jnp.stack([n_groups, ok.astype(jnp.int32)]).reshape(2)


def _read_meta(world: int, res):
    """``read_meta`` of a program whose last output is :func:`_meta_out`
    on each of ``world`` shards: the group counts and whether every
    shard's windowed gather stayed inside its spans, one pull."""
    meta = host_array(res[-1]).astype(np.int64).reshape(world, -1)
    return meta[:, 0], bool(np.all(meta[:, 1:]))


def _density_window(mesh, live):
    """``window`` of a site whose shards hold ``live`` rows each: the one
    rule (:func:`~.fused.window_rule`) on the MEASURED per-shard group
    density (min over shards): ``(window, why it is 0, that density)``."""
    from . import fused
    live = np.maximum(np.asarray(live, np.int64), 1)

    def window(seg_cap, n_groups):
        dens = float((n_groups / live).min()) if n_groups.size else 0.0
        return (*fused.window_rule(mesh, seg_cap, dens), dens)

    return window


#: static intermediate-column order per op (mapreduce.hpp:27 analog: MEAN ->
#: {sum,count}, VAR/STD -> {sum,sumsq,count})
INTER_NAMES = {
    "sum": ("sum",),
    "sumsq": ("sumsq",),
    "count": ("count",),
    "min": ("count", "min"),
    "max": ("count", "max"),
    "mean": ("count", "sum"),
    "var": ("count", "sum", "sumsq"),
    "std": ("count", "sum", "sumsq"),
}


def _normalize_aggs(aggs):
    """aggs: list of (value_col, op) or (value_col, op, q). Returns list of
    (col, op, q, out_name)."""
    out, seen = [], set()
    for a in aggs:
        col, op = a[0], a[1]
        q = a[2] if len(a) > 2 else 0.5
        if op == "median":
            op, q = "quantile", 0.5
        if op not in _VALID_OPS:
            raise InvalidError(f"unknown aggregation {op!r}")
        name = f"{col}_{a[1]}"
        if op == "quantile" and a[1] == "quantile" and len(a) > 2:
            name = f"{col}_quantile_{q:g}"
        if name in seen:
            raise InvalidError(f"duplicate aggregation output {name!r}")
        seen.add(name)
        out.append((col, op, float(q), name))
    return out


def _group_keys(by_datas, by_valids, vc, grouped: bool = False,
                narrow: tuple | None = None, fold: bool = False):
    """Per-shard dense group ids; padding rows route to trash segment ``cap``
    and never contribute a group (live rows sort first - ``fold``: as
    :func:`_sort_state`'s - so live ranks are a dense prefix 0..n_groups-1).

    ``grouped=True`` (table carries ``grouped_by`` metadata — join/sort
    output): equal keys are already contiguous, so ids come from boundary
    flags + prefix sum instead of a rank sort.  ``narrow`` = static
    narrow32 flags for the sort-operand packing (see common.narrow32_flags).
    """
    cap = by_datas[0].shape[0]
    mask = live_mask(vc, cap)
    if grouped:
        gids, n_groups, first = pack.grouped_gids(list(by_datas),
                                                  list(by_valids), mask,
                                                  narrow)
        return gids, n_groups, mask, first
    ko = pack.key_operands(list(by_datas), list(by_valids), row_mask=mask,
                           pad_key=PAD_L, narrow32=narrow, fold=fold)
    gids, _ = pack.dense_rank(ko)
    n_groups = jnp.max(jnp.where(mask, gids, -1)) + 1
    gids = jnp.where(mask, gids, cap)
    return gids, n_groups.astype(jnp.int32), mask, None


@staged("liveness")
def _value_mask(mask, val, valid):
    """Row mask for aggregation payloads: live row AND valid AND (for float
    payloads) not-NaN — pandas skipna=True semantics (NaN is stored as a
    float payload with validity=None, so validity alone misses it)."""
    vmask = mask if valid is None else (mask & valid)
    if jnp.issubdtype(val.dtype, jnp.floating):
        vmask = vmask & ~jnp.isnan(val)
    return vmask


def _plan_vspec(val_cols, by_cols, narrow, n_inters: int = 1):
    """Sort-path eligibility: a LaneSpec over (value cols ++ key cols) when
    the measured cost model favors riding the rank sort over per-op segment
    scatters.  Laneable columns cost ~1.7 ns/row/lane as sort payload; f64
    columns (laneless — any f64 bitcast/sort-payload SIGSEGVs the XLA:TPU
    compiler, measured v5e libtpu 2026-07) ride via ONE u32 row-index
    payload lane + one batched (n, K) f64 side-matrix gather at the sort
    permutation (matrix gathers amortize: ~15.5·(1+0.2·(K-1)) ns/row
    total).  The fallback costs ~12 ns/row per scatter-reduced intermediate
    (``n_inters``) plus the dense-rank gid scatter-back — and degrades
    further at tiny group counts where scatter-adds serialize on
    collisions, so ties go to the sort path."""
    from ..ops import lanes
    cand = lanes.plan_lanes(
        tuple(str(c.data.dtype) for c in val_cols + by_cols),
        tuple(c.validity is not None for c in val_cols + by_cols),
        narrow32_flags(val_cols) + narrow)
    n_side = sum(1 for c in cand.cols if not c.lanes)
    sort_ns = 1.7 * (cand.n_lanes + (1 if n_side else 0))
    if n_side:
        sort_ns += 15.5 * (1 + 0.2 * (n_side - 1))
    scatter_ns = 12.0 * max(n_inters, 1) + 8.8
    return cand if sort_ns <= scatter_ns else None


@staged("gather_rows")
def _rep_keys(by_datas, by_valids, gids, seg_cap):
    """Representative key row per group (first source index)."""
    rep = gbk.group_first_index(gids, seg_cap)
    safe = jnp.clip(rep, 0, by_datas[0].shape[0] - 1)
    key_out = tuple(d[safe] for d in by_datas)
    kval_out = tuple(v[safe] if v is not None else None for v in by_valids)
    return key_out, kval_out


def _sort_state(vc, by_datas, by_valids, val_datas, val_valids, narrow,
                vspec, fold: bool = False):
    """THE SORT PATH (non-grouped input): key operands + value/key u32
    payload lanes through one ``lax.sort`` — the input becomes
    run-contiguous, so downstream reductions use the grouped machinery.
    Returns (gids, n_groups, mask, first, by_datas, by_valids, val_datas,
    val_valids) with the column arrays replaced by their sorted versions.
    Padding sorts last (a liveness operand; ``fold``, common.fold_liveness:
    inside the leading key operand), so the live prefix is vc[rank] long."""
    from ..ops import lanes
    cap = by_datas[0].shape[0]
    my = jax.lax.axis_index(ROW_AXIS)
    n_live = vc[my].astype(jnp.int32)
    mask0 = live_mask(vc, cap)
    ko = pack.key_operands(list(by_datas), list(by_valids), row_mask=mask0,
                           pad_key=PAD_L, narrow32=narrow, fold=fold)
    all_datas = list(val_datas) + list(by_datas)
    # n_lanes == 0 (every column laneless f64, none nullable): nothing to
    # pack — the index lane alone carries the permutation
    vmat = (lanes.pack_lanes(vspec, all_datas,
                             list(val_valids) + list(by_valids))
            if vspec.n_lanes else None)
    # laneless (f64) columns cannot ride the sort — any f64 bitcast or sort
    # payload SIGSEGVs the XLA:TPU compiler — so a u32 row-index lane rides and
    # ONE (cap, K) matrix gather at the permutation moves them (~6 ns/row/col
    # at K=5 against ~16 for 1-D gathers, v5e); so do the lanes of a sort past
    # pack.SORT_OPERAND_BUDGET (``wide``: a folded flag counts as what it was)
    laneless = tuple(i for i, c in enumerate(vspec.cols) if not c.lanes)
    nk, nl = len(ko.ops), vspec.n_lanes
    wide = nk + fold + nl + bool(laneless) > pack.SORT_OPERAND_BUDGET
    extra = (jnp.arange(cap, dtype=jnp.uint32),) if laneless or wide else ()
    lane_ops = tuple(vmat[:, j] for j in range(0 if wide else nl))
    with stage("sort_keys"):
        sorted_all = jax.lax.sort(ko.ops + lane_ops + extra,
                                  num_keys=nk, is_stable=False)
    pos = jnp.arange(cap, dtype=jnp.int32)
    with stage("liveness"):
        mask = pos < n_live
    flags = pack.neighbor_flags(sorted_all[:nk], ko.kinds)
    with stage("boundaries"):
        first = (flags.astype(bool) | (pos == 0)) & mask
    with stage("scan"):
        gid = jnp.cumsum(first.astype(jnp.int32)).astype(jnp.int32) - 1
    with stage("boundaries"):
        n_groups = (jnp.max(jnp.where(mask, gid, -1)) + 1).astype(jnp.int32)
        gids = jnp.where(mask, gid, cap)
    if nl:
        smat = (_lanes_at(vmat, sorted_all[-1]) if wide
                else jnp.stack(sorted_all[nk:nk + nl], axis=1))
        sdatas, svalids = map(list, lanes.unpack_lanes(vspec, smat))
    else:
        sdatas = [None] * len(vspec.cols)
        svalids = [None] * len(vspec.cols)
    if laneless:
        with stage("gather_rows"):
            perm = sorted_all[-1].astype(jnp.int32)
            fmat = jnp.stack([all_datas[i] for i in laneless], axis=1)
            fsorted = fmat[perm]
        for j, i in enumerate(laneless):
            sdatas[i] = fsorted[:, j]
    nv = len(val_datas)
    return (gids, n_groups, mask, first, tuple(sdatas[nv:]),
            tuple(svalids[nv:]), tuple(sdatas[:nv]), tuple(svalids[:nv]))


def _runs_reduce(specs_ops, val_datas, vmasks, gids, first, mask, vc,
                 seg_cap, by_datas, by_valids, narrow, vnarrow,
                 use_window: int = 0):
    """Per-op intermediate dicts + representative keys for run-contiguous
    (grouped or freshly sorted) input: every cumsum-able intermediate AND
    the min/max ops' counts ride grouped_reduce's single prefix-diff
    gather (``use_window``: through the windowed Pallas kernel); only the
    min/max extrema themselves need segment scatters.  Ops outside
    CUMSUMMABLE/min/max get no intermediate entry (callers'
    non-associative branches compute their own).  Returns (inters,
    key_out, kval_out, win_ok) - ``win_ok`` is grouped_reduce's."""
    my = jax.lax.axis_index(ROW_AXIS)
    n_live = vc[my].astype(jnp.int32)
    starts = gbk.grouped_starts(first, mask, n_live, seg_cap)
    batch = []      # (batched op name, spec index)
    for i, op in enumerate(specs_ops):
        if op in gbk.CUMSUMMABLE:
            batch.append((op, i))
        elif op in ("min", "max"):
            batch.append(("count", i))
    inters_b, key_out, kval_out, win_ok = gbk.grouped_reduce(
        [b[0] for b in batch], [val_datas[b[1]] for b in batch],
        [vmasks[b[1]] for b in batch], starts, n_live,
        list(by_datas), list(by_valids), seg_cap, key_narrow=narrow,
        sum_forms=[(vnarrow[b[1]] if vnarrow else None)
                   for b in batch], use_window=use_window,
        blocked_scans=multi_shard())
    inters: dict = {}
    for (op, i), d in zip(batch, inters_b):
        inters.setdefault(i, {}).update(d)
    for i, op in enumerate(specs_ops):
        if op == "min":
            inters[i]["min"] = gbk.seg_min(val_datas[i], gids, seg_cap,
                                           vmasks[i])
        elif op == "max":
            inters[i]["max"] = gbk.seg_max(val_datas[i], gids, seg_cap,
                                           vmasks[i])
    return inters, key_out, kval_out, win_ok


@program_cache()
def _combine_fn(mesh: Mesh, ops: tuple, seg_cap: int, grouped: bool,
                narrow: tuple, vnarrow: tuple = (), vspec=None,
                val_map: tuple = (), use_window: int = 0, fold: bool = False):
    """Phase 1 per shard: group keys, reduce each (col, op) into
    intermediate arrays of static length seg_cap (rank-ordered dense
    prefix), gather per-group key representatives.  With ``vspec`` the
    value/key columns ride the rank sort (see :func:`_sort_state`) and the
    intermediates come from the run-contiguous prefix-diff machinery
    instead of per-op segment scatters.  Sum intermediates never ride ONE
    lane here — phase 2 sums them AGAIN across shards, so the
    single-shard rows·max|v| < 2^31 proof does not cover them — which is
    about the lane, not the scan: ``vnarrow`` (:func:`_raw_fn`'s, asked of
    :func:`sum_scan_form` with ``two_lanes``) scans a sum whose values fit
    int32 in 32 bits.  ``use_window`` and the last output: as
    :func:`_raw_fn`'s."""

    def per_shard(vc, by_datas, by_valids, uval_datas, uval_valids):
        if vspec is not None and not grouped:
            (gids, n_groups, mask, first, by_datas, by_valids, uval_datas,
             uval_valids) = _sort_state(vc, by_datas, by_valids, uval_datas,
                                        uval_valids, narrow, vspec, fold)
        else:
            gids, n_groups, mask, first = _group_keys(by_datas, by_valids, vc,
                                                      grouped, narrow, fold)
        val_datas = tuple(uval_datas[j] for j in val_map)
        val_valids = tuple(uval_valids[j] for j in val_map)
        vmasks = [_value_mask(mask, val_datas[i], val_valids[i])
                  for i in range(len(ops))]
        win_ok = None
        if first is not None:
            inters, key_out, kval_out, win_ok = _runs_reduce(
                ops, val_datas, vmasks, gids, first, mask, vc, seg_cap,
                by_datas, by_valids, narrow, vnarrow, use_window)
            inter_out = [tuple(inters[i][k] for k in INTER_NAMES[op])
                         for i, op in enumerate(ops)]
        else:
            key_out, kval_out = _rep_keys(by_datas, by_valids, gids, seg_cap)
            inter_out = []
            for i, op in enumerate(ops):
                inter = gbk.combine_locally(op, val_datas[i], gids, seg_cap,
                                            vmasks[i])
                inter_out.append(tuple(inter[k] for k in INTER_NAMES[op]))
        return (key_out, kval_out, tuple(inter_out),
                _meta_out(n_groups, win_ok, use_window))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW, ROW, ROW)))


@program_cache()
def _final_fn(mesh: Mesh, ops: tuple, seg_cap: int, ddof: int, narrow: tuple,
              use_window: int = 0, fold: bool = False):
    """Phase 2 per shard: reduce the shuffled intermediates under the new
    key grouping and finalize each op (the reference's
    ``ReduceShuffledResults``, mapreduce/mapreduce.hpp:56-76).  Rides THE
    SORT PATH (:func:`_sort_state`): the intermediates ride the one rank
    sort as u32 lanes (f64 sums by the index lane's side gather) and every
    sum-like one (sum/sumsq/count) comes out of the batched prefix-diff
    gather over ``seg_cap`` slots; only min/max extrema are segment
    scatters.  ``use_window`` and the last output: as :func:`_raw_fn`'s."""
    from ..ops import lanes

    def per_shard(vc, by_datas, by_valids, inter_by_op):
        flat_arrs, flat_kinds = [], []   # kind: 'sum' | 'min' | 'max'
        for i, op in enumerate(ops):
            for nm, arr in zip(INTER_NAMES[op], inter_by_op[i]):
                flat_arrs.append(arr)
                flat_kinds.append("sum" if nm in ("sum", "sumsq", "count")
                                  else nm)
        vspec = lanes.plan_lanes(
            tuple(str(a.dtype) for a in flat_arrs)
            + tuple(str(d.dtype) for d in by_datas),
            (False,) * len(flat_arrs)
            + tuple(v is not None for v in by_valids),
            (False,) * len(flat_arrs) + narrow)
        (gids, n_groups, mask, first, s_by, s_byv, s_arrs, _) = _sort_state(
            vc, by_datas, by_valids, tuple(flat_arrs),
            (None,) * len(flat_arrs), narrow, vspec, fold)
        my = jax.lax.axis_index(ROW_AXIS)
        n_live = vc[my].astype(jnp.int32)
        starts = gbk.grouped_starts(first, mask, n_live, seg_cap)
        sum_idx = [j for j, k in enumerate(flat_kinds) if k == "sum"]
        inters_b, key_out, kval_out, win_ok = gbk.grouped_reduce(
            ["sum"] * len(sum_idx), [s_arrs[j] for j in sum_idx],
            [mask] * len(sum_idx), starts, n_live, list(s_by), list(s_byv),
            seg_cap, key_narrow=narrow, use_window=use_window,
            blocked_scans=multi_shard())
        red_flat = [None] * len(flat_arrs)
        for j, d in zip(sum_idx, inters_b):
            red_flat[j] = d["sum"]
        for j, k in enumerate(flat_kinds):
            if k == "min":
                red_flat[j] = gbk.seg_min(s_arrs[j], gids, seg_cap, mask)
            elif k == "max":
                red_flat[j] = gbk.seg_max(s_arrs[j], gids, seg_cap, mask)
        res_d, res_v = [], []
        k = 0
        for i, op in enumerate(ops):
            inter = {}
            for nm in INTER_NAMES[op]:
                inter[nm] = red_flat[k]
                k += 1
            if "count" in inter:
                inter["count"] = inter["count"].astype(gbk._int_dtype())
            d, v = gbk.finalize(op, inter, ddof)
            res_d.append(d)
            res_v.append(v)
        return (key_out, kval_out, tuple(res_d), tuple(res_v),
                _meta_out(n_groups, win_ok, use_window))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW),
                             out_specs=(ROW, ROW, ROW, ROW, ROW)))


@program_cache()
def _raw_fn(mesh: Mesh, specs: tuple, seg_cap: int, ddof: int, grouped: bool,
            narrow: tuple, vnarrow: tuple = (), vspec=None,
            val_map: tuple = (), use_window: int = 0, fold: bool = False):
    """Single-phase per shard over raw (already co-located) rows — used for
    non-associative ops, the local path, and the grouped-input fast path
    (join/sort output: no shuffle, no rank sort).  ``vnarrow``: per spec,
    how an integer ``sum`` of that value column is scanned and how many
    lanes its prefix rides — :func:`sum_scan_form`'s descriptor, derived
    from the column's dtype and ``Column.bounds`` and reduced to one of
    three words and a block size so this cache keys on the decision, not
    on per-batch data bounds (``ops/groupby.SumScan``).

    ``vspec`` (non-grouped inputs only): a :class:`~.lanes.LaneSpec` over
    (value columns per spec ++ key columns) — the SORT PATH
    (:func:`_sort_state`).  Instead of dense-ranking keys (sort + gid
    scatter-back) and then scatter-reducing every aggregation in source
    order (~12 ns/row per op, measured), the value and key columns ride
    THE rank sort as u32 payload lanes (~1.7 ns/row/lane) and the input
    becomes grouped — every cumsum-able aggregation, the min/max counts
    and the representative keys then come from the run machinery's single
    prefix-diff gather (:func:`_runs_reduce`).  The reference's pipeline
    groupby (groupby/pipeline_groupby.cpp) is the moral analog: sort once,
    reduce runs.

    ``use_window`` (a window size, 0 = off; a dimension of the program
    cache, as :func:`~.fused._fused_fn`'s last static): that gather
    through the windowed Pallas kernel.  ``fold``: :func:`_sort_state`'s.
    The last output is :func:`_meta_out`."""

    def per_shard(vc, by_datas, by_valids, uval_datas, uval_valids):
        # uval_*: one array per DISTINCT value column; val_map expands to
        # per-spec lists (repeated aggs over one column share lanes/sorts)
        if vspec is not None and not grouped:
            (gids, n_groups, mask, first, by_datas, by_valids, uval_datas,
             uval_valids) = _sort_state(vc, by_datas, by_valids, uval_datas,
                                        uval_valids, narrow, vspec, fold)
        else:
            gids, n_groups, mask, first = _group_keys(
                by_datas, by_valids, vc, grouped, narrow, fold)
        val_datas = tuple(uval_datas[j] for j in val_map)
        val_valids = tuple(uval_valids[j] for j in val_map)
        vmasks = [_value_mask(mask, val_datas[i], val_valids[i])
                  for i in range(len(specs))]
        # grouped/sorted fast path: ONE batched prefix-diff pass computes
        # every cumsum-able aggregation, min/max counts AND the
        # representative keys
        batched: dict[int, dict] = {}
        win_ok = None
        if first is not None:
            batched, key_out, kval_out, win_ok = _runs_reduce(
                tuple(op for op, _ in specs), val_datas, vmasks, gids,
                first, mask, vc, seg_cap, by_datas, by_valids, narrow,
                vnarrow, use_window)
        else:
            key_out, kval_out = _rep_keys(by_datas, by_valids, gids, seg_cap)
        res_d, res_v = [], []
        for i, (op, q) in enumerate(specs):
            vmask = vmasks[i]
            if op in gbk.ASSOCIATIVE:
                if i in batched:
                    inter = batched[i]
                else:
                    inter = gbk.combine_locally(op, val_datas[i], gids,
                                                seg_cap, vmask)
                d, v = gbk.finalize(op, inter, ddof)
            elif op == "nunique":
                ko = pack.key_operands([val_datas[i]], [val_valids[i]])
                d = gbk.nunique(ko, gids, seg_cap, vmask)
                v = None
            else:  # quantile
                d, v = gbk.quantile(val_datas[i], gids, seg_cap, q, vmask)
            res_d.append(d)
            res_v.append(v)
        return (key_out, kval_out, tuple(res_d), tuple(res_v),
                _meta_out(n_groups, win_ok, use_window))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW, ROW, ROW, ROW)))


@program_cache()
def _shrink_fn(mesh: Mesh, new_cap: int):
    @staged("compact")
    def per_shard(d):
        return d[:new_cap]

    return jit(shard_map(per_shard, mesh=mesh, in_specs=ROW,
                             out_specs=ROW))


def _shrink(table: Table, n_rows: np.ndarray) -> Table:
    """Slice each shard's dense row prefix down to a pow2 cap (cuts the cost
    of downstream shuffles/sorts on oversized intermediate tables)."""
    cap = table.capacity
    new_cap = config.pow2ceil(int(n_rows.max()) if n_rows.size else 1)
    if new_cap >= cap:
        return table
    fn = _shrink_fn(table.env.mesh, new_cap)
    cols = {}
    for n, c in table.columns.items():
        d = fn(c.data)
        v = fn(c.validity) if c.validity is not None else None
        cols[n] = Column(d, c.type, v, c.dictionary)
    return Table(cols, table.env, n_rows)


def _result_types(specs, val_cols):
    """Logical type + dictionary of each aggregation result column."""
    types, dicts = [], []
    for (c, op, _, _), col in zip(specs, val_cols):
        if op in ("count", "nunique"):
            types.append(LogicalType.INT64)
            dicts.append(None)
        elif col.type in (LogicalType.STRING, LogicalType.DECIMAL):
            types.append(col.type)  # a string's min/max: its code; a decimal
            dicts.append(_kept_dictionary(c, op, col))   # keeps its scale
        else:
            src = physical_np_dtype(col.type)
            types.append(from_numpy_dtype(gbk.np_result_dtype(op, src)))
            dicts.append(None)
    return types, dicts


def _result_table(env, by_names, by_cols, key_out, kval_out, res_names,
                  res_d, res_v, res_types, res_dicts, n_groups) -> Table:
    cols = {}
    for n, c, d, v in zip(by_names, by_cols, key_out, kval_out):
        cols[n] = Column(d, c.type, v, c.dictionary)
    for n, d, v, t, dc in zip(res_names, res_d, res_v, res_types, res_dicts):
        phys = physical_np_dtype(t)
        if d.dtype != phys:  # f64 accumulators -> declared result dtype
            d = d.astype(phys)
        # armed-audit overflow guard (result names are `{col}_{op}` — a
        # public contract, so the op suffix is derivable here at the one
        # host assembly point every groupby route funnels through)
        gbk.guard_saturation(n.rsplit("_", 1)[-1], d, column=n)
        cols[n] = Column(d, t, v, dc)
    return Table(cols, env, np.asarray(n_groups, np.int64))


@program_cache()
def _sink_finalize_fn(mesh: Mesh, ops: tuple, ddof: int):
    """Per-shard finalize of a sink combine's DERIVED ops (mean/var/std)
    over the summed (count, sum[, sumsq]) intermediate columns — the
    IDENTICAL :func:`cylon_tpu.ops.groupby.finalize` expressions,
    compiled by the same backend in one program, so FMA-contraction
    decisions match the batch groupby's in-jit finalize and the
    streaming bit-equality contract extends to var/std (an eager
    host-side ``sumsq/c - mean·mean`` computes the multiply and
    subtract as separate dispatches, which XLA would have contracted —
    a 1-ulp fork measured on the CPU rig)."""

    def per_shard(*arrs):
        outs = []
        i = 0
        for op in ops:
            inter = {"count": arrs[i], "sum": arrs[i + 1]}
            i += 2
            if op != "mean":
                inter["sumsq"] = arrs[i]
                i += 1
            d, v = gbk.finalize(op, inter, ddof)
            outs.append(d)
            outs.append(v)
        return tuple(outs)

    n_in = sum(2 if op == "mean" else 3 for op in ops)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=(ROW,) * n_in,
                             out_specs=(ROW,) * (2 * len(ops))))


def combine_sink_partials(partial: Table, by, aggs, chunk_aggs,
                          combine_ops, ddof: int = 1,
                          disjoint: bool = False) -> Table:
    """The sink snapshot/absorb API's COMBINE step: fold a table of
    per-chunk partial aggregates (one row per (chunk, group) — the
    concatenation of a :class:`~cylon_tpu.exec.pipeline.GroupBySink`'s
    adopted partials) into the final public aggregate table, without
    touching the partials themselves — so a streaming view's
    ``read()`` can snapshot a LIVE sink repeatedly while ingestion
    continues (:mod:`cylon_tpu.stream.view`).

    ``chunk_aggs``: the sorted distinct (col, intermediate-op) pairs the
    sink maintains; ``combine_ops``: intermediate-op → combining op
    (sum/min/max); ``disjoint``: the partials' key sets are pairwise
    disjoint (range-partitioned pipelines), so the cross-chunk combine
    groupby is skipped and the partials ARE the final groups.

    Derived ops (mean/var/std) finalize ON DEVICE through the very
    :func:`cylon_tpu.ops.groupby.finalize` the monolithic groupby jits
    (:func:`_sink_finalize_fn`), so whenever the partial sums are EXACT
    (integer payloads, or integer-valued f64 below 2^53 — the
    fixed-point money representation) the combined result is bit-equal
    to a from-scratch batch groupby over all rows, var/std included
    (docs/streaming.md "exactness contract")."""
    env = partial.env
    if disjoint:
        comb = partial

        def part_name(col, i):
            return f"{col}_{i}"
    else:
        combine = [(f"{c}_{i}", combine_ops[i]) for c, i in chunk_aggs]
        comb = groupby_aggregate(partial, by, combine)

        def part_name(col, i):
            return f"{col}_{i}_{combine_ops[i]}"
    # derived ops: one shared device finalize over the summed
    # intermediates (count/sum[/sumsq] per derived column)
    derived = [(col, op) for col, op, *_ in aggs
               if op in ("mean", "var", "std")]
    dev_out: dict[tuple, tuple] = {}
    if derived:
        arrs = []
        for col, op in derived:
            arrs.append(comb.column(part_name(col, "count")).data)
            arrs.append(comb.column(part_name(col, "sum")).data)
            if op != "mean":
                arrs.append(comb.column(part_name(col, "sumsq")).data)
        outs = _sink_finalize_fn(env.mesh, tuple(op for _, op in derived),
                                 int(ddof))(*arrs)
        for j, key in enumerate(derived):
            dev_out[key] = (outs[2 * j], outs[2 * j + 1])
    cols = {}
    for n in by:
        cols[n] = comb.column(n)
    for col, op, *_ in aggs:
        name = f"{col}_{op}"
        if (col, op) in dev_out:
            d, v = dev_out[(col, op)]
            cols[name] = Column(d, from_numpy_dtype(np.dtype(d.dtype)), v)
        else:
            # non-derived ops (sum/count/min/max) ARE their own single
            # intermediate — the combined column passes through renamed
            c = comb.column(part_name(col, op))
            # armed-audit overflow guard at the COMBINE boundary: two
            # partials each below the rail can wrap when folded, and the
            # disjoint pass-through never reaches _result_table's guard
            gbk.guard_saturation(op, c.data, column=name,
                                 site="groupby.combine")
            cols[name] = c
    out = Table(cols, env, np.asarray(comb.valid_counts, np.int64))
    out.grouped_by = None  # combine order is chunk-partial order
    return out


def groupby_aggregate(table: Table, by, aggs, ddof: int = 1) -> Table:
    """Group ``table`` by key columns ``by`` and aggregate.

    aggs: list of (value_col, op[, q]) with op in sum/count/min/max/mean/var/
    std/nunique/quantile/median.  Returns key columns + one column per agg
    named ``{col}_{op}``.  Null keys form their own group (reference
    semantics: comparators treat nulls as equal).

    Device OOM falls back to chunked streaming aggregation
    (exec/pipeline.GroupBySink) when every op decomposes through public
    partial aggregations (sum/count/min/max/mean/var/std)."""
    from ..exec.pipeline import GroupBySink, chunk_table
    from ..obs import plan as _plan
    from .common import run_with_oom_fallback

    def fallback(nc):
        _plan.annotate(route="chunked_sink", n_chunks=nc)
        sink = GroupBySink(by, aggs, ddof=ddof)
        for ch in chunk_table(table, nc):
            sink(ch)
        return sink.finalize()

    by_l = [by] if isinstance(by, str) else list(by)
    with _plan.node("groupby", by=tuple(by_l),
                    aggs=tuple((a[0], a[1]) for a in aggs
                               if isinstance(a, (list, tuple))
                               and len(a) >= 2)) as pn:
        # a stitch-deferred skew join feeds its PRE-stitch table here:
        # aggregation cannot observe row order/placement, so the skew
        # route's merge exchange is elided for join→groupby pipelines
        # (relational/skew.consume_unstitched, docs/skew.md)
        from .skew import consume_unstitched
        table = consume_unstitched(table)
        if pn:
            from ..core.table import DeferredTable
            # a DeferredTable input (fused join→groupby pushdown) stays
            # untouched: reading its counts or sampling its keys would
            # force the materialization the pushdown exists to avoid
            if not isinstance(table, DeferredTable):
                pn.set(rows_in=table.row_count)
                _plan.profile_keys(pn, table, by_l)
            else:
                pn.annotate(deferred_input=True)
        res = run_with_oom_fallback(
            lambda: _groupby_aggregate_impl(table, by, aggs, ddof),
            can_fallback=all(a[1] in GroupBySink._DECOMP for a in aggs),
            fallback=fallback, label="groupby", env=table.env)
        if pn and type(res) is Table:
            pn.set(rows_out=res.row_count)
        return res


def _groupby_aggregate_impl(table: Table, by, aggs, ddof: int = 1) -> Table:
    from ..exec.recovery import maybe_inject
    maybe_inject("groupby.device_oom")  # device-OOM ladder test point
    env = table.env
    by = [by] if isinstance(by, str) else list(by)
    specs = _normalize_aggs(aggs)
    # fused path: an unmaterialized inner-join result grouped by the join
    # keys aggregates straight off the pre-expansion sorted state
    # (relational/fused.py) — must run before any column access below,
    # which would materialize the join
    from ..obs import plan as _plan
    from .fused import try_join_groupby_pushdown
    pushed = try_join_groupby_pushdown(table, by, specs, ddof)
    if pushed is not None:
        _plan.annotate(route="fused_pushdown")
        return pushed
    # a skew-deferred join the pushdown could not serve still feeds its
    # PRE-stitch (split-layout) table here — aggregation cannot observe
    # row order/placement, so the stitch's merge exchange is skipped
    # (relational/skew.consume_unstitched, docs/skew.md)
    from .skew import consume_unstitched
    table = consume_unstitched(table, include_deferred=True)
    by_cols = [table.column(n) for n in by]
    val_cols = [table.column(c) for c, _, _, _ in specs]
    from ..core.column import HashedStrings
    for n, col in zip(by, by_cols):
        if col.type == LogicalType.LIST:
            raise InvalidError(
                f"groupby on list passthrough column {n!r} is not "
                "supported (codes are row ids, not value-equal)")
    for (c, op, _, _), col in zip(specs, val_cols):
        if col.type == LogicalType.LIST and op != "count":
            raise InvalidError(
                f"agg {op!r} not valid for list passthrough column {c!r}")
        if col.type == LogicalType.STRING and op not in ("count", "nunique",
                                                         "min", "max"):
            raise InvalidError(f"agg {op!r} not valid for string column {c!r}")
        if (col.type == LogicalType.STRING and op in ("min", "max")
                and isinstance(col.dictionary, HashedStrings)):
            raise InvalidError(
                f"agg {op!r} on high-cardinality hashed string column "
                f"{c!r}: hashed codes carry no lexical order")
    res_types, res_dicts = _result_types(specs, val_cols)
    res_names = [n for _, _, _, n in specs]
    all_assoc = all(op in gbk.ASSOCIATIVE for _, op, _, _ in specs)
    distributed = env.world_size > 1
    # grouped fast path: equal keys already contiguous per shard AND
    # co-located across shards (join/sort/groupby output) — one single-phase
    # pass, no shuffle, no rank sort
    grouped = (table.grouped_by is not None
               and tuple(by) == tuple(table.grouped_by))
    narrow = narrow32_flags(by_cols)
    fold = not grouped and note_liveness("groupby", fold_liveness(by_cols))
    if distributed and all_assoc and not grouped:
        # phase 1: local pre-combine (reference groupby.cpp:76-81), riding
        # the sort path when the columns lane-pack (see _raw_fn/vspec)
        _plan.annotate(route="combine_shuffle")
        by_datas, by_valids = col_arrays(by_cols)
        uniq_names = list(dict.fromkeys(c for c, _, _, _ in specs))
        val_map = tuple(uniq_names.index(c) for c, _, _, _ in specs)
        uval_cols = [table.column(c) for c in uniq_names]
        uval_datas = tuple(c.data for c in uval_cols)
        uval_valids = tuple(c.validity for c in uval_cols)
        vc = np.asarray(table.valid_counts, np.int32)
        ops_t = tuple(op for _, op, _, _ in specs)
        cap_full = max(table.capacity, 1)
        cspec = _plan_vspec(uval_cols, by_cols, narrow,
                            sum(len(INTER_NAMES[op]) for op in ops_t))
        cargs = (vc, by_datas, by_valids, uval_datas, uval_valids)
        # the partial sums are summed again across shards: two lanes
        cforms = _sum_forms(specs, val_cols, cap_full, two_lanes=True)
        with timing.region("groupby.combine"):
            (key_out, kval_out, inter_out, _), n_groups = dispatch_at_bucket(
                _SEG_CACHE,
                ("combine-seg", env.serial, ops_t, tuple(by), narrow,
                 cap_full, int(table.valid_counts.sum())), cap_full,
                lambda sc, win: _combine_fn(env.mesh, ops_t, sc, False,
                                            narrow, cforms, cspec, val_map,
                                            win, fold)(*cargs),
                partial(_read_meta, env.world_size),
                # the gather the window serves exists on the sort path alone
                _density_window(env.mesh, table.valid_counts)
                if cspec is not None else None).resolve()
        # intermediate table: keys + flat intermediate columns
        cols = {}
        for n, c, d, v in zip(by, by_cols, key_out, kval_out):
            cols[n] = Column(d, c.type, v, c.dictionary)
        inames_by_op = []
        for i, (_, op, _, _) in enumerate(specs):
            inames = []
            for iname, arr in zip(INTER_NAMES[op], inter_out[i]):
                cn = f"__i{i}_{iname}"
                cols[cn] = Column(arr, from_numpy_dtype(np.dtype(arr.dtype)),
                                  None, None)
                inames.append(cn)
            inames_by_op.append(inames)
        # phase 2: shuffle intermediates by key hash, final combine
        with timing.region("groupby.shuffle"):
            inter_table = _shrink(Table(cols, env, n_groups), n_groups)
            shuffled = shuffle_table(inter_table, by, owner="groupby.recv")
        s_by_datas, s_by_valids = col_arrays([shuffled.column(n) for n in by])
        # phase 2 ranks phase 1's keys: the table's values, so its bounds,
        # under the received column's null flag
        k0 = by_cols[0]
        ffold = note_liveness("groupby", fold_liveness([Column(
            s_by_datas[0], k0.type, s_by_valids[0], k0.dictionary,
            bounds=k0.bounds)]))
        inter_by_op = tuple(
            tuple(shuffled.column(cn).data for cn in inames)
            for inames in inames_by_op)
        vc2 = np.asarray(shuffled.valid_counts, np.int32)
        fin_cap = max(shuffled.capacity, 1)
        fargs = (vc2, s_by_datas, s_by_valids, inter_by_op)
        with timing.region("groupby.final"):
            (key2, kval2, res_d, res_v, _), ng2 = dispatch_at_bucket(
                _SEG_CACHE,
                ("final-seg", env.serial, ops_t, tuple(by), narrow, ddof,
                 fin_cap, int(shuffled.valid_counts.sum())), fin_cap,
                lambda sc, win: _final_fn(env.mesh, ops_t, sc, ddof, narrow,
                                          win, ffold)(*fargs),
                partial(_read_meta, env.world_size),
                _density_window(env.mesh, shuffled.valid_counts),
                phase="final_").resolve()
            # phase 2 sums partial sums, whose bounds nobody knows
            _SUM_SCANS["pair64"].inc(sum(
                nm not in ("min", "max") and np.dtype(a.dtype).kind in "iu"
                for op, arrs in zip(ops_t, inter_by_op)
                for nm, a in zip(INTER_NAMES[op], arrs)))
        out = _result_table(env, by, by_cols, key2, kval2, res_names, res_d,
                            res_v, res_types, res_dicts, ng2)
        out = _shrink(out, ng2)
        out.grouped_by = tuple(by)
        return out

    # non-associative ops (or local, or grouped input): co-locate raw rows
    _plan.annotate(route="grouped_fastpath" if grouped else "raw")
    work = table.project(list(dict.fromkeys(by + [c for c, _, _, _ in specs])))
    if distributed and not grouped:
        # the raw-row co-location shuffle is the one groupby route a heavy
        # key CAN concentrate on a single rank: non-decomposable aggs
        # (quantile/median/nunique) need every row of a group together, so
        # the join tier's split/duplicate-broadcast remedy does not apply
        # (associative aggs are skew-immune — per-group intermediates
        # collapse a heavy key to one row per shard before their shuffle).
        # Surface the hazard on the plan node so an EXPLAIN diff against
        # key_profile's est_rows_per_rank names WHY this plan is exposed
        # (docs/skew.md).
        _plan.annotate(skew_vulnerable=True)
        work = shuffle_table(work, by)
    by_datas, by_valids = col_arrays([work.column(n) for n in by])
    uniq_names = list(dict.fromkeys(c for c, _, _, _ in specs))
    val_map = tuple(uniq_names.index(c) for c, _, _, _ in specs)
    uval_cols = [work.column(c) for c in uniq_names]
    uval_datas = tuple(c.data for c in uval_cols)
    uval_valids = tuple(c.validity for c in uval_cols)
    vc = np.asarray(work.valid_counts, np.int32)
    spec_t = tuple((op, q) for _, op, q, _ in specs)
    cap_full = max(work.capacity, 1)

    vnarrow = _sum_forms(specs, [work.column(c) for c, _, _, _ in specs],
                         cap_full)

    # sort-path lane spec (non-grouped inputs): value + key columns ride the
    # rank sort as u32 lanes when all are laneable and the lane count is
    # modest (payload ~1.7 ns/row/lane vs ~12 ns/row per scatter-reduce)
    vspec = None
    if not grouped:
        n_inters = sum(len(INTER_NAMES[op]) for _, op, _, _ in specs
                       if op in gbk.ASSOCIATIVE)
        vspec = _plan_vspec(uval_cols, [work.column(n) for n in by], narrow,
                            max(n_inters, 1))
    args = (vc, by_datas, by_valids, uval_datas, uval_valids)
    with timing.region("groupby.raw"):
        (key_out, kval_out, res_d, res_v, _), n_groups = dispatch_at_bucket(
            _SEG_CACHE,
            (env.serial, spec_t, tuple(by), grouped, narrow, ddof, cap_full,
             int(work.valid_counts.sum())), cap_full,
            lambda sc, win: _raw_fn(env.mesh, spec_t, sc, ddof, grouped,
                                    narrow, vnarrow, vspec, val_map,
                                    win, fold)(*args),
            partial(_read_meta, env.world_size),
            # the gather the window serves exists on run-contiguous input
            # alone (grouped, or the sort path)
            _density_window(env.mesh, work.valid_counts)
            if grouped or vspec is not None else None).resolve()
    out = _result_table(env, by, by_cols, key_out, kval_out, res_names, res_d,
                        res_v, res_types, res_dicts, n_groups)
    out = _shrink(out, n_groups)
    out.grouped_by = tuple(by)
    return out


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry): groupby's two
# phases are pure-local shard programs separated by the hash shuffle — the
# jaxpr pass asserts no hidden collective, no row-scale i32→i64 widening,
# zero host callbacks.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _decl_args(mesh, cap=1024):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32)
    keys = (S((w * cap,), np.int64),)
    valids = (S((w * cap,), np.bool_),)
    vals = (S((w * cap,), np.float64),)
    return w, S, vc, keys, valids, vals


def _trace_combine(mesh):
    _w, _S, vc, keys, valids, vals = _decl_args(mesh)
    fn = _unwrap(_combine_fn(mesh, ("sum",), 256, False, (False,), (),
                             None, (0,)))
    return jax.make_jaxpr(fn)(vc, keys, valids, vals, valids)


def _trace_shrink(mesh):
    w, S, _vc, _k, _v, _vals = _decl_args(mesh)
    fn = _unwrap(_shrink_fn(mesh, 512))
    return jax.make_jaxpr(fn)(S((w * 1024,), np.float64))


def _trace_sink_finalize(mesh):
    w, S, _vc, _k, _v, _vals = _decl_args(mesh)
    fn = _unwrap(_sink_finalize_fn(mesh, ("mean", "var"), 1))
    cnt = S((w * 1024,), np.int64)
    f = S((w * 1024,), np.float64)
    return jax.make_jaxpr(fn)(cnt, f, cnt, f, f)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._combine_fn", _trace_combine,
                tags=("groupby",))
declare_builder(f"{__name__}._shrink_fn", _trace_shrink, tags=("groupby",))
declare_builder(f"{__name__}._sink_finalize_fn", _trace_sink_finalize,
                tags=("groupby", "stream"))


# ---------------------------------------------------------------------------
# what a settled dispatch is counted and shown as (host side).  Below
# everything a program is traced through, and the code above keeps its
# line numbers: Mosaic's serialized kernel body embeds the lines of the
# traced frames, so a shifted ``per_shard`` is a new cache key and a cold
# compile of every windowed program (ROADMAP S1(b)).
# ---------------------------------------------------------------------------

#: why a settled dispatch ran XLA's gather, one registry counter each,
#: registered at import so that a snapshot shows the whole family: the
#: site hands the dispatcher no rule (its program holds no run gather);
#: a span overflow forbade the window for good; ``fused.window_rule`` said
#: no (its three words); or the rule would say yes on these counts but the
#: site remembers window 0 from other data of the same signature
_PLAIN = {why: _metrics.counter("grouped_reduce_plain_dispatches",
                                reason=why)
          for why in ("no_window_rule", "span_overflow", "not_tpu",
                      "density_below_floor", "segment_space_small",
                      "remembered_plain")}


def _note_settled(bucket, seg_cap, allowed, win, window, n_groups,
                  phase: str = ""):
    """One settled dispatch of :func:`dispatch_at_bucket`: a plain one is
    counted under the reason it is plain - asked of the site's own
    ``window`` rule at the segment space the program ran at, no second
    copy of its thresholds - and the groupby plan node (where a profile
    is on) gets the segment bucket the site remembers, the window, and
    the group density the rule was given, as ``<phase>segment_space`` /
    ``<phase>window`` / ``<phase>density``: a call with two sites (route
    ``combine_shuffle``: phase 1 under the bare names, phase 2 under
    ``final_``) says both."""
    from ..obs import plan as _plan
    why, dens = "no_window_rule", None
    if window is not None:
        _w, why, dens = window(seg_cap, n_groups)
        if not allowed:
            why = "span_overflow"
    if not win:
        _PLAIN[why or "remembered_plain"].inc()
    node = _plan.current()          # None with no profile on
    if node is not None and node.op == "groupby":
        attrs = {"segment_space": int(bucket), "window": int(win)}
        if dens is not None:
            attrs["density"] = round(dens, 6)
        node.annotate(**{phase + k: v for k, v in attrs.items()})


# ---------------------------------------------------------------------------
# how an integer ``sum`` is scanned (host side; ``ops/groupby.SUM_FORMS``)
# ---------------------------------------------------------------------------

#: one count an integer ``sum`` a dispatched groupby, by the form its
#: prefix scan took; registered at import so that a snapshot shows the
#: whole family
_SUM_SCANS = {form: _metrics.counter("grouped_sum_scans", form=form)
              for form in gbk.SUM_FORMS}


def sum_scan_form(dtype, bounds, rows: int,
                  two_lanes: bool = False) -> gbk.SumScan:
    """THE rule of how a grouped integer ``sum`` over a value column of
    physical ``dtype`` with host-known ``bounds`` (``Column.bounds``: (lo,
    hi) or None) is scanned over ``rows`` rows a shard, read from what
    the column says of itself and nothing else: ``sum32`` where rows *
    max|v| fits int32 (so every prefix does; not with ``two_lanes``: the
    sums are summed again, and one shard's proof does not cover the
    lane); ``val32`` where each value does, in the largest blocks of
    ``ops/groupby.VAL32_BLOCKS`` whose sums fit int32 too (flat where none
    does: a column that uses int32's width); ``pair64`` where nothing is
    proven - no bounds (a derived column), bounds past int32 (a uint64 or
    int64 column that uses its width, a decimal whose scaled bounds pass)
    or not an integer at all (whose prefixes never read it)."""
    if bounds is None or np.dtype(dtype).kind not in ("i", "u"):
        return gbk.SumScan("pair64")
    lo, hi = int(bounds[0]), int(bounds[1])
    top = max(abs(lo), abs(hi))
    if top * rows < (1 << 31) and not two_lanes:
        return gbk.SumScan("sum32")
    if lo >= -(1 << 31) and hi < (1 << 31):
        return gbk.SumScan("val32", next(
            (b for b in gbk.VAL32_BLOCKS if b * top < (1 << 31)), 1))
    return gbk.SumScan("pair64")


def _sum_forms(specs, val_cols, rows: int, two_lanes: bool = False) -> tuple:
    """:func:`sum_scan_form` of each spec's value column (anything with
    ``.type`` and ``.bounds``), and the one place the scans are counted
    and shown: a count a spec whose op is an integer ``sum``, by form, and
    ``sum_scan=`` those descriptors on the groupby plan node."""
    from ..obs import plan as _plan
    forms, summed = [], []
    for (_c, op, _q, _n), col in zip(specs, val_cols):
        dt = physical_np_dtype(col.type)
        form = sum_scan_form(dt, col.bounds, rows, two_lanes)
        forms.append(form)
        if op == "sum" and dt.kind in ("i", "u", "b"):
            _SUM_SCANS[form.form].inc()
            summed.append(str(form))
    node = _plan.current()          # None with no profile on
    if summed and node is not None and node.op == "groupby":
        node.annotate(sum_scan=tuple(summed))
    return tuple(forms)


def _kept_dictionary(name: str, op: str, col: Column):
    """The ``Column.dictionary`` an aggregate of a STRING or DECIMAL value
    column keeps (:func:`_result_types`; down here so that no line above
    moves).  A decimal's scaled integers sum, and order, as the decimals do
    at the same scale: ``sum`` / ``min`` / ``max`` stay DECIMAL - a sum with
    all of int64's 18 digits - and every other op raises (``mean`` / ``var``
    / ``std`` / quantiles of the scaled integers would be off by the scale
    and are not exact)."""
    if col.type == LogicalType.DECIMAL:
        if op not in ("sum", "min", "max"):
            raise InvalidError(
                f"agg {op!r} of decimal column {name!r} is not scale-exact "
                "(sum / min / max are); cast the column to float64 first")
        if op == "sum":
            from ..core.column import DecimalScale
            return DecimalScale(18, col.dictionary.scale)
    return col.dictionary


@staged("gather_rows")
def _lanes_at(vmat, perm):
    """A lane matrix at a sort's permutation (``perm``: the uint32 row index
    that rode it) - :func:`_sort_state`'s one gather where the lanes would
    take the sort past ``pack.SORT_OPERAND_BUDGET``.  (At the file's end:
    Mosaic embeds the line numbers of the traced frames above.)"""
    return vmat[perm.astype(jnp.int32)]
