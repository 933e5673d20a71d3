"""Table-level set ops (union/intersect/subtract), unique, equals.

TPU-native equivalents of the reference's row-set operators — ``Union``
(table.cpp:925), ``Subtract`` (:997), ``Intersect`` (:1051) and their
distributed wrappers (:1152-1166, shuffle both then local), ``Unique``
(:1306) / ``DistributedUnique`` (:1376), and ``Equals``/``DistributedEquals``
(:1389/:1440 — repartition-to-match then compare).

The reference builds ska::bytell hash sets over row comparators; here rows of
both tables are rank-sorted together per shard (ops/pack.py's key operands,
then the row index: stable) and an operator is the filter's pair of programs
(relational/repart) behind that sort: the count program reads the row flags
off the sorted order (ops/setops.py), sorts the kept rows' SOURCE positions
and returns ``[kept rows, widest tile span]``; the materialize program moves
the rows at those positions by the filter's body - the windowed Pallas take
where ``repart.filter_window`` says it serves.  Nothing is scattered back.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..utils.cache import jit, program_cache
from ..core.dtypes import LogicalType
from ..core.table import Table
from ..obs import metrics as _metrics
from ..ops import lanes
from ..ops import pack
from ..ops import setops as setk
from ..parallel import shuffle
from ..status import InvalidError
from ..utils.host import host_array
from ..utils.stages import stage
from . import repart
from .common import (PAD_L, REP, ROW, check_same_env, col_arrays, live_count,
                     fold_liveness, live_mask, narrow32_flags, note_liveness,
                     promote_key_pair, rebuild_like, table_lane_spec)
from .repart import repartition, shuffle_table

shard_map = jax.shard_map

#: one count an operator call that ran the normal path to its end, and the
#: rows it returned (``op``: ``unique``, ``union``, ``intersect``,
#: ``subtract``); the plan node's ``route`` says the same call's path
_DISPATCHES = {op: _metrics.counter("setop_dispatches", op=op)
               for op in ("unique", "union", "intersect", "subtract")}
_ROWS_OUT = {op: _metrics.counter("setop_rows_out", op=op)
             for op in _DISPATCHES}
#: one count a materialize dispatch, by the path its rows took
#: (``repart.path_counters``: ``windowed``, or ``plain`` with the reason)
_MAT_PATHS = repart.path_counters("setop_mat_dispatches")


def plan_route(env, assume_colocated: bool = False) -> str:
    """The ``route`` of a ``unique`` / ``set_op`` plan node: ``colocated``
    where the caller vouches that equal rows share a shard, ``hash`` where
    ``shuffle_table`` put them there, ``local`` on one device."""
    if assume_colocated:
        return "colocated"
    return "hash" if env.world_size > 1 else "local"


def _said(ctx, pn, op: str, res: Table, route: str, **args) -> None:
    """What a finished call says of itself: the counters, the arguments of
    its ``cylon.op.*`` span, the plan node's route and rows."""
    _DISPATCHES[op].inc()
    _ROWS_OUT[op].inc(res.row_count)
    ctx.span_args(rows_out=res.row_count, out_cap=res.capacity, **args)
    if pn:
        pn.set(rows_out=res.row_count, route=route)


def _rank_sorted(keyops: pack.KeyOps, n_live) -> tuple:
    """``(first, live, sidx)`` in the rank sort's order (keys, then the row
    index, stable: a group of equal rows is one run in source order): the
    sorted row starts its run; is no padding row (padding sorted last, so
    the position compare ``p < n_live``); its index in the ranked rows."""
    idx = jnp.arange(keyops.n, dtype=jnp.int32)
    with stage("sort_keys"):
        srt = jax.lax.sort(keyops.ops + (idx,), num_keys=len(keyops.ops),
                           is_stable=True)
    with stage("setop_flags"):
        first = (pack.neighbor_flags(srt[:-1], keyops.kinds) != 0) | (idx == 0)
        live = idx < n_live
    return first, live, srt[-1]


def _taken(like: Table, meta, srt, cap: int, live, spec, program,
           *source) -> Table:
    """``repart.materialize_kept`` (first occurrences thin out) and its
    result, in ``like``'s schema; the operator's plan node says the
    dispatch as the ``filter`` node does."""
    env = like.env
    (out_d, out_v), counts, said = repart.materialize_kept(
        env.mesh, meta, srt, cap, live, spec.n_lanes, _MAT_PATHS, program,
        *source, thinning=True)
    from ..obs import plan as _plan
    pn = _plan.current()
    if pn is not None and pn.op in ("unique", "set_op"):    # not a chunk's
        pn.set(**said)
    return rebuild_like(list(like.columns.items()), out_d, out_v, counts, env)


# ---------------------------------------------------------------------------
# unique (drop_duplicates)
# ---------------------------------------------------------------------------

@program_cache()
def _unique_count_fn(mesh: Mesh, keep: str, narrow: tuple, fold: bool = False):
    """``repart._filter_count_fn``'s ``(meta, srt)`` for the kept occurrence
    of each distinct key.  ``narrow``: static per-key flags
    (common.narrow32_flags) - a 64-bit integer key whose host-known bounds
    fit int32 sorts as ONE operand, not a (hi, lo) pair; ``fold``
    (common.fold_liveness): padding sorts last INSIDE the leading operand.
    XLA:TPU compiles, and runs, a sort by its operands (ops/pack)."""
    def per_shard(vc, key_datas, key_valids):
        cap, my = key_datas[0].shape[0], jax.lax.axis_index(shuffle.ROW_AXIS)
        first, live, sidx = _rank_sorted(pack.key_operands(
            list(key_datas), list(key_valids), row_mask=live_mask(vc, cap),
            pad_key=PAD_L, narrow32=narrow, fold=fold), vc[my])
        return repart.kept_positions(setk.unique_flags(first, live, keep),
                                     sidx, cap)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW, ROW),
                             out_specs=(ROW, ROW)))


@program_cache()
def _unique_mat_fn(mesh: Mesh, spec, out_cap: int, window: int):
    """``repart._filter_mat_fn``'s program under this family's name: the
    table's rows at the sorted kept positions."""
    return jit(shard_map(repart.take_kept(out_cap, spec, window), mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


def unique_table(table: Table, subset=None, keep: str = "first") -> Table:
    """Drop duplicate rows (by ``subset`` columns, default all).  Distributed:
    shuffle by subset hash so equal rows co-locate; within a shard the
    (source rank, source position) receive order makes keep=first/last pick
    the *globally* first/last occurrence.  The kept rows keep their order."""
    env = table.env
    subset = list(subset) if subset is not None else table.column_names
    if keep not in ("first", "last"):
        raise InvalidError("keep must be 'first' or 'last'")
    for n in subset:
        if table.column(n).type == LogicalType.LIST:
            raise InvalidError(
                f"unique on list passthrough column {n!r} is not supported "
                "(codes are row ids, not value-equal)")
    from ..obs import plan as _plan
    ctx = _plan.node("unique", subset=tuple(subset), keep=keep)
    with ctx as pn:
        rows_in = table.row_count
        if pn:
            pn.set(rows_in=rows_in)
        if env.world_size > 1:
            table = shuffle_table(table, subset)
        res = table
        if table.capacity:      # else: no row to rank or to take
            key_cols = [table.column(n) for n in subset]
            cols = list(table.columns.values())
            vc = np.asarray(table.valid_counts, np.int32)
            fold = note_liveness("setops", fold_liveness(key_cols))
            meta, srt = _unique_count_fn(env.mesh, keep, narrow32_flags(
                key_cols), fold)(vc, *col_arrays(key_cols))
            spec = table_lane_spec(cols)
            res = _taken(table, meta, srt, table.capacity, vc, spec,
                         partial(_unique_mat_fn, env.mesh, spec),
                         *col_arrays(cols))
        _said(ctx, pn, "unique", res, plan_route(env), keep=keep,
              rows_in=rows_in)
        return res


# ---------------------------------------------------------------------------
# union / intersect / subtract (distinct semantics, like the reference)
# ---------------------------------------------------------------------------

def _align_schemas(a: Table, b: Table):
    if a.column_names != b.column_names:
        raise InvalidError(
            f"set op schema mismatch: {a.column_names} vs {b.column_names}")
    cols_a, cols_b = {}, {}
    for n in a.column_names:
        ca, cb = promote_key_pair(a.column(n), b.column(n))
        cols_a[n] = ca
        cols_b[n] = cb
    return (Table(cols_a, a.env, a.valid_counts),
            Table(cols_b, b.env, b.valid_counts))


@program_cache()
def _setop_count_fn(mesh: Mesh, op: str, narrow: tuple, fold: bool = False):
    """``repart._filter_count_fn``'s ``(meta, srt)`` for a set operation's
    output rows, the positions in the materialize program's SOURCE
    (:func:`_setop_mat_fn`).  ``union`` ranks ``[a; b]``, ``subtract`` /
    ``intersect`` rank ``[b; a]`` (ops/setops.set_op_flags).  ``narrow``,
    ``fold``: :func:`_unique_count_fn`'s, over BOTH tables' columns."""
    def per_shard(vca, vcb, a_datas, a_valids, b_datas, b_valids):
        cap_a, cap_b = a_datas[0].shape[0], b_datas[0].shape[0]
        # operand structures must match across the two tables: emit a
        # null-flag operand for a column when EITHER side is nullable
        need_nf = tuple((av is not None) or (bv is not None)
                        for av, bv in zip(a_valids, b_valids))
        ko_a, ko_b = (pack.key_operands(
            list(d), list(v), row_mask=live_mask(vc, d[0].shape[0]), fold=fold,
            pad_key=PAD_L, need_null_flags=need_nf, narrow32=narrow)
            for vc, d, v in ((vca, a_datas, a_valids),
                             (vcb, b_datas, b_valids)))
        first, live, sidx = _rank_sorted(
            pack.concat_keyops(ko_a, ko_b) if op == "union"
            else pack.concat_keyops(ko_b, ko_a), live_count(vca, vcb))
        with stage("setop_flags"):      # the side, the address: of sidx
            if op == "union":
                is_b = sidx >= cap_a
                # b's rows stand directly behind a's LIVE rows in the source
                n_a = vca[jax.lax.axis_index(shuffle.ROW_AXIS)]
                src_pos = jnp.where(is_b, sidx - cap_a + n_a, sidx)
                fill = cap_a + cap_b
            else:
                is_b = sidx < cap_b
                src_pos, fill = sidx - cap_b, cap_a     # a kept row is a's
        return repart.kept_positions(setk.set_op_flags(first, live, is_b, op),
                                     src_pos, fill)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


@program_cache()
def _setop_mat_fn(mesh: Mesh, op: str, spec, out_cap: int, window: int):
    """``repart._filter_mat_fn``'s body on the operator's source: ``a``
    alone (``subtract`` / ``intersect``), or a's live rows with b's directly
    behind them, built here (``union``) - addressed at ``cap_a + position``
    the output tile that straddles a's padding would span it whole."""
    if op != "union":
        return _unique_mat_fn.__wrapped__(mesh, spec, out_cap, window)
    take = repart.take_kept(out_cap, spec, window)

    def behind(xa, xb, n_a):
        return jax.lax.dynamic_update_slice(jnp.concatenate([xa, xb]), xb,
                                            (n_a,))

    def per_shard(kept, srt, vca, a_datas, a_valids, b_datas, b_valids):
        n_a = vca[jax.lax.axis_index(shuffle.ROW_AXIS)]
        cap_a, cap_b = a_datas[0].shape[0], b_datas[0].shape[0]
        datas, valids = [], []
        with stage("pack"):
            for da, va, db, vb in zip(a_datas, a_valids, b_datas, b_valids):
                datas.append(behind(da, db, n_a))
                valids.append(None if va is None and vb is None else behind(
                    jnp.ones(cap_a, bool) if va is None else va,
                    jnp.ones(cap_b, bool) if vb is None else vb, n_a))
        return take(kept, srt, tuple(datas), tuple(valids))

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


def set_operation(a: Table, b: Table, op: str,
                  assume_colocated: bool = False) -> Table:
    """union/intersect/subtract with distinct-row semantics (reference
    table.cpp:925-1110).  Distributed path shuffles both tables by full-row
    hash first (:1152-1166).  ``assume_colocated=True`` skips the shuffle
    AND schema alignment (pipelined execution pre-aligns and shuffles the
    resident side once, exec/pipeline.pipelined_set_op).  The output's rows
    are a's kept rows in a's order, then (``union``) b's in b's.

    Device OOM falls back to the streaming chunked pipeline."""
    from .common import run_with_oom_fallback
    for t in (a, b):
        for n in t.column_names:
            if t.column(n).type == LogicalType.LIST:
                raise InvalidError(
                    f"set op on a table with list passthrough column {n!r} "
                    "is not supported (rows are compared by value)")

    def fb(nc):
        from ..exec.pipeline import pipelined_set_op
        return pipelined_set_op(a, b, op, n_chunks=nc)

    ran = None

    def primary():
        nonlocal ran
        ran = _set_operation_impl(a, b, op, assume_colocated)
        return ran

    from ..obs import plan as _plan
    ctx = _plan.node("set_op", kind=op, colocated=bool(assume_colocated))
    with ctx as pn:
        rows_in = a.row_count + b.row_count
        if pn:
            pn.set(rows_in=rows_in)
        res = run_with_oom_fallback(
            primary, can_fallback=not assume_colocated, fallback=fb,
            label="set_op", env=a.env)
        if ran is not None and res is ran:
            # the fallback's nodes say their own names; this one names a
            # route only where the normal path ran to its end
            _said(ctx, pn, op, res, plan_route(a.env, assume_colocated),
                  kind=op, rows_in=rows_in)
        elif pn and type(res) is Table:
            pn.set(rows_out=res.row_count)
        return res


def _set_operation_impl(a: Table, b: Table, op: str,
                        assume_colocated: bool = False) -> Table:
    if op not in ("union", "intersect", "subtract"):
        raise InvalidError(f"unknown set op {op!r}")
    env = check_same_env(a, b)
    if not assume_colocated:
        a, b = _align_schemas(a, b)
    names = a.column_names
    if env.world_size > 1 and not assume_colocated:
        a = shuffle_table(a, names)
        b = shuffle_table(b, names)
    cols_a, cols_b = ([t.column(n) for n in names] for t in (a, b))
    a_arrays, b_arrays = col_arrays(cols_a), col_arrays(cols_b)
    narrow = narrow32_flags(cols_a, cols_b)
    fold = fold_liveness(cols_a, cols_b)
    vca = np.asarray(a.valid_counts, np.int32)
    vcb = np.asarray(b.valid_counts, np.int32)
    if op == "union":
        # the source's lanes hold both tables' values and either's nulls
        spec = lanes.plan_lanes(
            tuple(str(c.data.dtype) for c in cols_a),
            tuple(ca.validity is not None or cb.validity is not None
                  for ca, cb in zip(cols_a, cols_b)), narrow)
        cap, live = a.capacity + b.capacity, vca + vcb
        source = (vca, *a_arrays, *b_arrays)
    else:
        spec = table_lane_spec(cols_a)
        cap, live, source = a.capacity, vca, a_arrays
    if not cap:         # no row to take: the (empty) source is the result
        return a
    meta, srt = _setop_count_fn(
        env.mesh, op, narrow, note_liveness("setops", fold))(
            vca, vcb, *a_arrays, *b_arrays)
    return _taken(a, meta, srt, cap, live, spec,
                  partial(_setop_mat_fn, env.mesh, op, spec), *source)


# ---------------------------------------------------------------------------
# equals (reference table.cpp:1389 Equals / :1440 DistributedEquals)
# ---------------------------------------------------------------------------

@program_cache()
def _equals_fn(mesh: Mesh, kinds: tuple):
    def per_shard(vc, a_datas, a_valids, b_datas, b_valids):
        cap = a_datas[0].shape[0]
        mask = live_mask(vc, cap)
        ok = jnp.ones(cap, bool)
        for da, va, db, vb, kind in zip(a_datas, a_valids, b_datas, b_valids,
                                        kinds):
            va_ = va if va is not None else jnp.ones(cap, bool)
            vb_ = vb if vb is not None else jnp.ones(cap, bool)
            val_eq = pack.op_eq(da, db, kind)
            ok = ok & (va_ == vb_) & (val_eq | ~va_)
        return jnp.all(ok | ~mask).reshape(1)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW, ROW),
                             out_specs=ROW))


def equals(a: Table, b: Table, ordered: bool = True) -> bool:
    """Table equality.  ordered=False compares as multisets by sorting both
    on all columns first (the reference's unordered Equals)."""
    env = check_same_env(a, b)
    if a.column_names != b.column_names:
        return False
    if a.row_count != b.row_count:
        return False
    if a.row_count == 0:
        return True
    from ..status import CylonTypeError
    try:
        a, b = _align_schemas(a, b)
    except CylonTypeError:
        # no common key type => schemas are genuinely incomparable;
        # any other exception is a real bug and propagates
        return False
    if not ordered:
        from .sort import sort_table
        names = a.column_names
        a = sort_table(a, names)
        b = sort_table(b, names)
    # repartition-to-match (reference RepartitionToMatchOtherTable :1414)
    if not np.array_equal(a.valid_counts, b.valid_counts):
        b = repartition(b, tuple(int(x) for x in a.valid_counts))
    if a.capacity != b.capacity:
        from .repart import repad_table
        common = max(a.capacity, b.capacity)
        a = repad_table(a, common)
        b = repad_table(b, common)
    names = a.column_names
    a_datas, a_valids = col_arrays([a.column(n) for n in names])
    b_datas, b_valids = col_arrays([b.column(n) for n in names])
    kinds = tuple("f" if a.column(n).type in (LogicalType.FLOAT32,
                                              LogicalType.FLOAT64) else "i"
                  for n in names)
    vc = np.asarray(a.valid_counts, np.int32)
    res = _equals_fn(env.mesh, kinds)(vc, a_datas, a_valids, b_datas, b_valids)
    return bool(host_array(res).all())


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry) — pure-local
# shard programs; no collective may appear.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _trace_unique_count(mesh):
    w = int(mesh.devices.size)
    cap = 1024
    S = jax.ShapeDtypeStruct
    fn = _unwrap(_unique_count_fn(mesh, "first", (False,)))
    return jax.make_jaxpr(fn)(S((w,), np.int32), (S((w * cap,), np.int64),),
                              (S((w * cap,), np.bool_),))


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._unique_count_fn", _trace_unique_count,
                tags=("setops",))
