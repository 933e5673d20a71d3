"""Table-level set ops (union/intersect/subtract), unique, equals.

TPU-native equivalents of the reference's row-set operators — ``Union``
(table.cpp:925), ``Subtract`` (:997), ``Intersect`` (:1051) and their
distributed wrappers (:1152-1166, shuffle both then local), ``Unique``
(:1306) / ``DistributedUnique`` (:1376), and ``Equals``/``DistributedEquals``
(:1389/:1440 — repartition-to-match then compare).

The reference builds ska::bytell hash sets over row comparators; here rows of
both tables are dense-ranked together per shard (ops/pack.py — the dual-table
comparator analog) and membership/uniqueness become segment min/max logic
(ops/setops.py), followed by a static-capacity compaction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.dtypes import LogicalType
from ..core.table import Table
from ..obs import metrics as _metrics
from ..ops import pack
from ..ops import setops as setk
from ..ops import sort as sortk
from ..status import InvalidError
from ..utils.host import host_array
from ..utils.stages import stage
from .common import (PAD_L, REP, ROW, check_same_env, col_arrays, live_mask,
                     narrow32_flags, promote_key_pair, rebuild_like)
from .repart import repartition, shuffle_table

shard_map = jax.shard_map

#: one count an operator call that ran the normal path to its end, and the
#: rows it returned (``op``: ``unique``, ``union``, ``intersect``,
#: ``subtract``); the plan node's ``route`` says the same call's path
_DISPATCHES = {op: _metrics.counter("setop_dispatches", op=op)
               for op in ("unique", "union", "intersect", "subtract")}
_ROWS_OUT = {op: _metrics.counter("setop_rows_out", op=op)
             for op in _DISPATCHES}


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


# ---------------------------------------------------------------------------
# unique (drop_duplicates)
# ---------------------------------------------------------------------------

def _unique_flags_per_shard(vc, key_datas, key_valids, keep: str, narrow):
    cap = key_datas[0].shape[0]
    mask = live_mask(vc, cap)
    ko = pack.key_operands(list(key_datas), list(key_valids), row_mask=mask,
                           pad_key=PAD_L, narrow32=narrow)
    gids, _ = pack.dense_rank(ko)
    return setk.unique_flags(gids, mask, keep), mask


@program_cache()
def _unique_count_fn(mesh: Mesh, keep: str, narrow: tuple):
    """``narrow``: static per-key flags (common.narrow32_flags) - a 64-bit
    integer key whose host-known bounds fit int32 sorts as ONE operand, not
    a (hi, lo) pair: XLA:TPU compiles a sort in time that grows with its
    operands (ops/pack.SORT_OPERAND_BUDGET)."""
    def per_shard(vc, key_datas, key_valids):
        flags, _ = _unique_flags_per_shard(vc, key_datas, key_valids, keep,
                                           narrow)
        return jnp.sum(flags, dtype=jnp.int32).reshape(1)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=(REP, ROW, ROW),
                             out_specs=ROW))


@program_cache()
def _unique_mat_fn(mesh: Mesh, keep: str, narrow: tuple, out_cap: int, spec):
    from ..ops import lanes

    def per_shard(vc, key_datas, key_valids, datas, valids):
        flags, _ = _unique_flags_per_shard(vc, key_datas, key_valids, keep,
                                           narrow)
        idx, _total = sortk.compact_by_flag(flags, out_cap)
        # ONE lane-matrix gather for all columns (+ f64 side gathers)
        return lanes.gather_columns(spec, list(datas), list(valids), idx)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


def unique_table(table: Table, subset=None, keep: str = "first") -> Table:
    """Drop duplicate rows (by ``subset`` columns, default all).  Distributed:
    shuffle by subset hash so equal rows co-locate; within a shard the
    (source rank, source position) receive order makes keep=first/last pick
    the *globally* first/last occurrence."""
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
        key_cols = [table.column(n) for n in subset]
        key_datas, key_valids = col_arrays(key_cols)
        narrow = narrow32_flags(key_cols)
        vc = np.asarray(table.valid_counts, np.int32)
        counts = host_array(_unique_count_fn(env.mesh, keep, narrow)(
            vc, key_datas, key_valids)).astype(np.int64)
        out_cap = config.pow2ceil(int(counts.max()) if counts.size else 1)
        items = list(table.columns.items())
        datas = tuple(c.data for _, c in items)
        valids = tuple(c.validity for _, c in items)
        from .common import table_lane_spec
        out_d, out_v = _unique_mat_fn(env.mesh, keep, narrow, out_cap,
                                      table_lane_spec(
                                          [c for _, c in items]))(
            vc, key_datas, key_valids, datas, valids)
        res = rebuild_like(items, out_d, out_v, counts, env)
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


def _setop_flags_per_shard(vca, vcb, a_datas, a_valids, b_datas, b_valids,
                           op: str, narrow):
    cap_a, cap_b = a_datas[0].shape[0], b_datas[0].shape[0]
    mask_a = live_mask(vca, cap_a)
    mask_b = live_mask(vcb, cap_b)
    # operand structures must match across the two tables: emit a null-flag
    # operand for a column when EITHER side is nullable
    need_nf = tuple((av is not None) or (bv is not None)
                    for av, bv in zip(a_valids, b_valids))
    ko_a = pack.key_operands(list(a_datas), list(a_valids), row_mask=mask_a,
                             pad_key=PAD_L, need_null_flags=need_nf,
                             narrow32=narrow)
    ko_b = pack.key_operands(list(b_datas), list(b_valids), row_mask=mask_b,
                             pad_key=PAD_L, need_null_flags=need_nf,
                             narrow32=narrow)
    gids_cat, _ = pack.dense_rank(pack.concat_keyops(ko_a, ko_b))
    side_is_b = jnp.concatenate([jnp.zeros(cap_a, bool), jnp.ones(cap_b, bool)])
    mask_cat = jnp.concatenate([mask_a, mask_b])
    flags = setk.set_op_flags(gids_cat, side_is_b, op, mask_cat)
    return flags


@program_cache()
def _setop_count_fn(mesh: Mesh, op: str, narrow: tuple):
    """``narrow``: :func:`_unique_count_fn`'s, over BOTH tables' columns."""
    def per_shard(vca, vcb, a_datas, a_valids, b_datas, b_valids):
        flags = _setop_flags_per_shard(vca, vcb, a_datas, a_valids, b_datas,
                                       b_valids, op, narrow)
        return jnp.sum(flags, dtype=jnp.int32).reshape(1)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW, ROW),
                             out_specs=ROW))


@program_cache()
def _setop_mat_fn(mesh: Mesh, op: str, narrow: tuple, out_cap: int):
    def per_shard(vca, vcb, a_datas, a_valids, b_datas, b_valids):
        flags = _setop_flags_per_shard(vca, vcb, a_datas, a_valids, b_datas,
                                       b_valids, op, narrow)
        idx, _ = sortk.compact_by_flag(flags, out_cap)
        cap_a, cap_b = a_datas[0].shape[0], b_datas[0].shape[0]
        n_cat = cap_a + cap_b
        safe = jnp.clip(idx, 0, max(n_cat - 1, 0))
        out_d, out_v = [], []
        with stage("gather_rows"):
            for da, va, db, vb in zip(a_datas, a_valids, b_datas, b_valids):
                cat = jnp.concatenate([da, db])
                out_d.append(cat[safe])
                if va is None and vb is None:
                    out_v.append(None)
                else:
                    va_ = va if va is not None else jnp.ones(cap_a, bool)
                    vb_ = vb if vb is not None else jnp.ones(cap_b, bool)
                    out_v.append(jnp.concatenate([va_, vb_])[safe])
        return tuple(out_d), tuple(out_v)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW, ROW),
                             out_specs=(ROW, ROW)))


def set_operation(a: Table, b: Table, op: str,
                  assume_colocated: bool = False) -> Table:
    """union/intersect/subtract with distinct-row semantics (reference
    table.cpp:925-1110).  Distributed path shuffles both tables by full-row
    hash first (:1152-1166).  ``assume_colocated=True`` skips the shuffle
    AND schema alignment (pipelined execution pre-aligns and shuffles the
    resident side once, exec/pipeline.pipelined_set_op).

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
    a_datas, a_valids = col_arrays(cols_a)
    b_datas, b_valids = col_arrays(cols_b)
    narrow = narrow32_flags(cols_a, cols_b)
    vca = np.asarray(a.valid_counts, np.int32)
    vcb = np.asarray(b.valid_counts, np.int32)
    counts = host_array(_setop_count_fn(env.mesh, op, narrow)(
        vca, vcb, a_datas, a_valids, b_datas, b_valids)).astype(np.int64)
    out_cap = config.pow2ceil(int(counts.max()) if counts.size else 1)
    out_d, out_v = _setop_mat_fn(env.mesh, op, narrow, out_cap)(
        vca, vcb, a_datas, a_valids, b_datas, b_valids)
    return rebuild_like([(n, a.column(n)) for n in names], out_d, out_v,
                        counts, env)


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
