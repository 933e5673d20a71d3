"""Fused join→groupby: aggregate through the join without materializing it.

The TPU realization of the reference's streaming operator DAG
(cpp/src/cylon/ops/ — ``DisJoinOP`` feeding downstream ops through queues,
SURVEY §2 C9): when a groupby's keys are exactly an inner join's keys and
every aggregation is multiplicity-algebraic, the per-group answer is
computable from the join's *pre-expansion sorted state* (phase 1) — the
output-space expansion (two ~15 ns/slot gathers over every output row, the
dominant join cost) never runs.

The algebra: an inner join's output rows for key group g are the L_g × R_g
cross product, so over the join output

  sum(c_left)   = S_g(c) · R_g          count(c_left) = C_g(c) · R_g
  mean(c_left)  = S_g(c) / C_g(c)       (multiplicity cancels)
  var/std       = moments scale by R_g; ddof applies to the full C·R count

with S/C the per-group masked sum/valid-count of c over the *left rows of
the sorted state* (symmetrically with L_g for right columns).  All of
S, C, L, R come out of the groupby engine's batched prefix-diff machinery
(ops/groupby.grouped_reduce) over the already-sorted state — a few cumsums
and ONE (seg_cap, L) gather.  min/max/quantile/nunique do not reduce to
prefix sums over the state and take the materialize path.

Trigger: ``groupby_aggregate`` calls :func:`try_join_groupby_pushdown`
first; it returns None (and the DeferredTable later materializes
transparently) unless every condition holds.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..utils.cache import jit, program_cache
from ..core.dtypes import LogicalType
from ..core.table import DeferredTable, Table
from ..ops import groupby as gbk
from ..ops import join as joink
from ..ops import lanes
from ..utils import timing
from ..utils.host import host_array
from ..utils.stages import stage, staged
from .common import REP, ROW, BoundedCache, live_count, multi_shard

shard_map = jax.shard_map

#: ops whose join pushdown is exact multiplicity algebra
PUSHDOWN_OPS = {"sum", "count", "mean", "var", "std", "sumsq"}

#: fused callsite-signature (``sig[0]`` the env serial) -> (kept-group
#: bucket, windowed gather allowed, window): what
#: :func:`~.groupby.dispatch_at_bucket` settled on
_SEG_CACHE = BoundedCache()


class JoinState(NamedTuple):
    """Pre-expansion inner-join state a DeferredTable carries for fused
    consumers (built in relational/join.py; device arrays stay sharded).

    Two producers emit this state: the monolithic deferred join (lane
    specs over the output-plan column lists) and the PACKED-PIECE join
    (relational/piece.py — lane specs are the piece sources' own specs
    and ``pl_s`` holds the sorted WINDOW lanes, so the groupby pushdown
    consumes range pieces without any piece ever materializing; columns
    the aggregation never reads are never unpacked).  The fused kernel is
    agnostic: ``plan``/``lspec``/``rspec``/``layout`` are self-consistent
    in both."""
    vcl: np.ndarray      # left per-shard valid counts
    vcr: np.ndarray      # right per-shard valid counts
    idx_s: jax.Array     # (N,) concat-row index at each sorted position
    bnd: jax.Array       # (N,) key-boundary flags of the sorted state
    pl_s: tuple          # the sort's physical arrays: ``layout``'s kept
                         # sorted keys, then the shared payload operands
    lspec: lanes.LaneSpec
    rspec: lanes.LaneSpec
    layout: joink.PayloadLayout   # pl_s -> (left lanes, right lanes)
    plan: tuple          # output plan entries parallel to names
    names: tuple
    types: tuple
    dicts: tuple
    bounds: tuple        # host-known (lo, hi) per output column, or None
    key_names: tuple     # join-key output column names (== left_on)
    cap_l: int
    cap_r: int
    all_live: bool
    #: finalized skew-split plan (relational/skew.SkewPlan) when the join
    #: ran the adaptive heavy-key route: each heavy key's rows span a
    #: RANK GROUP, so the fused kernel's per-shard output rows are
    #: PARTIALS for those keys and resolve() must run the tiny
    #: heavy-partial combine (skew.combine_heavy_partials) before the
    #: result is final.  None for plain colocated joins.
    skew_plan: object = None
    #: with ``skew_plan``: materializes the SPLIT-layout join output
    #: WITHOUT the stitch — the pre-stitch table an order-insensitive
    #: consumer takes when the fused pushdown itself declines
    #: (relational/skew.consume_unstitched include_deferred leg)
    pre_thunk: object = None


def _col_entry(state: JoinState, name: str):
    """(side, lane-col-index) of output column ``name`` in the carried
    state; None when the column is not a plain carried l/r column."""
    try:
        i = state.names.index(name)
    except ValueError:
        return None
    e = state.plan[i]
    if e[0] in ("l", "r"):
        return e[0], e[1]
    return None


@program_cache()
def _fused_fn(mesh: Mesh, n_l: int, all_live: bool, lspec, rspec, layout,
              vspecs: tuple, key_cols: tuple, key_narrow: tuple,
              seg_cap: int, ddof: int, use_window: int = 0,
              sum_forms: tuple = ()):
    """Per-shard fused join+groupby kernel.

    ``vspecs``: per aggregation (side, lane_col_idx, op); ``key_cols``:
    left lane-col index per groupby key; ``layout``: what ``pl_s`` holds
    (ops/join.PayloadLayout); ``sum_forms``: per aggregation, how an
    integer ``sum`` is scanned (relational/groupby.sum_scan_form's
    descriptor; empty = ``pair64`` everywhere).  Live rows form a sorted PREFIX
    (the row-liveness operand sorts padding last), so liveness is a
    position compare — no gather (ops/join.live_sides, the one statement
    of that rule)."""

    def per_shard(vcl, vcr, idx_s, bnd, pl_s):
        N = bnd.shape[0]
        pos = jnp.arange(N, dtype=jnp.int32)
        n_live = jnp.int32(N) if all_live else live_count(vcl, vcr)
        lefts_b, rights_b, live = joink.live_sides(idx_s, n_l, n_live)
        with stage("liveness"):
            lefts = lefts_b.astype(jnp.int32)
            rights = rights_b.astype(jnp.int32)
        with stage("boundaries"):
            first = bnd.astype(bool) | (pos == 0)
            ebnd = jnp.concatenate([first[1:], jnp.ones(1, bool)])
        imax = jnp.int32(2**31 - 1)
        with stage("scan"):
            s_l = jnp.cumsum(lefts).astype(jnp.int32)
            s_r = jnp.cumsum(rights).astype(jnp.int32)
            e_l = jax.lax.cummin(jnp.where(ebnd, s_l, imax), reverse=True)
            e_r = jax.lax.cummin(jnp.where(ebnd, s_r, imax), reverse=True)
            b_l = jax.lax.cummax(jnp.where(first, s_l - lefts, jnp.int32(0)))
            b_r = jax.lax.cummax(jnp.where(first, s_r - rights,
                                           jnp.int32(0)))
        with stage("join_count"):
            l_grp = e_l - b_l    # own group's left count, per position
            r_grp = e_r - b_r
            keep = (l_grp > 0) & (r_grp > 0) & live
        with stage("boundaries"):
            kstart = first & keep
        with stage("scan"):
            kgid = jnp.cumsum(kstart.astype(jnp.int32)).astype(jnp.int32) - 1
        with stage("boundaries"):
            n_groups = (jnp.max(jnp.where(keep, kgid, -1)) + 1).astype(
                jnp.int32)
        # empty segment slots point at the END OF THE LIVE PREFIX, not at
        # N: every dead row is masked out of every lane, so the prefix
        # there already holds the full totals — and the tile of starts
        # that straddles n_groups then spans a few rows, not the whole
        # capacity pad (shape-family padding, N - n_live ~ 1M rows at 32M
        # rows/side, overflowed every window and silently lost the
        # windowed gather)
        # kgid is cumsum(kstart) - 1 and every kept row is live: the
        # invariant grouped_starts' sort rests on
        starts = gbk.grouped_starts(kstart, keep, n_live, seg_cap)

        pl_l, pl_r = joink.payload_lanes(layout, pl_s)
        with stage("unpack"):
            lmat = jnp.stack(pl_l, axis=1)
            rmat = jnp.stack(pl_r, axis=1)
        ldat, lval = lanes.unpack_lanes(lspec, lmat)
        rdat, rval = lanes.unpack_lanes(rspec, rmat)

        @staged("liveness")
        def value_of(side, ci):
            d = ldat[ci] if side == "l" else rdat[ci]
            v = lval[ci] if side == "l" else rval[ci]
            sidemask = lefts_b if side == "l" else rights_b
            vm = sidemask & keep
            if v is not None:
                vm = vm & v
            if jnp.issubdtype(d.dtype, jnp.floating):
                vm = vm & ~jnp.isnan(d)
            return d, vm

        ops_list, vals, masks = [], [], []
        for side, ci, op in vspecs:
            d, vm = value_of(side, ci)
            ops_list.append(op)
            vals.append(d)
            masks.append(vm)
        # the two multiplicity counts ride the same batched pass
        ops_list += ["count", "count"]
        vals += [s_l, s_l]
        masks += [lefts_b & keep, rights_b & keep]

        key_datas = [ldat[ci] for ci in key_cols]
        key_valids = [lval[ci] for ci in key_cols]
        inters, key_out, kval_out, wok = gbk.grouped_reduce(
            ops_list, vals, masks, starts, n_live, key_datas,
            key_valids, seg_cap, key_narrow=key_narrow,
            sum_forms=sum_forms,
            use_window=use_window, blocked_scans=multi_shard())
        l_cnt = inters[-2]["count"]
        r_cnt = inters[-1]["count"]

        res_d, res_v = [], []
        for i, (side, ci, op) in enumerate(vspecs):
            mult = (r_cnt if side == "l" else l_cnt)
            inter = inters[i]
            with stage("segment_reduce"):
                if op == "sum":
                    s = inter["sum"]
                    d, v = s * mult.astype(s.dtype), None
                elif op == "sumsq":
                    s = inter["sumsq"]
                    d, v = s * mult.astype(s.dtype), None
                elif op == "count":
                    d, v = inter["count"] * mult, None
                elif op == "mean":
                    d, v = gbk.finalize("mean", inter, ddof)
                else:  # var/std: moments scale by mult; ddof sees the count
                    scaled = {k: (a * mult.astype(a.dtype) if k != "count"
                                  else a * mult) for k, a in inter.items()}
                    d, v = gbk.finalize(op, scaled, ddof)
            res_d.append(d)
            res_v.append(v)
        # n_groups and the windowed-gather span flag ride ONE output so
        # the dispatch layer pays a single host pull (a second transfer
        # costs another host round trip per dispatch)
        meta = jnp.stack([n_groups, wok.astype(jnp.int32)]).reshape(2)
        return (tuple(key_out), tuple(kval_out), tuple(res_d), tuple(res_v),
                meta)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW),
                             out_specs=(ROW, ROW, ROW, ROW, ROW)))


def window_rule(mesh, seg_cap: int, density: float) -> tuple:
    """THE eligibility rule of the windowed gather, for a dispatch at
    segment space ``seg_cap``: ``(window, why)`` - the window to ask for
    (0 = plain) and, where it is 0, which test said so: TPU only
    (``not_tpu``), measured group density above the coverage floor
    (``density_below_floor``), segment space big enough for the plain
    gather to hurt (``segment_space_small``)."""
    from ..ops import pallas_gather as pg
    if next(iter(mesh.devices.flat)).platform != "tpu":
        return 0, "not_tpu"
    if density < pg.MIN_DENSITY:
        return 0, "density_below_floor"
    if seg_cap < (1 << 20):
        return 0, "segment_space_small"
    return pg.pick_window(density), ""


def window_for(mesh, seg_cap: int, density: float) -> int:
    """:func:`window_rule`'s window alone (what ``chip_smoke.py`` and the
    benchmark hold a callsite's memory to)."""
    return window_rule(mesh, seg_cap, density)[0]


def try_join_groupby_pushdown(table: Table, by: list, specs: list,
                              ddof: int):
    """Fused path when ``table`` is an unmaterialized inner-join result and
    the groupby reduces to multiplicity algebra over its sorted state.
    Returns the result Table, or None to take the normal path."""
    h = try_begin_join_groupby(table, by, specs, ddof)
    return h.resolve() if h is not None else None


def try_begin_join_groupby(table: Table, by: list, specs: list,
                           ddof: int):
    """Dispatch the fused join+groupby WITHOUT waiting for its meta pull.
    Returns a :class:`~.groupby.PendingReduce` (resolve() -> Table), or
    None when the fused path does not apply."""
    if not isinstance(table, DeferredTable) or table.materialized:
        return None
    state = table.op_state
    if not isinstance(state, JoinState):
        return None
    if tuple(by) != state.key_names:
        return None
    #: under a skew plan the heavy keys' per-shard fused rows are
    #: PARTIALS, combinable only for ops whose FINALIZED value is
    #: additive in the probe chunks (S_chunk·R over the members sums to
    #: S_g·R).  mean/var/std finalize to ratios of moments the members
    #: no longer share — those take the materialize path, where
    #: consume_unstitched still skips the stitch (docs/skew.md).
    skew_ops = ("sum", "count", "sumsq")
    if state.skew_plan is not None \
            and any(op not in skew_ops for _, op, _q, _n in specs):
        return None
    vspecs = []
    for col, op, _q, _name in specs:
        if op not in PUSHDOWN_OPS:
            return None
        ent = _col_entry(state, col)
        if ent is None:
            return None
        # string value columns carry dictionary CODES in the lanes;
        # aggregating codes would silently return garbage.  Bail to the
        # normal path, whose validation raises the same InvalidError the
        # materialized path does (only count is code-independent).
        if (state.types[state.names.index(col)] == LogicalType.STRING
                and op != "count"):
            return None
        spec = state.lspec if ent[0] == "l" else state.rspec
        if not spec.cols[ent[1]].lanes:
            return None   # carry-lite f64 column: not in the sorted lanes
        vspecs.append((ent[0], ent[1], op))
    key_cols, key_narrow = [], []
    for k in by:
        ent = _col_entry(state, k)
        if ent is None or ent[0] != "l" \
                or not state.lspec.cols[ent[1]].lanes:
            return None
        key_cols.append(ent[1])
        key_narrow.append(bool(state.lspec.cols[ent[1]].narrow))

    env = table.env
    from .groupby import (PendingReduce, _density_window, _result_table,
                          _result_types, _shrink, _sum_forms,
                          dispatch_at_bucket)
    # result typing from the join output schema
    def col(name):
        # a stand-in with .type / .dictionary / .bounds that keeps no
        # reference to ``state``: its device arrays go when the query does
        i = state.names.index(name)
        return SimpleNamespace(type=state.types[i],
                               dictionary=state.dicts[i],
                               bounds=state.bounds[i])
    val_cols = [col(c) for c, _, _, _ in specs]
    res_types, res_dicts = _result_types(specs, val_cols)
    by_cols = [col(k) for k in by]
    res_names = [n for _, _, _, n in specs]
    # the prefixes run over the whole concatenated state
    sum_forms = _sum_forms(specs, val_cols, state.cap_l + state.cap_r)

    args = (state.vcl, state.vcr, state.idx_s, state.bnd, state.pl_s)
    live = np.asarray(state.vcl, np.int64) + np.asarray(state.vcr, np.int64)

    def call(sc, win):
        return _fused_fn(env.mesh, state.cap_l, state.all_live, state.lspec,
                         state.rspec, state.layout, tuple(vspecs),
                         tuple(key_cols), tuple(key_narrow), sc, ddof,
                         win, sum_forms)(*args)

    def read_meta(res):
        # n_groups and the windowed gather's span flag, one pull
        meta = host_array(res[-1]).astype(np.int64).reshape(-1, 2)
        return meta[:, 0], bool(np.all(meta[:, 1]))

    with timing.region("groupby.fused"):
        h = dispatch_at_bucket(
            _SEG_CACHE,
            (env.serial, tuple(by), tuple(vspecs), state.cap_l, state.cap_r,
             int(state.vcl.sum()), int(state.vcr.sum()), ddof),
            config.pow2ceil(state.cap_l + state.cap_r), call, read_meta,
            _density_window(env.mesh, live))

    def _resolve():
        with timing.region("groupby.fused"):
            (key_out, kval_out, res_d, res_v, _), n_groups = h.resolve()
        out = _result_table(env, by, by_cols, key_out, kval_out, res_names,
                            res_d, res_v, res_types, res_dicts, n_groups)
        out = _shrink(out, n_groups)
        if state.skew_plan is not None:
            # heavy-key member rows are partials: sum them onto the home
            # rank's row and drop the rest — the result equals the
            # unsplit fused plan's table, layout and all (docs/skew.md)
            from .skew import combine_heavy_partials
            out = combine_heavy_partials(out, list(by), res_names,
                                         state.skew_plan)
        else:
            out.grouped_by = tuple(by)
        return out

    return PendingReduce(_resolve)
