"""Table-level join: local + distributed.

TPU-native equivalent of the reference's join stack — ``DistributedJoin``
(table.cpp:861: shuffle both tables by key hash, then local join) over the
local sort-join (join/sort_join.cpp:66, the reference's default algorithm,
join_config.hpp:37) with join_utils.cpp's output assembly (suffix naming,
null sides of outer joins).

The local kernel is the two-phase static-shape single-sort merge in
:mod:`cylon_tpu.ops.join` run per shard under ``shard_map``:

* phase 1 runs THE one stable sort of both sides' packed key tuples and
  returns exact per-shard output counts (the sidecar that replaces Arrow's
  growing builders) plus the per-position geometry carry as device arrays;
* the host picks a pow2 capacity;
* phase 2 reuses the carried geometry — no re-sort, no re-scan — to build
  (l_take, r_take) and gathers every output column through ONE u32
  lane-matrix gather per side (:mod:`cylon_tpu.ops.lanes`) instead of one
  gather per column — the dominant cost on TPU is per-gather, not per-lane.

Key packing consults host-known column bounds (``Column.bounds``) so int64
keys whose values fit in 32 bits sort as a single native operand.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..obs import metrics as _metrics
from ..obs import plan as _plan
from ..utils.cache import jit, program_cache
from ..core.column import Column
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..ops import join as joink
from ..ops import lanes
from ..ops import pack
from ..status import InvalidError
from ..utils import timing
from ..utils.host import host_array
from .common import (PAD_L, PAD_R, REP, ROW, BoundedCache, build_table,
                     check_same_env,
                     sample_positions,
                     col_arrays, fold_liveness, key_bounds,
                     live_count, live_mask, narrow32_flags, note_liveness,
                     promote_key_pair)
from .piece import PackedPiece
from .repart import shuffle_table

shard_map = jax.shard_map

HOW = ("inner", "left", "right", "outer", "semi", "anti")

#: capacity hysteresis: callsite-signature -> last exact output bucket.
#: Lets join_tables dispatch the materialize phase at the PREDICTED capacity
#: before the (blocking) count pull, overlapping the host sync with device
#: work; a mispredict (counts exceed the prediction) just re-dispatches at
#: the correct bucket.  Steady-state loops (benchmarks, iterative pipelines)
#: hit every time.
_CAP_CACHE = BoundedCache()

@program_cache()
def _hash_sample_fn(mesh: Mesh, m: int, nkeys: int):
    """Evenly spaced per-shard sample of the key tuple's ROW HASH —
    detection runs in hash space so multi-column and float keys work
    uniformly and the predicate is exactly the shuffle-routing hash
    (ops/hashing.hash_rows canonicalizes floats and folds validity)."""
    from ..ops import hashing

    def per_shard(vc, *args):
        datas = list(args[:nkeys])
        valids = list(args[nkeys:])
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        n = vc[my]
        h = hashing.hash_rows(datas, valids)
        idx = sample_positions(n, m, cap)
        live = jnp.full((m,), n > 0)
        return h[idx], live

    specs = (REP,) + (ROW,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=(ROW, ROW)))


def _hash_args(cols):
    cap = cols[0].data.shape[0]
    datas = tuple(c.data for c in cols)
    valids = tuple(c.validity if c.validity is not None
                   else np.ones(cap, bool) for c in cols)
    return datas, valids


def _heavy_keys(table: Table, key_names: list, env):
    """Host-side heavy-hitter estimate from a small device sample: key
    HASHES whose owner's projected load passes ``skew.split_rule``'s
    bound (the one threshold of every heavy-key route).  Returns a small
    np uint32 array or None.  Reference analog: the sampled partition
    machinery (table.cpp:620-689) applied to skew (SURVEY.md §7 hard-part
    4).  A hash collision only widens the split to an extra (light) key —
    both sides flag with the same predicate, so joins stay exact."""
    w = env.world_size
    total = int(table.valid_counts.sum())
    if total < w * 64:  # too small to skew-split — skip the device sample
        return None
    cols = [table.column(n) for n in key_names]
    datas, valids = _hash_args(cols)
    m = config.SKEW_SAMPLE
    fn = _hash_sample_fn(env.mesh, m, len(cols))
    vc = np.asarray(table.valid_counts, np.int32)
    vals_d, live_d = fn(vc, *datas, *valids)
    vals = host_array(vals_d).reshape(w, m)
    live = host_array(live_d).reshape(w, m)
    # weight each shard's sample by its true row share — unweighted pooling
    # would let a tiny shard's keys dominate the global estimate
    shares: dict = {}
    for s in range(w):
        lv = vals[s][live[s]]
        if lv.size == 0:
            continue
        weight = float(table.valid_counts[s]) / total / lv.size
        uniq, cnt = np.unique(lv, return_counts=True)
        keep = cnt / lv.size > config.SKEW_MIN_SHARE
        for u, c in zip(uniq[keep], cnt[keep]):
            shares[u] = shares.get(u, 0.0) + c * weight
    from .skew import split_rule
    heavy = [(u, sh) for u, sh in shares.items() if split_rule(sh, w)[2]]
    if not heavy:
        return None
    heavy.sort(key=lambda x: -x[1])
    return np.asarray([u for u, _ in heavy[:config.SKEW_MAX_KEYS]],
                      np.uint32)


@program_cache()
def _heavy_flag_fn(mesh: Mesh, k: int, nkeys: int):
    from ..ops import hashing

    def per_shard(heavy_hashes, *args):
        datas = list(args[:nkeys])
        valids = list(args[nkeys:])
        h = hashing.hash_rows(datas, valids)
        flag = jnp.zeros(h.shape[0], bool)
        for j in range(k):
            flag = flag | (h == heavy_hashes[j])
        return flag

    specs = (REP,) + (ROW,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=ROW))


def _shuffle_for_join(lwork: Table, rwork: Table, left_on, right_on,
                      how: str, env):
    """Distributed co-location with adaptive heavy-key skew splitting.

    Default: hash-shuffle both sides (reference table.cpp:219).  For
    inner/left/right/outer joins with ``CYLON_TPU_SKEW_SPLIT`` armed
    (default), the probe side's sampled key distribution feeds the
    weighted Misra-Gries detector and any finalized :class:`~.skew.
    SkewPlan` (relational/skew.py — the plan facade, lint rule TS115)
    routes the exchange: each heavy key's probe rows land as
    fixed-stride global-order subsequences on the key's rank group
    (order-preserving salted sub-partitioning) and its build rows
    duplicate-broadcast to that group, so no shard ever receives a whole
    heavy key while the caller can stitch the output bit- and
    order-equal to the unsplit hash plan (docs/skew.md).  The plan is
    VOTED over the consensus wire before any split collective runs
    (``Code.SkewPlan``).

    semi/anti keep the legacy round-robin spread: their output is a
    filter of probe rows (no output expansion to rebalance, no stitch),
    and a fully replicated heavy build row lets ANY shard detect the
    match.

    Returns ``(lwork, rwork, split)`` — ``split`` is False (plain hash),
    True (broadcast / legacy spread: co-location broken, no plan), or
    the finalized :class:`~.skew.SkewPlan` (caller must stitch)."""
    from ..parallel import shuffle as shf
    from ..parallel.collectives import allgather_table
    from . import skew as skewmod
    from .repart import concat_tables, exchange_by_targets, filter_table

    # ---- broadcast join: replicate a SMALL side, shuffle NOTHING --------
    # (the classic broadcast-hash-join; reference analog: Bcast(Table) +
    # local join, net/communicator.hpp:51).  Safe only when the small
    # side's unmatched rows are never emitted (they would emit once per
    # replica): small-RIGHT for inner/left/semi/anti, small-LEFT for
    # inner/right.  The big side stays in place, so equal keys are NOT
    # co-located afterwards — the returned flag suppresses grouped_by and
    # the deferred fused pushdown exactly like the skew split does.
    bc = config.BROADCAST_JOIN_ROWS
    if (how in ("inner", "left", "semi", "anti")
            and rwork.row_count <= bc
            and lwork.row_count >= 4 * max(rwork.row_count, 1)):
        # countable path marker (tests/test_fuzz.py regime tier)
        timing.bump("join.broadcast")
        _plan.annotate(route="broadcast", broadcast_side="right")
        return lwork, allgather_table(rwork), True
    if (how in ("inner", "right")
            and lwork.row_count <= bc
            and rwork.row_count >= 4 * max(lwork.row_count, 1)):
        timing.bump("join.broadcast")
        _plan.annotate(route="broadcast", broadcast_side="left")
        return allgather_table(lwork), rwork, True

    if how in ("inner", "left", "right", "outer") and config.SKEW_SPLIT:
        # adaptive skew-split plan (relational/skew.py): detect heavy
        # probe keys, vote the plan, split + duplicate-broadcast.  The
        # escape hatch CYLON_TPU_SKEW_SPLIT=0 is the UNSPLIT baseline
        # the route's bit/order-equality contract is stated against.
        if how == "right":
            probe, probe_on = rwork, right_on
            build, build_on = lwork, left_on
        else:
            probe, probe_on = lwork, left_on
            build, build_on = rwork, right_on
        plan = skewmod.detect(probe, probe_on, env)
        if plan is not None:
            plan = skewmod.finalize_or_none(plan, probe, probe_on,
                                            build, build_on)
        if plan is not None:
            # vote rides the consensus wire BEFORE the split's first
            # collective; every rank adopts the identical plan hash
            skewmod.adopt(plan, env)
            _plan.annotate(route="skew_split", skew_plan=plan.summary())
            probe_out, build_out = skewmod.split_exchange(
                probe, probe_on, build, build_on, plan)
            if how == "right":
                return build_out, probe_out, plan
            return probe_out, build_out, plan
        _plan.annotate(skew_split_armed=True, skew_split_keys=0)

    if how in ("semi", "anti"):
        # legacy spread: output ⊆ left rows, and a replicated heavy
        # build row lets ANY shard detect the match
        probe, probe_on = lwork, left_on
        build, build_on = rwork, right_on
        heavy = _heavy_keys(probe, probe_on, env)
        if heavy is not None:
            bcols = [build.column(n) for n in build_on]
            bdatas, bvalids = _hash_args(bcols)
            flag = _heavy_flag_fn(env.mesh, len(heavy), len(bcols))(
                heavy, *bdatas, *bvalids)
            build_heavy = filter_table(build, flag)
            # replication guard: if the BUILD side is itself heavy on
            # these keys, W-way replication would recreate the blow-up
            # the split exists to avoid — fall back to plain hashing
            if (build_heavy.row_count * env.world_size
                    > config.SKEW_GUARD_RATIO * max(build.row_count, 1)
                    and build_heavy.row_count > config.SKEW_GUARD_ROWS):
                _plan.annotate(route="hash", skew_guard_fallback=True)
                return (shuffle_table(lwork, left_on),
                        shuffle_table(rwork, right_on), False)
            _plan.annotate(route="skew_split", heavy_keys=int(len(heavy)))
            build_light = filter_table(build, ~flag)
            build_out = concat_tables(
                [shuffle_table(build_light, build_on),
                 allgather_table(build_heavy)])
            pcols = [probe.column(n) for n in probe_on]
            pdatas, pvalids = _hash_args(pcols)
            tgt = shf.skew_targets(env.mesh, pdatas, pvalids,
                                   probe.valid_counts, heavy)
            counts = shf.count_targets(env.mesh, tgt)
            probe_out = exchange_by_targets(probe, tgt, counts)
            return probe_out, build_out, True
    return (shuffle_table(lwork, left_on), shuffle_table(rwork, right_on),
            False)


def _sorted_state(vcl, vcr, l_datas, l_valids, r_datas, r_valids,
                  narrow: tuple, payloads: tuple = (),
                  all_live: bool = False, keep: tuple = (),
                  fold: bool = False):
    """Per-shard single-sort join state (bnd, idx_s, n_live, ``pl_s`` =
    the ``keep`` sorted key operands then the sorted payloads,
    ops/join.PayloadLayout).

    Both sides must build structurally identical operand lists, so the
    null-flag presence per key column is the union of the two sides' and the
    narrow-key decision is made by the caller for the pair.

    ``all_live=True`` (host-known: both tables' valid_counts == capacity:
    no padding at all) builds no row mask (n_live=None); otherwise padding
    sorts last - by a liveness operand that leads the sort, or, with
    ``fold`` (common.fold_liveness: the leading key operand has room), by
    that operand's two top values, one operand fewer - which is what makes
    the live rows the sorted prefix ``[0, n_live)``."""
    cap_l, cap_r = l_datas[0].shape[0], r_datas[0].shape[0]
    mask_l = None if all_live else live_mask(vcl, cap_l)
    mask_r = None if all_live else live_mask(vcr, cap_r)
    need_nf = tuple((lv is not None) or (rv is not None)
                    for lv, rv in zip(l_valids, r_valids))
    ko_l = pack.key_operands(list(l_datas), list(l_valids), row_mask=mask_l,
                             pad_key=PAD_L, need_null_flags=need_nf,
                             narrow32=narrow, fold=fold)
    ko_r = pack.key_operands(list(r_datas), list(r_valids), row_mask=mask_r,
                             pad_key=PAD_R, need_null_flags=need_nf,
                             narrow32=narrow, fold=fold)
    bnd, idx_s, pl_s = joink.join_sort_state(ko_l, ko_r, payloads, keep)
    return bnd, idx_s, None if all_live else live_count(vcl, vcr), pl_s


@program_cache()
def _semi_flag_fn(mesh: Mesh, narrow: tuple, all_live: bool, anti: bool,
                  fold: bool = False):
    """Per-left-row matched flag for SEMI/ANTI joins over the single-sort
    state: one run of the boundary algebra (right-count per key run), no
    output expansion at all — the output is a filter of the left table.
    Null keys match null keys (pandas merge semantics, same as the other
    join types here).  Reference: the LEFT_SEMI/LEFT_ANTI shapes the C++
    core reaches via unmatched-count bookkeeping in its sort join
    (sort_join.cpp:66 ``advance()`` run extraction)."""

    def per_shard(vcl, vcr, l_datas, l_valids, r_datas, r_valids):
        cap_l = l_datas[0].shape[0]
        bnd, idx_s, n_live, _pl = _sorted_state(
            vcl, vcr, l_datas, l_valids, r_datas, r_valids, narrow, (),
            all_live, fold=fold)
        n = bnd.shape[0]
        pos = jnp.arange(n, dtype=jnp.int32)
        lefts_b, rights_b, _live = joink.live_sides(idx_s, cap_l, n_live)
        rights = rights_b.astype(jnp.int32)
        first = bnd.astype(bool) | (pos == 0)
        s_r = jnp.cumsum(rights).astype(jnp.int32)
        ebnd = jnp.concatenate([first[1:], jnp.ones(1, bool)])
        imax = jnp.int32(2**31 - 1)
        e_r = jax.lax.cummin(jnp.where(ebnd, s_r, imax), reverse=True)
        b_r = jax.lax.cummax(jnp.where(first, s_r - rights, jnp.int32(0)))
        matched = (e_r - b_r) > 0
        keep = (matched ^ anti) & lefts_b
        tgt = jnp.where(lefts_b, idx_s, jnp.int32(cap_l))
        return jnp.zeros(cap_l + 1, bool).at[tgt].set(
            keep, mode="drop")[:cap_l]

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW, ROW),
                             out_specs=ROW))


@program_cache()
def _count_fn(mesh: Mesh, how: str, narrow: tuple,
              lspec: lanes.LaneSpec | None = None,
              rspec: lanes.LaneSpec | None = None,
              layout: joink.PayloadLayout = joink.PayloadLayout(),
              all_live: bool = False, slim: bool = False,
              fold: bool = False):
    """Phase 1: sort once; return per-shard exact counts + carried state.

    With ``lspec``/``rspec`` (inner/left joins over fully-laneable output
    columns), that side's u32 lane matrix RIDES THE SORT as payload
    operands — 1.1-1.25 ns a row an operand on v5e (ledger, PRs 29-34) vs
    ~15 ns/row for the gathers the materialize phase would otherwise pay:
    ``rspec`` kills the dependent ``idx_s[mpos]`` + right lane-matrix
    gathers, ``lspec`` folds the left values into the meta-stack gather
    that phase 2 already does.  ``layout`` (ops/join.PayloadLayout, built
    on the host by ``ops/join.payload_layout`` from these specs) says which
    operands carry them: the two sides share operands and a key column's
    lanes come back from the sorted key.  ``fold`` (common.fold_liveness,
    the same answer ``layout`` was built with): padding sorts last inside
    the leading key operand and no liveness operand is built."""
    assert (layout.nl, layout.nr) == tuple(
        0 if sp is None else sp.n_lanes for sp in (lspec, rspec))

    def per_shard(vcl, vcr, l_datas, l_valids, r_datas, r_valids,
                  lg_cols, lg_valids, rg_cols, rg_valids):
        cap_l = l_datas[0].shape[0]
        cap_r = r_datas[0].shape[0]
        lmat = None if lspec is None \
            else lanes.pack_lanes(lspec, lg_cols, lg_valids)
        rmat = None if rspec is None \
            else lanes.pack_lanes(rspec, rg_cols, rg_valids)
        payloads = joink.payload_operands(layout, lmat, rmat, cap_l, cap_r)
        bnd, idx_s, n_live, pl_s = _sorted_state(
            vcl, vcr, l_datas, l_valids, r_datas, r_valids, narrow, payloads,
            all_live, layout.kept_keys, fold)
        n, carry = joink.join_carry(bnd, idx_s, n_live, cap_l, how)
        if slim:
            # deferred-join state: only what the fused consumer needs
            # (relational/fused.py) — dropping the other carry arrays frees
            # ~5 N-length buffers of HBM while the state is held; a later
            # materialization rebuilds the carry from (idx_s, bnd) with
            # scans alone (_carry_fn — the sort never runs twice)
            return (n.reshape(1), idx_s, bnd) + pl_s
        return (n.reshape(1),) + tuple(carry) + pl_s

    n_out = (3 if slim else 7) + layout.n_arrays
    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW, ROW, ROW, ROW,
                                       ROW, ROW, ROW),
                             out_specs=(ROW,) * n_out))


@program_cache()
def _carry_fn(mesh: Mesh, how: str, cap_l: int, all_live: bool):
    """Recompute the full phase-1 carry from a held SLIM state (idx_s, bnd)
    — prefix scans only (~1 ns/row), no re-sort.  Used when a deferred
    join materializes: the slim outputs are a superset of what join_carry
    needs as inputs, so the dominant single-sort never runs twice."""

    def per_shard(vcl, vcr, idx_s, bnd):
        n_live = None if all_live else live_count(vcl, vcr)
        _, carry = joink.join_carry(bnd, idx_s, n_live, cap_l, how)
        return tuple(carry)

    return jit(shard_map(per_shard, mesh=mesh,
                             in_specs=(REP, REP, ROW, ROW),
                             out_specs=(ROW,) * 6))


@program_cache()
def _un_count_fn(mesh: Mesh):
    """Per-shard count of an OUTER join's appended unmatched-right rows
    (the carry's ``un`` flags) — the skew stitch needs the zone-B split
    of every shard's output to reconstruct the unsplit plan's row order
    (relational/skew.stitch_join_output).  One tiny pure-local sum."""

    def per_shard(un):
        return jnp.sum(un, dtype=jnp.int32).reshape(1)

    return jit(shard_map(per_shard, mesh=mesh, in_specs=ROW,
                             out_specs=ROW))


@program_cache()
def _materialize_fn(mesh: Mesh, how: str, out_cap: int, cap_l: int,
                    plan: tuple, lspec: lanes.LaneSpec,
                    rspec: lanes.LaneSpec,
                    layout: joink.PayloadLayout = joink.PayloadLayout()):
    """Phase 2.  ``plan`` entries (static):
    ("l", i, needs_valid) — output column = left lane-matrix column i;
    ("r", j, needs_valid) — right lane-matrix column j;
    ("k", i, j, needs_valid) — coalesce left col i with right col j.

    ``layout`` (ops/join.PayloadLayout) says what phase 1's ``pl_s``
    holds.  A right side that rode the sort (``carry_match``): right
    values come from ONE (out, Lr) gather of the sorted lanes at the match
    positions instead of idx_s[mpos] + a second lane-matrix gather.  A
    left side that rode (``carry_emit``): its lanes ride join_take's
    meta-stack gather — no separate left gather at all.  Both only for how
    in (inner, left)."""

    carry_emit, carry_match = layout.nl > 0, layout.nr > 0
    l_f64 = any(not c.lanes for c in lspec.cols)
    r_f64 = any(not c.lanes for c in rspec.cols)

    def per_shard(carry, pl_s, l_cols, l_valids, r_cols, r_valids):
        pl_e, pl_m = joink.payload_lanes(layout, pl_s)
        tk = joink.join_take(joink.JoinCarry(*carry), cap_l, how, out_cap,
                             extra=pl_e, carry_emit=carry_emit,
                             carry_match=carry_match,
                             emit_idx=carry_emit and l_f64,
                             match_idx=carry_match and r_f64)
        if carry_emit:
            emat = jnp.stack(tk.extra, axis=1)      # already at out slots
            ldat, lval = lanes.unpack_lanes(lspec, emat)
            l_ok = tk.valid
            if l_f64:   # carry-lite: f64 columns gather by take index
                ldat = list(ldat)
                for i, d in lanes.gather_laneless(lspec, l_cols,
                                                  tk.l_take).items():
                    ldat[i] = d
        else:
            ldat, lval = lanes.gather_columns(lspec, l_cols, l_valids,
                                              tk.l_take)
            l_ok = tk.l_take >= 0
        if carry_match:
            smat = jnp.stack(pl_m, axis=1)          # (N, Lr) sorted lanes
            rrows = smat[jnp.clip(tk.mpos, 0, smat.shape[0] - 1)]
            rdat, rval = lanes.unpack_lanes(rspec, rrows)
            r_ok = tk.matched
            if r_f64:
                rdat = list(rdat)
                for i, d in lanes.gather_laneless(rspec, r_cols,
                                                  tk.r_take).items():
                    rdat[i] = d
        else:
            rdat, rval = lanes.gather_columns(rspec, r_cols, r_valids,
                                              tk.r_take)
            r_ok = tk.r_take >= 0

        return _plan_outputs(plan, ldat, lval, l_ok, rdat, rval, r_ok)

    return jit(shard_map(
        per_shard, mesh=mesh,
        in_specs=(ROW, ROW, ROW, ROW, ROW, ROW),
        out_specs=(ROW, ROW)))


def _plan_outputs(plan, ldat, lval, l_ok, rdat, rval, r_ok):
    """Assemble the output (datas, valids) from per-side gathered columns
    per the static ``plan`` (traced; shared by the materialize programs)."""

    def side_out(datas, vals, ok, i, needs_valid):
        d = datas[i]
        if not needs_valid:
            return d, None
        v = ok if vals[i] is None else (ok & vals[i])
        return d, v

    out_d, out_v = [], []
    for entry in plan:
        if entry[0] == "k":
            _, i, j, needs_valid = entry
            dl, vl = side_out(ldat, lval, l_ok, i, True)
            dr, vr = side_out(rdat, rval, r_ok, j, True)
            d = jnp.where(l_ok, dl, dr)
            v = jnp.where(l_ok, vl, vr)
            out_d.append(d)
            out_v.append(v if needs_valid else None)
        else:
            side, i, needs_valid = entry
            datas, vals, ok = ((ldat, lval, l_ok) if side == "l"
                               else (rdat, rval, r_ok))
            d, v = side_out(datas, vals, ok, i, needs_valid)
            out_d.append(d)
            out_v.append(v)
    return tuple(out_d), tuple(out_v)


# ---------------------------------------------------------------------------
# packed-piece entry: joins that consume PackedPiece window descriptors
# (relational/piece.py) — the range-partitioned pipeline's fast path.  The
# window slice and lane unpack happen INSIDE the jitted join program,
# fused with key-operand construction: keys unpack first, payload lanes
# ride the phase-1 sort and unpack lazily in the carry/materialize stage.
# The seed's materialize-then-join path (PackedPiece.to_table + the normal
# colocated join) is the reference these programs are exactly equal to.
# ---------------------------------------------------------------------------

def _window(spec: lanes.LaneSpec, arrs, s, cap: int):
    """(lane-matrix window | None, tuple of f64 windows) of one side's
    packed arrays at per-shard offset ``s`` — dynamic slices only; XLA
    drops any window a consumer never reads."""
    has_mat = spec.n_lanes > 0
    mat = lanes.slice_lanes(spec, arrs[0], s, cap) if has_mat else None
    f64w = tuple(jax.lax.dynamic_slice(a, (s,), (cap,))
                 for a in arrs[int(has_mat):])
    return mat, f64w


def _window_keys(spec: lanes.LaneSpec, mat, f64w, key_idx: tuple):
    """Unpack ONLY the key columns from a window — the fused half of the
    seed's unpack-everything + re-pack-keys round trip."""
    fpos = {i: j for j, i in enumerate(
        i for i, c in enumerate(spec.cols) if not c.lanes)}
    datas, valids = [], []
    for i in key_idx:
        if spec.cols[i].lanes:
            d, v = lanes.unpack_column(spec, mat, i)
        else:
            d = f64w[fpos[i]]
            v = lanes.unpack_column(spec, mat, i)[1] if spec.n_lanes \
                else None
        datas.append(d)
        valids.append(v)
    return datas, valids


@program_cache()
def _packed_count_fn(mesh: Mesh, how: str, narrow: tuple, need_nf: tuple,
                     lspec: lanes.LaneSpec, rspec: lanes.LaneSpec,
                     layout: joink.PayloadLayout,
                     kil: tuple, kir: tuple, cap_l: int, cap_r: int,
                     n_arrs_l: int, n_arrs_r: int, all_live: bool,
                     slim: bool = False, fold: bool = False):
    """Phase 1 over packed windows: slice both windows, unpack only the
    KEY columns, sort once, return per-shard exact counts + carried state.
    The window's OWN lanes ride the sort as payload where ``layout``
    (ops/join.PayloadLayout, as in :func:`_count_fn`) says that side rides
    — there is no separate pack step at all (the windows already are lane
    matrices)."""

    def per_shard(vcl, vcr, sl, sr, *arrs):
        arrs_l, arrs_r = arrs[:n_arrs_l], arrs[n_arrs_l:]
        my = jax.lax.axis_index(ROW_AXIS)
        mat_l, f64_l = _window(lspec, arrs_l, sl[my], cap_l)
        mat_r, f64_r = _window(rspec, arrs_r, sr[my], cap_r)
        l_datas, l_valids = _window_keys(lspec, mat_l, f64_l, kil)
        r_datas, r_valids = _window_keys(rspec, mat_r, f64_r, kir)
        mask_l = None if all_live else live_mask(vcl, cap_l)
        mask_r = None if all_live else live_mask(vcr, cap_r)
        ko_l = pack.key_operands(l_datas, l_valids, row_mask=mask_l,
                                 pad_key=PAD_L, need_null_flags=need_nf,
                                 narrow32=narrow, fold=fold)
        ko_r = pack.key_operands(r_datas, r_valids, row_mask=mask_r,
                                 pad_key=PAD_R, need_null_flags=need_nf,
                                 narrow32=narrow, fold=fold)
        # a side that does not ride has no lane in ``layout``: its window
        # is handed over all the same and nothing of it is read
        payloads = joink.payload_operands(layout, mat_l, mat_r, cap_l, cap_r)
        bnd, idx_s, pl_s = joink.join_sort_state(ko_l, ko_r, payloads,
                                                 layout.kept_keys)
        n_live = None if all_live else live_count(vcl, vcr)
        n, carry = joink.join_carry(bnd, idx_s, n_live, cap_l, how)
        if slim:
            return (n.reshape(1), idx_s, bnd) + pl_s
        return (n.reshape(1),) + tuple(carry) + pl_s

    n_out = (3 if slim else 7) + layout.n_arrays
    in_specs = (REP, REP, REP, REP) + (ROW,) * (n_arrs_l + n_arrs_r)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=(ROW,) * n_out))


@program_cache()
def _packed_materialize_fn(mesh: Mesh, how: str, out_cap: int, cap_l: int,
                           cap_r: int, plan: tuple,
                           lspec: lanes.LaneSpec, rspec: lanes.LaneSpec,
                           layout: joink.PayloadLayout,
                           n_arrs_l: int, n_arrs_r: int,
                           donate: tuple = ()):
    """Phase 2 over packed windows.  Carried sides unpack from the sorted
    payload lanes exactly like :func:`_materialize_fn`; non-carried sides
    gather whole rows from the WINDOW lane matrix (one (out, L) gather —
    the matrix already exists, so there is no pack step) and unpack only
    at the output rows.  f64 side columns slice their window and gather by
    take index (carry-LITE, same as the monolith).

    ``donate``: argnums of per-piece phase-1 state this FINAL dispatch
    consumes — ``(0,)`` the carry tuple, ``(0, 1)`` carry + sorted
    payload lanes — so the steady-state loop reuses those buffers for
    the output instead of allocating fresh ones (docs/pipeline.md
    donation rules).  Never includes the window arrays (positions 4+):
    they are the packed SOURCE, shared by every remaining piece — a
    use-after-donate (lint rule TS108).  Callers donate only on the last
    dispatch over the state: the speculative-capacity dispatch and any
    fused consumer sharing the state via JoinState must not donate."""

    carry_emit, carry_match = layout.nl > 0, layout.nr > 0
    l_f64 = any(not c.lanes for c in lspec.cols)
    r_f64 = any(not c.lanes for c in rspec.cols)

    def f64_pick(spec, f64w, take):
        # spread the compact window list back to spec column slots so the
        # ONE laneless-gather implementation (lanes.gather_laneless)
        # serves both the packed and the monolithic materialize paths
        datas = [None] * len(spec.cols)
        wins = iter(f64w)
        for i, c in enumerate(spec.cols):
            if not c.lanes:
                datas[i] = next(wins)
        return lanes.gather_laneless(spec, datas, take)

    def per_shard(carry, pl_s, sl, sr, *arrs):
        arrs_l, arrs_r = arrs[:n_arrs_l], arrs[n_arrs_l:]
        my = jax.lax.axis_index(ROW_AXIS)
        pl_e, pl_m = joink.payload_lanes(layout, pl_s)
        tk = joink.join_take(joink.JoinCarry(*carry), cap_l, how, out_cap,
                             extra=pl_e, carry_emit=carry_emit,
                             carry_match=carry_match,
                             emit_idx=carry_emit and l_f64,
                             match_idx=carry_match and r_f64)
        mat_l, f64_l = _window(lspec, arrs_l, sl[my], cap_l)
        mat_r, f64_r = _window(rspec, arrs_r, sr[my], cap_r)
        if carry_emit:
            emat = jnp.stack(tk.extra, axis=1)      # already at out slots
            ldat, lval = lanes.unpack_lanes(lspec, emat)
            l_ok = tk.valid
            if l_f64:
                ldat = list(ldat)
                for i, d in f64_pick(lspec, f64_l, tk.l_take).items():
                    ldat[i] = d
        else:
            l_ok = tk.l_take >= 0
            if lspec.n_lanes:
                lrows = mat_l[jnp.clip(tk.l_take, 0, cap_l - 1)]
                ldat, lval = lanes.unpack_lanes(lspec, lrows)
                ldat, lval = list(ldat), list(lval)
            else:
                ldat = [None] * len(lspec.cols)
                lval = [None] * len(lspec.cols)
            for i, d in f64_pick(lspec, f64_l, tk.l_take).items():
                ldat[i] = d
        if carry_match:
            smat = jnp.stack(pl_m, axis=1)          # (N, Lr) sorted lanes
            rrows = smat[jnp.clip(tk.mpos, 0, smat.shape[0] - 1)]
            rdat, rval = lanes.unpack_lanes(rspec, rrows)
            r_ok = tk.matched
            if r_f64:
                rdat = list(rdat)
                for i, d in f64_pick(rspec, f64_r, tk.r_take).items():
                    rdat[i] = d
        else:
            r_ok = tk.r_take >= 0
            if rspec.n_lanes:
                rrows = mat_r[jnp.clip(tk.r_take, 0, cap_r - 1)]
                rdat, rval = lanes.unpack_lanes(rspec, rrows)
                rdat, rval = list(rdat), list(rval)
            else:
                rdat = [None] * len(rspec.cols)
                rval = [None] * len(rspec.cols)
            for i, d in f64_pick(rspec, f64_r, tk.r_take).items():
                rdat[i] = d
        return _plan_outputs(plan, ldat, lval, l_ok, rdat, rval, r_ok)

    in_specs = (ROW, ROW, REP, REP) + (ROW,) * (n_arrs_l + n_arrs_r)
    jit_kwargs = {"donate_argnums": tuple(donate)} if donate else {}
    return jit(shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=(ROW, ROW)), **jit_kwargs)


#: registered at import, so that a snapshot shows both at 0 before any
#: join: the operands handed to the join's ``lax.sort`` summed over the
#: count programs dispatched, and those dispatches (static numbers of the
#: layout, bumped on the host; benchmark metric join_sort_operands_per_join)
_SORT_OPERANDS = _metrics.counter("join_sort_operands")
_SORT_DISPATCHES = _metrics.counter("join_sort_dispatches")


def _note_sort(layout: joink.PayloadLayout) -> None:
    """One count program was dispatched with ``layout``: bump the two
    registry counters and, under ``obs.explain*``, say on the join's plan
    node what its one sort carries."""
    _SORT_OPERANDS.inc(layout.sort_operands)
    _SORT_DISPATCHES.inc()
    _plan.annotate(sort_operands=layout.sort_operands,
                   payload_operands=layout.n_payloads,
                   aliased_key_lanes=layout.nl - len(layout.riding))


def _fits32_meta(dtype, bounds) -> bool:
    """fits_int32 over piece metadata (physical dtype name + host bounds)."""
    dt = np.dtype(dtype)
    if dt.itemsize != 8 or dt.kind not in ("i", "u"):
        return False
    return bounds is not None and bounds[0] >= -(1 << 31) \
        and bounds[1] <= (1 << 31) - 1


def _same_dictionary(a, b) -> bool:
    if a is b:
        return True
    if a is None or b is None:
        return False
    if isinstance(a, np.ndarray) and isinstance(b, np.ndarray):
        return len(a) == len(b) and bool(np.array_equal(a, b))
    try:
        return bool(a == b)
    except Exception:  # noqa: BLE001 — exotic dictionary types: identity only
        return False


def _packed_keys_compatible(pl: PackedPiece, pr: PackedPiece,
                            left_on, right_on) -> bool:
    """Packed joins cannot promote keys inside the lanes — the pipeline
    promotes BEFORE packing, so pieces normally arrive aligned.  Any
    residual mismatch (dtype, dictionary code space) bails to the
    materialized path, which promotes like any other join."""
    for ln, rn in zip(left_on, right_on):
        i, j = pl.column_names.index(ln), pr.column_names.index(rn)
        if pl.spec.cols[i].dtype != pr.spec.cols[j].dtype:
            return False
        tl, tr = pl.meta[i][1], pr.meta[j][1]
        if tl != tr:
            return False
        dl, dr = pl.meta[i][2], pr.meta[j][2]
        if (dl is not None or dr is not None) \
                and not _same_dictionary(dl, dr):
            return False
    return True


class _LazyCounts:
    """A dispatched-but-not-pulled device count vector.  Sharing one
    instance between a DeferredTable's ``counts_thunk`` and its
    materialize thunk makes the host sync happen at most once, and only
    when someone actually needs the counts — a fused consumer that drains
    the join state never does (the piece loop's software pipeline)."""

    __slots__ = ("_dev", "value")

    def __init__(self, dev):
        self._dev = dev
        self.value = None

    def __call__(self) -> np.ndarray:
        if self.value is None:
            self.value = host_array(self._dev).astype(np.int64)
            self._dev = None
        return self.value


def _packed_statics(pl: PackedPiece, pr: PackedPiece, left_on, right_on,
                    how: str, suffixes, coalesce_keys: bool):
    """Derive every static input of the packed join programs (shared by
    the impl and the AOT prewarm)."""
    names_l, names_r = pl.column_names, pr.column_names
    kil = tuple(names_l.index(n) for n in left_on)
    kir = tuple(names_r.index(n) for n in right_on)
    need_nf = tuple((pl.spec.cols[i].valid_bit >= 0)
                    or (pr.spec.cols[j].valid_bit >= 0)
                    for i, j in zip(kil, kir))
    narrow = tuple(_fits32_meta(pl.spec.cols[i].dtype, pl.meta[i][3])
                   and _fits32_meta(pr.spec.cols[j].dtype, pr.meta[j][3])
                   for i, j in zip(kil, kir))

    coalesce = coalesce_keys and list(left_on) == list(right_on)
    key_set_l, key_set_r = set(left_on), set(right_on)
    overlap = (set(names_l) & set(names_r)) - (
        key_set_l if coalesce else set())
    plan, names, types, dicts, bounds = [], [], [], [], []
    for i, (n, t, dc, nb) in enumerate(pl.meta):
        has_v = pl.spec.cols[i].valid_bit >= 0
        if coalesce and n in key_set_l:
            j = kir[left_on.index(n)]
            _rn, _rt, _rdc, rnb = pr.meta[j]
            rv = pr.spec.cols[j].valid_bit >= 0
            bounds.append(None if nb is None or rnb is None
                          else (min(nb[0], rnb[0]), max(nb[1], rnb[1])))
            if how in ("inner", "left"):
                plan.append(("l", i, has_v))
            elif how == "right":
                plan.append(("r", j, rv))
            else:
                plan.append(("k", i, j, has_v or rv))
        else:
            plan.append(("l", i, has_v or how in ("right", "outer")))
            bounds.append(nb)
            n = n + suffixes[0] if n in overlap else n
        names.append(n)
        types.append(t)
        dicts.append(dc)
    for j, (n, t, dc, nb) in enumerate(pr.meta):
        if coalesce and n in key_set_r:
            continue
        rv = pr.spec.cols[j].valid_bit >= 0
        plan.append(("r", j, rv or how in ("left", "outer")))
        names.append(n + suffixes[1] if n in overlap else n)
        types.append(t)
        dicts.append(dc)
        bounds.append(nb)

    def can_carry(spec) -> bool:
        return bool(how in ("inner", "left")
                    and any(c.lanes for c in spec.cols))

    carry_emit = can_carry(pl.spec) and pl.spec.n_lanes <= 6
    carry_match = can_carry(pr.spec) and pr.spec.n_lanes <= 8
    all_live = bool((pl.lens == pl.piece_cap).all()
                    and (pr.lens == pr.piece_cap).all())
    # common.fold_liveness over piece metadata: the first key's dtype,
    # null flag and host bounds on both sides
    fold = not all_live and pack.fold_room(
        pl.spec.cols[kil[0]].dtype, need_nf[0],
        [key_bounds(*p.meta[i][1:])
         for p, i in ((pl, kil[0]), (pr, kir[0]))])
    # the window's own spec holds every column, the keys among them
    layout = joink.payload_layout(
        pl.spec if carry_emit else None, pr.spec if carry_match else None,
        kil, tuple(pl.spec.cols[i].dtype for i in kil), need_nf, narrow,
        all_live, fold)
    return (kil, kir, need_nf, narrow, coalesce, tuple(plan), tuple(names),
            tuple(types), tuple(dicts), tuple(bounds), layout, all_live,
            fold)


def prewarm_packed_join(pl: PackedPiece, pr: PackedPiece, left_on,
                        right_on, how: str, suffixes, allow_defer: bool,
                        coalesce_keys: bool = True) -> None:
    """AOT-compile the phase-1 program for this piece-pair SHAPE
    (``.lower().compile()`` — nothing executes): with per-range piece
    capacities precomputed, every distinct program can compile before the
    range loop starts instead of stalling dispatch mid-stream.  The
    executable lands in the persistent compile cache, where the in-process
    jit call path picks it up; best-effort — any failure just means the
    loop compiles lazily like the seed did."""
    if not (config.PREWARM_PIECE_PROGRAMS and config.COMPILE_CACHE_ENABLED):
        return
    try:
        (kil, kir, need_nf, narrow, coalesce, _plan, _names, _types,
         _dicts, _bounds, layout, all_live, fold) = _packed_statics(
            pl, pr, left_on, right_on, how, suffixes, coalesce_keys)
        slim = (how == "inner" and layout.nl and layout.nr
                and coalesce and allow_defer)
        fn = _packed_count_fn(
            pl.env.mesh, how, narrow, need_nf, pl.spec, pr.spec, layout,
            kil, kir, pl.piece_cap, pr.piece_cap, len(pl.arrs),
            len(pr.arrs), all_live, bool(slim), fold)
        vcl = np.asarray(pl.lens, np.int32)
        vcr = np.asarray(pr.lens, np.int32)
        from ..exec.compiler import aot_compile
        aot_compile(fn, vcl, vcr, pl.starts, pr.starts,
                    *pl.arrs, *pr.arrs)
    except Exception:  # noqa: BLE001 — best-effort warm only
        pass


def _join_packed_impl(pl: PackedPiece, pr: PackedPiece, left_on, right_on,
                      how: str, suffixes, coalesce_keys: bool,
                      allow_defer: bool) -> Table:
    env = pl.env
    if pr.env is not env and pr.env.mesh is not env.mesh:
        raise InvalidError("pieces belong to different CylonEnvs")
    # LRU bump for the HBM ledger: the spill tier's eviction order is
    # "cold first", measured by last piece-loop CONSUMPTION, not just
    # descriptor creation (exec/memory)
    from ..exec import memory
    memory.touch(pl.reg)
    memory.touch(pr.reg)
    (kil, kir, need_nf, narrow, coalesce, plan, names, types, dicts,
     bounds, layout, all_live, fold) = _packed_statics(
        pl, pr, left_on, right_on, how, suffixes, coalesce_keys)
    cap_l, cap_r = pl.piece_cap, pr.piece_cap
    vcl = np.asarray(pl.lens, np.int32)
    vcr = np.asarray(pr.lens, np.int32)

    defer = bool(how == "inner" and layout.nl and layout.nr and coalesce
                 and allow_defer)
    fn = _packed_count_fn(env.mesh, how, narrow, need_nf, pl.spec, pr.spec,
                          layout, kil, kir, cap_l, cap_r, len(pl.arrs),
                          len(pr.arrs), all_live, defer, fold)
    args = (vcl, vcr, pl.starts, pr.starts) + pl.arrs + pr.arrs
    _note_sort(layout)
    note_liveness("join", fold, all_live)

    if defer:
        with timing.region("join.sort_count"):
            res = fn(*args)
        counts_dev, idx_s_s, bnd_s = res[0], res[1], res[2]
        pl_s = tuple(res[3:])
        # the counts stay ON DEVICE: the next piece's programs can be
        # enqueued before this piece's host sync, and a fused consumer
        # that drains the state never pulls them at all
        holder = _LazyCounts(counts_dev)

        def materialize_cols():
            counts = holder()
            out_cap = config.pow2ceil(int(counts.max())
                                      if counts.size else 1)
            with timing.region("join.materialize"):
                carry = _carry_fn(env.mesh, how, cap_l, all_live)(
                    vcl, vcr, idx_s_s, bnd_s)
                # donate the freshly built carry (exclusively owned here)
                # but NOT pl_s — the JoinState shares those lanes with any
                # fused consumer that drains the deferred state (TS108)
                mfn = _packed_materialize_fn(
                    env.mesh, how, out_cap, cap_l, cap_r, plan, pl.spec,
                    pr.spec, layout, len(pl.arrs), len(pr.arrs),
                    donate=(0,) if config.DONATE_BUFFERS else ())
                out_d, out_v = mfn(carry, pl_s, pl.starts, pr.starts,
                                   *pl.arrs, *pr.arrs)
            return {nme: Column(d, t, v, dc, bounds=b)
                    for nme, d, v, t, dc, b in
                    zip(names, out_d, out_v, types, dicts, bounds)}

        from ..core.table import DeferredTable
        from .fused import JoinState
        state = JoinState(
            vcl=vcl, vcr=vcr, idx_s=idx_s_s, bnd=bnd_s, pl_s=pl_s,
            lspec=pl.spec, rspec=pr.spec, layout=layout, plan=plan,
            names=names,
            types=types, dicts=dicts, bounds=bounds,
            key_names=tuple(left_on),
            cap_l=cap_l, cap_r=cap_r, all_live=all_live)
        out = DeferredTable(
            env, None, None, materialize_cols,
            (names, types, dicts, tuple(bool(e[-1]) for e in plan)),
            op_state=state, counts_thunk=holder)
        out.grouped_by = tuple(left_on)
        return out

    with timing.region("join.sort_count"):
        res = fn(*args)
        counts_dev, carry = res[0], res[1:7]
        pl_s = tuple(res[7:])
    cache_key = ("packed", env.serial, how, narrow, cap_l, cap_r,
                 int(pl.lens.sum()), int(pr.lens.sum()), tuple(left_on),
                 tuple(right_on), tuple(pl.column_names),
                 tuple(pr.column_names))
    predicted = _CAP_CACHE.get(cache_key)
    mat_args = (carry, pl_s, pl.starts, pr.starts) + pl.arrs + pr.arrs

    def mat_fn(cap, donate=()):
        return _packed_materialize_fn(
            env.mesh, how, cap, cap_l, cap_r, plan, pl.spec, pr.spec,
            layout, len(pl.arrs), len(pr.arrs), donate=donate)

    # phase-1 state (carry + sorted payload lanes) dies with this piece:
    # its LAST materialize dispatch donates it so the output reuses the
    # buffers.  The speculative dispatch below must NOT donate — a
    # capacity miss re-dispatches over the same state (TS108)
    final_donate = (0, 1) if config.DONATE_BUFFERS else ()
    with timing.region("join.materialize"):
        out_d = out_v = None
        if predicted is not None:
            # speculative dispatch at the predicted capacity BEFORE the
            # blocking count pull — the sync overlaps device work
            out_d, out_v = mat_fn(predicted)(*mat_args)
        counts = host_array(counts_dev).astype(np.int64)
        out_cap = config.pow2ceil(int(counts.max()) if counts.size else 1)
        _CAP_CACHE.put(cache_key, out_cap)
        if out_d is None or out_cap > predicted:
            out_d, out_v = mat_fn(out_cap, donate=final_donate)(*mat_args)
    out = build_table(names, out_d, out_v, types, dicts, counts, env,
                      bounds=bounds)
    if coalesce:
        # pieces are key-grouped (sorted windows) and hash-colocated —
        # same grouped contract as the colocated monolith
        out.grouped_by = tuple(left_on)
    return out


def _join_packed_entry(left, right, left_on, right_on, how, suffixes,
                       coalesce_keys, allow_defer):
    left_on = [left_on] if isinstance(left_on, str) else list(left_on)
    right_on = [right_on] if isinstance(right_on, str) else list(right_on)
    if len(left_on) != len(right_on) or not left_on:
        raise InvalidError("left_on/right_on must be equal-length, non-empty")
    pl = left if isinstance(left, PackedPiece) else None
    pr = right if isinstance(right, PackedPiece) else None
    use_packed = (config.PACKED_PIECES and pl is not None and pr is not None
                  and how in ("inner", "left", "right", "outer")
                  and _packed_keys_compatible(pl, pr, left_on, right_on))
    if use_packed:
        from ..exec.recovery import maybe_inject
        maybe_inject("join.piece_cap")  # CapacityOverflowError test point
        return _join_packed_impl(pl, pr, left_on, right_on, how, suffixes,
                                 coalesce_keys, bool(allow_defer))
    # no packed entry for this shape: materialize the window(s) and take
    # the normal colocated path (the equivalence reference)
    lt = pl.to_table() if pl is not None else left
    rt = pr.to_table() if pr is not None else right
    return join_tables(lt, rt, left_on, right_on, how=how,
                       suffixes=suffixes, coalesce_keys=coalesce_keys,
                       assume_colocated=True, allow_defer=allow_defer)


def join_tables(left: Table, right: Table, left_on, right_on,
                how: str = "inner", suffixes=("_x", "_y"),
                coalesce_keys: bool = True,
                assume_colocated: bool = False,
                allow_defer: bool | None = None) -> Table:
    """Join two tables. Distributed path = hash-shuffle both sides on the
    (promoted) keys, then per-shard local sort-join — the reference's exact
    skeleton (table.cpp:861,219,194).

    ``assume_colocated=True`` skips the shuffle: the caller guarantees equal
    keys already share a shard on both sides (pipelined execution shuffles
    the build side once and streams pre-shuffled probe chunks).

    Device OOM falls back to the range-partitioned pipeline
    (exec/pipeline.py — the reference's operator-DAG slot): the work tiles
    over key ranges so sort scratch and per-piece output each fit; retried
    at growing range counts.  Range disjointness makes the fallback valid
    for all four join types.

    ``left``/``right`` may be :class:`~cylon_tpu.relational.piece.
    PackedPiece` window descriptors instead of Tables (the pipelined range
    loop's fast path): the window slice + lane unpack then run INSIDE the
    jitted join program, fused with key-operand construction — no
    per-piece unpack→repack HBM round trip.  Packed inputs are colocated
    by construction and have no streaming fallback (the pieces ARE the
    streaming decomposition)."""
    from .common import run_with_oom_fallback

    if isinstance(left, PackedPiece) or isinstance(right, PackedPiece):
        # per-piece plan node (docs/pipeline.md): the window caps ARE the
        # piece geometry the pipelined node's children are judged by
        with _plan.node(
                "join.piece", how=how,
                cap_l=int(getattr(left, "piece_cap", 0)),
                cap_r=int(getattr(right, "piece_cap", 0))) as pn:
            if pn:
                pn.set(rows_in=int(getattr(left, "lens", np.zeros(1)).sum()
                                   + getattr(right, "lens",
                                             np.zeros(1)).sum()))
            res = _join_packed_entry(left, right, left_on, right_on, how,
                                     suffixes, coalesce_keys, allow_defer)
            if pn and type(res) is Table:
                pn.set(rows_out=res.row_count)
            return res

    def fallback(nc):
        from ..exec.pipeline import pipelined_join
        return pipelined_join(left, right, left_on, right_on, how=how,
                              n_chunks=nc, suffixes=suffixes)

    lo = [left_on] if isinstance(left_on, str) else list(left_on)
    ro = [right_on] if isinstance(right_on, str) else list(right_on)
    with _plan.node(
            "join", how=how, left_on=tuple(lo), right_on=tuple(ro),
            route=("colocated" if assume_colocated
                   or left.env.world_size == 1 else "hash")) as pn:
        if pn:
            pn.set(rows_in=left.row_count + right.row_count)
            _plan.profile_keys(pn, left, lo)
        res = run_with_oom_fallback(
            lambda: _join_tables_impl(left, right, left_on, right_on, how,
                                      suffixes, coalesce_keys,
                                      assume_colocated, allow_defer),
            can_fallback=(not assume_colocated and coalesce_keys
                          and how not in ("semi", "anti")),
            fallback=fallback, label="join", env=left.env)
        if pn and type(res) is Table:
            pn.set(rows_out=res.row_count)
        return res


def join_tables_multi(tables: list, ons: list, how: str = "inner",
                      suffixes=("_x", "_y")) -> Table:
    """N-way join on ONE shared key set: every table is co-partitioned
    ONCE (a single hash shuffle each — or a broadcast for small tables),
    then the chain runs as LOCAL colocated joins.  A naive binary chain
    re-shuffles the accumulated intermediate at every step; this issues
    exactly one exchange per input table.  Reference: the multi-table
    ``JoinTables`` overload, cpp/src/cylon/join/join.hpp:29.

    ``ons[i]``: key column name(s) of ``tables[i]`` (all key sets must be
    equal length; values are compared pairwise-promoted).  ``how`` applies
    to every step (inner/left)."""
    if len(tables) < 2 or len(tables) != len(ons):
        raise InvalidError("join_tables_multi needs >= 2 tables with one "
                           "key set each")
    if how not in ("inner", "left"):
        raise InvalidError("join_tables_multi supports how in "
                           "('inner','left') — chain others manually")
    ons = [[o] if isinstance(o, str) else list(o) for o in ons]
    if len({len(o) for o in ons}) != 1:
        raise InvalidError("all key sets must have the same length")
    env = tables[0].env
    # promote every table's keys to ONE representation BEFORE the
    # shuffles: the routing hash depends on the physical dtype (int64
    # hashes as two u32 lanes, int32 as one) and on string dictionaries
    # (table-local codes) — unpromoted shuffles would send equal keys to
    # different shards and the colocated chain would silently drop
    # matches.  Pairwise promotion converges on cols[0]; a second sweep
    # brings the middles to the final representation (same pattern as
    # concat_tables).
    tables = list(tables)
    for ki in range(len(ons[0])):
        cols = [t.column(ons[i][ki]) for i, t in enumerate(tables)]
        for j in range(1, len(cols)):
            cols[0], cols[j] = promote_key_pair(cols[0], cols[j])
        cols = [cols[0]] + [promote_key_pair(cols[0], c)[1]
                            for c in cols[1:]]
        tables = [t.with_columns({ons[i][ki]: c})
                  for i, (t, c) in enumerate(zip(tables, cols))]
    bc = config.BROADCAST_JOIN_ROWS
    big = max(t.row_count for t in tables)
    shuffled = []
    from ..parallel.collectives import allgather_table
    for i, (t, on) in enumerate(zip(tables, ons)):
        if env.world_size == 1:
            shuffled.append(t)
        elif (i > 0 and t.row_count <= bc
                and big >= 4 * max(t.row_count, 1)):
            # only RIGHT-side tables may replicate: a replicated LEFT
            # accumulator would emit its matches once per shard
            shuffled.append(allgather_table(t))
        else:
            shuffled.append(shuffle_table(t, on))
    acc = shuffled[0]
    acc_on = list(ons[0])
    for t, on in zip(shuffled[1:], ons[1:]):
        # Post-suffix tracking of the ACCUMULATED left key names (review,
        # r5): when the key name sets are equal the keys coalesce onto the
        # left names; otherwise a left key colliding with a right column
        # is renamed with suffixes[0] (mirror of _join_tables_impl's
        # output plan).  The seed's fallback silently switched to the
        # RIGHT table's key names here — null for unmatched rows in a
        # `how='left'` chain, fabricating null-key matches downstream.
        coalesce = acc_on == on
        overlap = (set(acc.column_names) & set(t.column_names)) \
            - (set(acc_on) if coalesce else set())
        acc = join_tables(acc, t, acc_on, on, how=how, suffixes=suffixes,
                          assume_colocated=True, allow_defer=False)
        acc_on = [n if (coalesce or n not in overlap) else n + suffixes[0]
                  for n in acc_on]
        missing = [n for n in acc_on if n not in acc.column_names]
        if missing:
            raise InvalidError(
                f"accumulated join key column(s) {missing} disappeared "
                "after suffix renaming — choose non-colliding suffixes "
                "or rename the payload columns before join_tables_multi")
    acc.grouped_by = None
    return acc


def _join_tables_impl(left: Table, right: Table, left_on, right_on,
                      how: str = "inner", suffixes=("_x", "_y"),
                      coalesce_keys: bool = True,
                      assume_colocated: bool = False,
                      allow_defer: bool | None = None) -> Table:
    if how not in HOW:
        raise InvalidError(f"how must be one of {HOW}, got {how!r}")
    env = check_same_env(left, right)
    left_on = [left_on] if isinstance(left_on, str) else list(left_on)
    right_on = [right_on] if isinstance(right_on, str) else list(right_on)
    if len(left_on) != len(right_on) or not left_on:
        raise InvalidError("left_on/right_on must be equal-length, non-empty")

    # promote key pairs to comparable representations
    lkey_cols, rkey_cols = [], []
    for ln, rn in zip(left_on, right_on):
        a, b = promote_key_pair(left.column(ln), right.column(rn))
        lkey_cols.append(a)
        rkey_cols.append(b)
    lwork = left.with_columns(dict(zip(left_on, lkey_cols)))
    rwork = right.with_columns(dict(zip(right_on, rkey_cols)))

    from . import skew as skewmod

    skew_split = False
    skew_plan = None
    if env.world_size > 1 and not assume_colocated:
        with timing.region("join.shuffle"):
            lwork, rwork, skew_split = _shuffle_for_join(
                lwork, rwork, left_on, right_on, how, env)
        if isinstance(skew_split, skewmod.SkewPlan):
            # the caller-side half of the adaptive route: the local join
            # below runs unchanged over the split layout, then the
            # output stitches back into the UNSPLIT plan's global row
            # order (bit- and order-equal; docs/skew.md)
            skew_plan = skew_split

    l_key_cols = [lwork.column(n) for n in left_on]
    r_key_cols = [rwork.column(n) for n in right_on]
    l_datas, l_valids = col_arrays(l_key_cols)
    r_datas, r_valids = col_arrays(r_key_cols)
    narrow = narrow32_flags(l_key_cols, r_key_cols)
    vcl = np.asarray(lwork.valid_counts, np.int32)
    vcr = np.asarray(rwork.valid_counts, np.int32)

    if how in ("semi", "anti"):
        # output ⊆ left rows: one matched-flag pass + filter, no plan and
        # no expansion (reference: JoinTables' semi/anti shapes)
        all_live_sa = bool((vcl == lwork.capacity).all()
                           and (vcr == rwork.capacity).all())
        fold = note_liveness("join", fold_liveness(l_key_cols, r_key_cols),
                             all_live_sa)
        with timing.region("join.semi"):
            flag = _semi_flag_fn(env.mesh, narrow, all_live_sa,
                                 how == "anti", fold)(
                vcl, vcr, l_datas, l_valids, r_datas, r_valids)
        from .repart import filter_table
        return filter_table(lwork, flag)

    # host.join_plan: the output plan's loops, the lane specs and what
    # rides the sort - host work between the last exchange (or the call's
    # start) and the count program's launch
    with timing.span("host.join_plan"):
        cache_key = (env.serial, how, narrow, lwork.capacity, rwork.capacity,
                     int(lwork.valid_counts.sum()),
                     int(rwork.valid_counts.sum()),
                     tuple(left_on), tuple(right_on),
                     tuple(lwork.column_names), tuple(rwork.column_names))
        predicted = _CAP_CACHE.get(cache_key)

        # ---- output plan -------------------------------------------------
        coalesce = coalesce_keys and left_on == right_on
        key_set_l, key_set_r = set(left_on), set(right_on)
        overlap = (set(lwork.column_names) & set(rwork.column_names)) - (
            key_set_l if coalesce else set())

        # lane-matrix column lists per side (keys first, then gathered columns)
        l_cols_list: list[Column] = []
        r_cols_list: list[Column] = []

        def lane_col(side_list, col) -> int:
            side_list.append(col)
            return len(side_list) - 1

        #: left key name -> its left lane column, where it is an output column
        l_key_lane: dict = {}

        plan, names, types, dicts, bounds = [], [], [], [], []

        def merged_bounds(a: Column, b: Column):
            if a.bounds is None or b.bounds is None:
                return None
            return (min(a.bounds[0], b.bounds[0]),
                    max(a.bounds[1], b.bounds[1]))

        for n in lwork.column_names:
            col = lwork.column(n)
            if coalesce and n in key_set_l:
                ki = left_on.index(n)
                rcol = rwork.column(right_on[ki])
                bounds.append(merged_bounds(col, rcol))
                # the coalesced key only needs BOTH sides for outer joins; for
                # inner/left every output row has a live left key (and for
                # right
                # a live right key) — one lane set instead of two
                if how in ("inner", "left"):
                    l_key_lane[n] = lane_col(l_cols_list, col)
                    plan.append(("l", l_key_lane[n], col.validity is not None))
                elif how == "right":
                    plan.append(("r", lane_col(r_cols_list, rcol),
                                 rcol.validity is not None))
                else:
                    needs_valid = (col.validity is not None
                                   or rcol.validity is not None)
                    plan.append(("k", lane_col(l_cols_list, col),
                                 lane_col(r_cols_list, rcol), needs_valid))
            else:
                needs_valid = (col.validity is not None
                               or how in ("right", "outer"))
                if n in key_set_l:
                    l_key_lane[n] = len(l_cols_list)
                plan.append(("l", lane_col(l_cols_list, col), needs_valid))
                bounds.append(col.bounds)
                n = n + suffixes[0] if n in overlap else n
            names.append(n)
            types.append(col.type)
            dicts.append(col.dictionary)
        for n in rwork.column_names:
            if coalesce and n in key_set_r:
                continue
            col = rwork.column(n)
            needs_valid = col.validity is not None or how in ("left", "outer")
            plan.append(("r", lane_col(r_cols_list, col), needs_valid))
            names.append(n + suffixes[1] if n in overlap else n)
            types.append(col.type)
            dicts.append(col.dictionary)
            bounds.append(col.bounds)

        # host-known bounds narrow 64-bit lanes to one u32 lane each
        from .common import table_lane_spec
        lspec = table_lane_spec(l_cols_list)
        rspec = table_lane_spec(r_cols_list)

        # ride a side's lane matrix through the phase-1 sort when every one of
        # its output columns is laneable (no f64 side channels) and the lane
        # count is small — a sort operand costs 1.1-1.25 ns a row on v5e
        # (ledger, PRs 29-34) vs ~15 ns/row gathers.  carry_match (right side)
        # kills the dependent idx_s[mpos] + right lane-matrix gathers;
        # carry_emit (left side) folds the left values into the meta-stack
        # gather join_take already performs.
        def _can_carry(spec, col_list, budget: int) -> bool:
            # laneless f64 columns do not disqualify (carry-LITE: laneable
            # columns ride the sort, f64 columns keep their take-index
            # gathers); there must be at least one laneable data column
            return bool(how in ("inner", "left") and col_list
                        and any(c.lanes for c in spec.cols)
                        and spec.n_lanes <= budget)

        carry_match = _can_carry(rspec, r_cols_list, 8)
        carry_emit = _can_carry(lspec, l_cols_list, 6)
        all_live = bool((vcl == lwork.capacity).all()
                        and (vcr == rwork.capacity).all())
        need_nf = tuple((lv is not None) or (rv is not None)
                        for lv, rv in zip(l_valids, r_valids))
        # padding sorts last inside the leading key operand where it has
        # room (one rule, common.fold_liveness), else by a liveness operand
        fold = note_liveness("join", fold_liveness(l_key_cols, r_key_cols),
                             all_live)

        # ---- deferred materialization (reference ops-DAG slot, C9) -------
        # Inner joins whose output columns fully ride the phase-1 sort can hand
        # the pre-expansion sorted state to a fused downstream consumer
        # (groupby pushdown, relational/fused.py) — the output expansion (two
        # ~15 ns/slot gathers over every output row, the dominant join cost)
        # never runs for join->groupby-on-the-join-keys pipelines.  Any other
        # access materializes transparently (core.table.DeferredTable).  Phase
        # 1 runs SLIM (no carry outputs, ~5 N-length HBM buffers freed) — a
        # later materialization rebuilds the carry from the held (idx_s, bnd) with
        # prefix scans only (_carry_fn) — the sort never runs twice.
        # allow_defer default: colocated (pipelined chunk) joins only defer
        # when the caller says a fused consumer will drain each chunk's state
        # immediately (pipelined_join with a sink).  The sink-less concat path
        # would retain every chunk's slim state simultaneously alongside the
        # resident build side — the HBM headroom the pipeline exists to keep.
        if allow_defer is None:
            allow_defer = not assume_colocated
        # the adaptive skew-split route (skew_plan) defers exactly like the
        # plain co-located join — the fused consumer combines the heavy
        # keys' per-shard partials (fused.py + skew.combine_heavy_partials),
        # any other access materializes THROUGH the stitch.  The plan-less
        # split=True legs (broadcast join / legacy semi-anti spread) have no
        # plan to reconstruct co-location from and stay eager.
        defer = (how == "inner" and carry_emit and carry_match and coalesce
                 and allow_defer
                 and (skew_plan is not None or not skew_split))
        if not defer:
            # an eager join's sides ride only as far as the sort stays
            # within pack.SORT_OPERAND_BUDGET (its compile time grows with
            # every operand): the keys' operands and the index come first,
            # the two sides share what is left; a side that does not fit is
            # gathered at the take index.  A liveness flag folded into the
            # key still counts as the operand it was: no join changes route
            # because an operand came free (ROADMAP S13)
            room = pack.SORT_OPERAND_BUDGET - 1 - len(pack.key_operand_slots(
                tuple(d.dtype for d in l_datas), need_nf, narrow,
                row_mask=not all_live)[0])
            carry_match = carry_match and rspec.n_lanes <= room
            carry_emit = carry_emit and lspec.n_lanes <= room

        l_gather_args = (tuple(c.data for c in l_cols_list),
                         tuple(c.validity for c in l_cols_list))
        r_gather_args = (tuple(c.data for c in r_cols_list),
                         tuple(c.validity for c in r_cols_list))
        # phase 1 only consumes the columns that ride the sort; keep the
        # rest out of the trace (no needless retraces)
        count_l_args = l_gather_args if carry_emit else ((), ())
        count_r_args = r_gather_args if carry_match else ((), ())
        count_args = (vcl, vcr, l_datas, l_valids, r_datas, r_valids,
                      *count_l_args, *count_r_args)
        cl_spec = lspec if carry_emit else None
        cr_spec = rspec if carry_match else None
        # what rides the sort, said once (ops/join.PayloadLayout): the two
        # sides share operands, a left key column is the sorted key itself
        layout = joink.payload_layout(
            cl_spec, cr_spec, tuple(l_key_lane.get(n) for n in left_on),
            tuple(d.dtype for d in l_datas), need_nf, narrow, all_live, fold)

    if defer:
        with timing.region("join.sort_count"):
            res = _count_fn(env.mesh, how, narrow, cl_spec, cr_spec, layout,
                            all_live, slim=True, fold=fold)(*count_args)
        _note_sort(layout)
        counts_dev, idx_s_s, bnd_s = res[0], res[1], res[2]
        pl_s = tuple(res[3:])
        counts = host_array(counts_dev).astype(np.int64)
        out_cap = config.pow2ceil(int(counts.max()) if counts.size else 1)
        _CAP_CACHE.put(cache_key, out_cap)

        def materialize_cols():
            with timing.region("join.materialize"):
                # the slim state already holds the sorted payloads and
                # (idx_s, bnd); the carry rebuilds from scans alone — the
                # dominant single-sort does NOT run a second time
                carry = _carry_fn(env.mesh, how, lwork.capacity, all_live)(
                    vcl, vcr, idx_s_s, bnd_s)
                fn = _materialize_fn(env.mesh, how, out_cap, lwork.capacity,
                                     tuple(plan), lspec, rspec, layout)
                out_d, out_v = fn(carry, pl_s, *l_gather_args,
                                  *r_gather_args)
            return {nme: Column(d, t, v, dc, bounds=b)
                    for nme, d, v, t, dc, b in
                    zip(names, out_d, out_v, types, dicts, bounds)}

        def fb(nc):
            from ..exec.pipeline import pipelined_join
            return pipelined_join(left, right, left_on, right_on,
                                  how=how, n_chunks=nc,
                                  suffixes=suffixes)

        def pre_table():
            # SPLIT-layout materialization (no stitch): the pre-stitch
            # table consume_unstitched hands an order-insensitive
            # consumer when the fused pushdown declined
            from .common import run_with_oom_fallback

            def mat():
                pre = Table(materialize_cols(), env, counts)
                pre.grouped_by = None
                return pre

            return run_with_oom_fallback(mat, True, fb,
                                         "deferred-join materialize",
                                         env=env)

        def thunk():
            # deferred materialization OOMs outside join_tables' wrapper —
            # give it the same streaming fallback; a fallback returns a
            # whole Table, which DeferredTable adopts (layout may differ)
            from .common import run_with_oom_fallback

            def mat():
                cols = materialize_cols()
                if skew_plan is None:
                    return cols
                # merge half of the adaptive route for a non-fused
                # consumer: stitch the split-layout output back into the
                # unsplit hash plan's global row order (docs/skew.md)
                pre = Table(cols, env, counts)
                pre.grouped_by = None
                with timing.region("join.skew_stitch"):
                    return skewmod.stitch_join_output(
                        pre, list(left_on), skew_plan, how, None)

            return run_with_oom_fallback(mat, True, fb,
                                         "deferred-join materialize",
                                         env=env)

        from ..core.table import DeferredTable
        from .fused import JoinState
        if skew_plan is not None:
            from .repart import even_partition_counts
            total = int(counts.sum())
            d_counts = even_partition_counts(total, env.world_size)
            d_cap = config.pow2ceil(int(d_counts.max()) if total else 1)
        else:
            d_counts, d_cap = counts, out_cap
        state = JoinState(
            vcl=vcl, vcr=vcr, idx_s=idx_s_s, bnd=bnd_s, pl_s=pl_s,
            lspec=lspec, rspec=rspec, layout=layout, plan=tuple(plan),
            names=tuple(names), types=tuple(types), dicts=tuple(dicts),
            bounds=tuple(bounds), key_names=tuple(left_on),
            cap_l=lwork.capacity, cap_r=rwork.capacity, all_live=all_live,
            skew_plan=skew_plan,
            pre_thunk=pre_table if skew_plan is not None else None)
        out = DeferredTable(
            env, d_counts, d_cap, thunk,
            (tuple(names), tuple(types), tuple(dicts),
             tuple(bool(e[-1]) for e in plan)),
            op_state=state)
        # a skew-split layout is not co-located (heavy keys span their
        # rank groups), and the stitched materialization is in global
        # row order on the even layout — neither satisfies grouped_by
        out.grouped_by = None if skew_plan is not None else tuple(left_on)
        return out

    with timing.region("join.sort_count"):
        res = _count_fn(env.mesh, how, narrow, cl_spec, cr_spec, layout,
                        all_live, fold=fold)(*count_args)
        counts_dev, carry = res[0], res[1:7]
        pl_s = tuple(res[7:])
    _note_sort(layout)

    mat_args = (carry, pl_s, *l_gather_args, *r_gather_args)

    with timing.region("join.materialize"):
        out_d = out_v = None
        if predicted is not None:
            # speculative dispatch at the predicted capacity BEFORE the
            # blocking count pull — the sync overlaps device work
            fn = _materialize_fn(env.mesh, how, predicted, lwork.capacity,
                                 tuple(plan), lspec, rspec, layout)
            out_d, out_v = fn(*mat_args)
        counts = host_array(counts_dev).astype(np.int64)
        out_cap = config.pow2ceil(int(counts.max()) if counts.size else 1)
        _CAP_CACHE.put(cache_key, out_cap)
        if out_d is None or out_cap > predicted:
            fn = _materialize_fn(env.mesh, how, out_cap, lwork.capacity,
                                 tuple(plan), lspec, rspec, layout)
            out_d, out_v = fn(*mat_args)
    out = build_table(names, out_d, out_v, types, dicts, counts, env,
                      bounds=bounds)
    if skew_plan is not None:
        # merge half of the adaptive route: per-row positions in the
        # UNSPLIT plan's global order + one order-preserving exchange
        # (repart.place_by_global_pos) — the result is bit- and
        # order-equal to the plain hash plan, on BALANCED shards.  The
        # stitch is DEFERRED (DeferredTable + skew.StitchState): an
        # order-insensitive consumer (groupby) takes the pre-stitch
        # table and the merge exchange never runs; any other access
        # stitches transparently.
        un_counts = None
        if how == "outer":
            # per-shard appended unmatched-right counts (zone B) from
            # the phase-1 carry's `un` flags — one tiny pull
            un_counts = host_array(_un_count_fn(env.mesh)(carry[5])) \
                .reshape(-1).astype(np.int64)
        if coalesce:
            key_out = list(left_on)
        elif how == "right":
            key_out = [n + suffixes[1] if n in overlap else n
                       for n in right_on]
        else:
            key_out = [n + suffixes[0] if n in overlap else n
                       for n in left_on]
        from .repart import even_partition_counts
        pre = out
        pre.grouped_by = None
        total = int(counts.sum())
        dest = even_partition_counts(total, env.world_size)

        def stitch_thunk():
            with timing.region("join.skew_stitch"):
                return skewmod.stitch_join_output(
                    pre, key_out, skew_plan, how, un_counts)

        from ..core.table import DeferredTable
        dt = DeferredTable(
            env, dest, config.pow2ceil(int(dest.max()) if total else 1),
            stitch_thunk,
            (tuple(names), tuple(types), tuple(dicts),
             tuple(bool(e[-1]) for e in plan)),
            op_state=skewmod.StitchState(pre, skew_plan, how, un_counts,
                                         key_out))
        dt.grouped_by = None
        return dt
    if coalesce and not skew_split:
        # join output rows are key-grouped per shard (sorted merge order) and
        # keys are co-located across shards (hash shuffle) -> groupby on the
        # same keys can skip shuffle + rank (relational/groupby.py fast path).
        # Skew splitting spreads heavy keys across shards, so the co-location
        # half of the contract does not hold there.
        out.grouped_by = tuple(left_on)
    return out


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry): the join kernels
# are pure-local shard programs — the jaxpr pass asserts NO collective ever
# appears in them (the shuffle happens upstream in parallel/shuffle.py), no
# row-scale i32→i64 widening, zero host callbacks.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _decl_args(mesh, cap=1024):
    w = int(mesh.devices.size)
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32)
    keys = (S((w * cap,), np.int64),)
    valids = (S((w * cap,), np.bool_),)
    return w, S, vc, keys, valids


def _trace_semi_flag(mesh):
    _w, _S, vc, keys, valids = _decl_args(mesh)
    fn = _unwrap(_semi_flag_fn(mesh, (False,), False, False))
    return jax.make_jaxpr(fn)(vc, vc, keys, valids, keys, valids)


def _trace_count(mesh):
    _w, _S, vc, keys, valids = _decl_args(mesh)
    fn = _unwrap(_count_fn(mesh, "inner", (False,)))
    return jax.make_jaxpr(fn)(vc, vc, keys, valids, keys, valids,
                              (), (), (), ())


def _trace_carry(mesh):
    w, S, vc, _keys, _valids = _decl_args(mesh)
    cap = 1024
    fn = _unwrap(_carry_fn(mesh, "inner", cap, False))
    cat = S((w * 2 * cap,), np.int32)
    return jax.make_jaxpr(fn)(vc, vc, cat, cat)


def _packed_decl_spec():
    # two non-null int32 lane columns: exercises window slice + key unpack
    # + payload carry without int64 lane reconstruction (which widens
    # i32→i64 by design and would trip JX203 in the trace)
    return lanes.plan_lanes(("int32", "int32"), (False, False))


def _trace_packed_count(mesh):
    w, S, vc, _keys, _valids = _decl_args(mesh)
    spec = _packed_decl_spec()
    cap = 512
    layout = joink.payload_layout(spec, spec, (0,), ("int32",), (False,),
                                  (False,), False)
    fn = _unwrap(_packed_count_fn(mesh, "inner", (False,), (False,), spec,
                                  spec, layout, (0,), (0,), cap, cap, 1, 1,
                                  False))
    st = S((w,), np.int32)
    mat = S((w * 1024, spec.n_lanes), np.uint32)
    return jax.make_jaxpr(fn)(vc, vc, st, st, mat, mat)


def _trace_packed_materialize(mesh):
    w, S, vc, _keys, _valids = _decl_args(mesh)
    spec = _packed_decl_spec()
    cap = 512
    plan = (("l", 0, False), ("l", 1, False), ("r", 1, False))
    fn = _unwrap(_packed_materialize_fn(mesh, "inner", 1024, cap, cap,
                                        plan, spec, spec,
                                        joink.PayloadLayout(), 1, 1))
    carry = tuple(S((w * 2 * cap,), np.int32) for _ in range(6))
    st = S((w,), np.int32)
    mat = S((w * 1024, spec.n_lanes), np.uint32)
    return jax.make_jaxpr(fn)(carry, (), st, st, mat, mat)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._semi_flag_fn", _trace_semi_flag,
                tags=("join",))
# _count_fn's static key spans (how x narrow x lane-spec x liveness x
# slim) — a combinatorially larger legitimate program family than the
# capacity-keyed builders, so its session budget is wider
declare_builder(f"{__name__}._count_fn", _trace_count, tags=("join",),
                retrace_budget=128)
declare_builder(f"{__name__}._carry_fn", _trace_carry, tags=("join",))
# the packed-window programs span the same (how x narrow x lane-spec x
# liveness x slim) static family as _count_fn PLUS the per-range capacity
# pair — same widened session budget
declare_builder(f"{__name__}._packed_count_fn", _trace_packed_count,
                tags=("join", "pipeline"), retrace_budget=128)
declare_builder(f"{__name__}._packed_materialize_fn",
                _trace_packed_materialize, tags=("join", "pipeline"),
                retrace_budget=128)
