"""Adaptive skew-split plan facade — THE one place split-set construction
and salt assignment happen (lint rule TS115, docs/skew.md).

ROADMAP item 2 / SURVEY §7 hard-part 4: a Zipf-skewed key column under
plain hash partitioning lands each heavy key whole on one rank, bounding
the whole mesh by its hottest chip.  This module builds the remedy as a
deterministic, rank-coherent PLAN:

1. **Detect** (pack time): the sort-splitter sampling machinery
   (:func:`cylon_tpu.relational.common.sample_key_rows` — evenly spaced
   per-shard positions, shard-weighted) feeds the weighted Misra-Gries
   sketch (:mod:`cylon_tpu.obs.sketch`); key-hash classes whose
   owner's projected load ``1 + share * (W - 1)`` passes ``2 - 1/W``
   (:func:`split_rule`) become candidate heavy keys, each named
   by the FULL sampled key tuple (values + validity bits) so every
   later predicate runs in sort-OPERAND space (``pack.key_operands`` +
   ``rows_cmp_splitters``) — equality and order agree bit-for-bit with
   the join sort itself (float canonicalization, null flags and all);
   a hash collision merely leaves the colliding second key on the
   ordinary hash route.

2. **Plan**: each heavy key gets a CONTIGUOUS rank group anchored at its
   hash-home rank (``ops/hashing.partition_of`` — where plain hashing
   would have sent it), fan-out ``ceil(share * W * FANOUT_FACTOR)``
   clamped to [2, W] and to the key's EXACT row count.  The salt is the
   row's within-key arrival index STRIDED over the group — global row
   ``j`` of the key lands on member ``j mod fanout`` — an
   ORDER-PRESERVING sub-partition (each member's rows are a fixed-stride
   subsequence of the key's rows in global (source rank, source
   position) order, so the unsplit position of every row stays
   closed-form), which is what makes the stitched output bit- and
   order-equal to the unsplit hash plan; a random salt would balance
   equally well but scramble the merge order forever.  Strided (not
   contiguous-chunk) assignment also keeps the exchange's
   per-(src,dst) traffic cells uniform: every SOURCE's heavy rows
   spread over the whole group instead of one source's block landing on
   one member, so the padded exchange stays single-round and the comm
   matrix flat (the measured 2× exchange cost of chunked salting).
   Per-member row counts equal ``repart.even_partition_counts`` (the
   first ``n mod fanout`` members take the remainder) — the stitch's
   accounting rides the same host math either way.

3. **Vote**: the canonical plan hash rides the PR 3 consensus wire
   (:func:`cylon_tpu.exec.recovery.skew_plan_consensus`, a
   ``Code.SkewPlan`` vote) so the recovery ladder, checkpoints and
   elastic resume all see ONE plan before any split collective runs.

4. **Stitch** (after the local join): every output row's position in the
   UNSPLIT plan's global row order is computed from host-known plan
   scalars plus K operand comparisons per row, and
   ``repart.place_by_global_pos`` redistributes onto an even
   order-preserving layout — the output is bit-equal and order-equal to
   the unsplit hash plan with BALANCED shards (the unsplit plan would
   have concentrated the heavy key's entire output on its home rank).

The unarmed path (``CYLON_TPU_SKEW_SPLIT=0``, or no key above the
cutoff) adds zero collectives, zero votes and zero extra exchanges —
detection is one pure-local sample program + one host pull, exactly the
pre-existing heavy-key probe.
"""

from __future__ import annotations

import hashlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..ops import pack
from ..status import ExecutionError
from ..utils.cache import jit, program_cache
from ..utils.host import host_array
from .common import REP, ROW, fits_int32, live_mask

shard_map = jax.shard_map

__all__ = ["SkewPlan", "StitchState", "consume_unstitched", "detect",
           "heavy_counts", "heavy_flag", "finalize_or_none", "adopt",
           "split_exchange", "stitch_join_output", "last_plan",
           "record_plan", "combine_heavy_partials"]

#: thread-local record of the most recently VOTED plan (a driver's JSON
#: detail and chaos_soak's same-plan-after-recovery assertions read it)
_TLS = threading.local()


def record_plan(plan) -> None:
    _TLS.last = plan


def last_plan():
    """The most recently voted :class:`SkewPlan` on this thread (None
    when the last eligible join ran unsplit)."""
    return getattr(_TLS, "last", None)


class StitchState:
    """The skew route's deferred-merge handle (DeferredTable.op_state):
    the SPLIT-layout join output plus everything the stitch needs to
    rebuild the unsplit plan's global row order on demand.

    The stitch is a full extra pass over the output (position programs +
    one order-preserving exchange + per-dest reorder) — but row ORDER
    and PLACEMENT are unobservable through an aggregation, so a groupby
    consumer takes ``pre`` directly (:func:`consume_unstitched`) and the
    merge exchange never runs — the PR 2 deferred-consumption discipline
    applied to the stitch.  Any other access (to_pandas, sort, a second
    join, ...) materializes through the stitch thunk and sees the exact
    bit- and order-equal table (docs/skew.md)."""

    __slots__ = ("pre", "plan", "how", "un_counts", "key_out_names")

    def __init__(self, pre: Table, plan, how: str, un_counts,
                 key_out_names):
        self.pre = pre
        self.plan = plan
        self.how = how
        self.un_counts = un_counts
        self.key_out_names = tuple(key_out_names)


def consume_unstitched(table, include_deferred: bool = False):
    """Hand an order-insensitive consumer (relational/groupby.py) the
    PRE-stitch table when ``table`` is a stitch-deferred skew join:
    aggregation output is a function of the row MULTISET only (key
    placement is re-derived by the groupby's own combine shuffle), so
    skipping the stitch changes nothing observable while saving a full
    pass over the join output.  Returns ``table`` unchanged otherwise.

    ``include_deferred=True`` (called AFTER the fused pushdown declined
    — relational/groupby._groupby_aggregate_impl) additionally handles a
    still-deferred skew JOIN (fused.JoinState with a plan): the state's
    ``pre_thunk`` materializes the SPLIT-layout output without the
    stitch, so a groupby the fused kernel cannot serve (min/max/
    quantile/...) still skips the merge exchange."""
    st = getattr(table, "op_state", None)
    if isinstance(st, StitchState):
        from ..obs import plan as _plan
        from ..utils import timing
        _plan.annotate(skew_stitch_elided=True)
        timing.bump("skew.stitch_elided")
        return st.pre
    if include_deferred and not getattr(table, "materialized", True):
        pre_thunk = getattr(st, "pre_thunk", None)
        if getattr(st, "skew_plan", None) is not None \
                and pre_thunk is not None:
            from ..obs import plan as _plan
            from ..utils import timing
            _plan.annotate(skew_stitch_elided=True)
            timing.bump("skew.stitch_elided")
            return pre_thunk()
    return table


# ---------------------------------------------------------------------------
# the plan object
# ---------------------------------------------------------------------------

class SkewPlan:
    """The split decision for one join: K heavy key tuples with their
    contiguous rank groups and order-preserving chunk (salt) bounds.
    Built in two steps: :func:`detect` fills the sampled estimate,
    :meth:`finalize` replaces it with EXACT counts (and drops keys the
    replication guard rejects) before the plan hash is voted."""

    __slots__ = ("world", "key_names", "values", "valids", "hashes",
                 "shares", "home", "start", "fanout", "n_probe", "n_build",
                 "chunk", "src_off", "lt", "_hash")

    def __init__(self, world: int, key_names: tuple, values: list,
                 valids: list, hashes: np.ndarray, shares: np.ndarray,
                 home: np.ndarray, fanout: np.ndarray):
        self.world = int(world)
        self.key_names = tuple(key_names)
        self.values = values          # per key column: (K,) value array
        self.valids = valids          # per key column: (K,) bool array
        self.hashes = hashes          # (K,) uint32 routing hashes
        self.shares = shares          # (K,) estimated probe share
        self.home = home              # (K,) int32 hash-home rank
        self.start = home.copy()      # contiguous group anchored at home
        self.fanout = fanout          # (K,) int32 (estimate until finalize)
        self.n_probe = None           # (K,) exact probe rows (finalize)
        self.n_build = None           # (K,) exact build rows (finalize)
        self.chunk = None             # (K, W) per-member chunk rows
        self.src_off = None           # (W, K) within-key source offsets
        self.lt = None                # (K, K) operand order: lt[i,j]=ti<tj
        self._hash = None

    def __len__(self) -> int:
        return len(self.hashes)

    def _take(self, keep: np.ndarray) -> None:
        self.values = [v[keep] for v in self.values]
        self.valids = [v[keep] for v in self.valids]
        for name in ("hashes", "shares", "home", "start", "fanout"):
            setattr(self, name, getattr(self, name)[keep])

    def finalize(self, probe_wk: np.ndarray, ltmat: np.ndarray,
                 build_wk: np.ndarray, build_total: int) -> bool:
        """Swap the sampled estimate for EXACT per-source counts, clamp
        fan-outs, apply the per-key replication guard, and derive the
        salt (chunk) bounds.  Returns False when nothing is left to
        split.  Pure host arithmetic on replicated sidecars — identical
        on every rank by construction."""
        from .repart import even_partition_counts
        w = self.world
        n_probe = probe_wk.sum(axis=0).astype(np.int64)
        n_build = build_wk.sum(axis=0).astype(np.int64)
        # replication guard: duplicate-broadcasting a key whose BUILD
        # side is itself huge recreates the blow-up the split avoids
        guard = (n_build > config.SKEW_GUARD_ROWS) \
            & (n_build * w > config.SKEW_GUARD_RATIO * max(build_total, 1))
        keep = (n_probe > 0) & ~guard
        if not keep.any():
            return False
        self._take(keep)
        probe_wk = probe_wk[:, keep]
        ltmat = ltmat[keep][:, keep]
        n_probe, n_build = n_probe[keep], n_build[keep]
        self.n_probe, self.n_build, self.lt = n_probe, n_build, ltmat
        self.fanout = np.minimum(
            np.minimum(self.fanout.astype(np.int64), n_probe),
            w).astype(np.int32)
        self.fanout = np.maximum(self.fanout, 1).astype(np.int32)
        k = len(self.hashes)
        self.chunk = np.zeros((k, w), np.int64)
        for i in range(k):
            f = int(self.fanout[i])
            self.chunk[i, :f] = even_partition_counts(int(n_probe[i]), f)
        self.src_off = np.concatenate(
            [np.zeros((1, k), np.int64),
             np.cumsum(probe_wk, axis=0)[:-1].astype(np.int64)])
        self._hash = None
        return True

    # -- identity ---------------------------------------------------------
    def plan_hash(self) -> int:
        """Canonical 64-bit plan identity: every field that shapes the
        split's collective sequence feeds a sha256.  Deterministic given
        the (allgathered) detection inputs, so a recovery-ladder retry
        re-votes the identical hash — the chaos ``--skew`` contract."""
        if self._hash is None:
            h = hashlib.sha256()
            h.update(repr((self.world, self.key_names,
                           tuple(str(v.dtype) for v in self.values))
                          ).encode())
            for v in self.values + self.valids:
                h.update(np.ascontiguousarray(v).tobytes())
            for a in (self.hashes, self.home, self.start, self.fanout,
                      self.n_probe, self.n_build, self.chunk):
                h.update(np.ascontiguousarray(a).tobytes())
            self._hash = int.from_bytes(h.digest()[:8], "big")
        return self._hash

    def summary(self) -> dict:
        """The JSON-friendly decision record (bench detail, EXPLAIN)."""
        return {
            "keys": int(len(self.hashes)),
            "fanout": [int(f) for f in self.fanout],
            "home": [int(d) for d in self.home],
            "share_est": [round(float(s), 4) for s in self.shares],
            "rows_probe": [int(n) for n in self.n_probe]
            if self.n_probe is not None else None,
            "rows_build": [int(n) for n in self.n_build]
            if self.n_build is not None else None,
            "plan_hash": format(self.plan_hash(), "016x"),
        }

    # -- operand-space statics -------------------------------------------
    def operand_statics(self, cols) -> tuple:
        """(need_nf, narrow) per key column for operand comparisons
        between ``cols``' rows and this plan's tuples — null flags
        whenever either side can hold nulls, narrow lanes only when BOTH
        the column's host-known bounds AND this plan's tuple values fit
        int32.  The tuples are drawn from the PROBE table, but ``cols``
        may be the BUILD side (or the join output): a build column whose
        bounds fit int32 compared against a wide probe tuple must stay
        on the (hi, lo) pair, or the tuple's truncation aliases it onto
        an unrelated narrow key (the cross-table rule of
        ``common.narrow32_flags``, applied one side at a time)."""
        need_nf = tuple((c.validity is not None) or bool((~tv).any())
                        for c, tv in zip(cols, self.valids))
        narrow = tuple(fits_int32(c) and _tuple_fits_i32(v, tv)
                       for c, v, tv in zip(cols, self.values, self.valids))
        return need_nf, narrow

    def tuple_args(self) -> tuple:
        """The replicated device-constant inputs naming the K tuples."""
        return tuple(self.values) + tuple(self.valids)


def _tuple_fits_i32(v: np.ndarray, tv: np.ndarray) -> bool:
    """Host-known: every VALID entry of this 64-bit integer tuple-value
    array fits int32 (the per-tuple half of :meth:`SkewPlan.
    operand_statics`' narrow-lane rule; null slots may hold garbage)."""
    if v.dtype.itemsize != 8 or v.dtype.kind not in ("i", "u"):
        return False
    live = v[tv]
    if live.size == 0:
        return True
    return int(live.min()) >= -(1 << 31) \
        and int(live.max()) <= (1 << 31) - 1


def _cmp_args(table: Table, key_names) -> tuple:
    cols = [table.column(n) for n in key_names]
    cap = cols[0].data.shape[0]
    datas = tuple(c.data for c in cols)
    valids = tuple(c.validity if c.validity is not None
                   else np.ones(cap, bool) for c in cols)
    return cols, datas, valids


def _tuple_ops(tup, nkeys: int, need_nf: tuple, narrow: tuple):
    """KeyOps of the K heavy tuples from the replicated constants."""
    tdatas = list(tup[:nkeys])
    tvalids = list(tup[nkeys:])
    return pack.key_operands(tdatas, tvalids, need_null_flags=need_nf,
                             narrow32=narrow)


def _row_ops(datas, valids, need_nf: tuple, narrow: tuple):
    return pack.key_operands(list(datas), list(valids),
                             need_null_flags=need_nf, narrow32=narrow)


# ---------------------------------------------------------------------------
# detection — MG sketch over the splitter sample
# ---------------------------------------------------------------------------

def detect(probe: Table, key_names, env) -> SkewPlan | None:
    """Pack-time heavy-hitter detection on the (promoted) probe side.
    Returns an un-finalized :class:`SkewPlan` or None.  One pure-local
    sample program + one (allgathered) host pull; rank-uniform by
    construction."""
    from ..obs.sketch import MisraGries
    from ..ops.hashing import partition_of
    from ..utils import timing
    from .common import sample_key_rows

    # every eligible join's decision sequence starts here: clear the
    # thread-local record so last_plan() never reports a PREVIOUS join's
    # plan when this one runs unsplit (adopt() re-records on a vote)
    record_plan(None)
    w = env.world_size
    if not config.SKEW_SPLIT or w <= 1:
        return None
    total = int(probe.valid_counts.sum())
    if total < w * 64:   # too small to be worth a split
        return None
    sampled = sample_key_rows(probe, list(key_names))
    if sampled is None:
        return None
    values, valids, hashes, weights, _total = sampled
    _DETECT_JOINS.inc()
    with timing.span("host.skew_detect"):
        # the detector's verdict on the pulled sample: pure host work
        mg = MisraGries(k=max(4 * config.SKEW_MAX_KEYS, 8))
        mg.update(hashes, weights)
        est = mg.shares()
        _annotate_rule(est, w)
        heavy = [(hv, sh) for hv, sh, _e in est if split_rule(sh, w)[2]]
    if not heavy:
        return None
    heavy = heavy[:config.SKEW_MAX_KEYS]
    idx, shares = [], []
    for hv, sh in heavy:
        pos = np.nonzero(hashes == hv)[0]
        if pos.size == 0:   # MG value decayed out of the sample: skip
            continue
        idx.append(int(pos[0]))
        shares.append(float(sh))
    if not idx:
        return None
    idx = np.asarray(idx, np.int64)
    shares = np.asarray(shares, np.float64)
    hv = hashes[idx].astype(np.uint32)
    home = np.asarray([partition_of(int(h), w) for h in hv], np.int32)
    fanout = np.clip(np.ceil(shares * w * config.SKEW_FANOUT_FACTOR), 2,
                     w).astype(np.int32)
    return SkewPlan(w, tuple(key_names),
                    [np.ascontiguousarray(v[idx]) for v in values],
                    [np.ascontiguousarray(v[idx]) for v in valids],
                    hv, shares, home, fanout)


def adopt(plan: SkewPlan, env) -> None:
    """Vote the finalized plan's canonical hash over the PR 3 consensus
    wire (:func:`cylon_tpu.exec.recovery.skew_plan_consensus`,
    ``Code.SkewPlan``) and record it for the bench/chaos assertions.
    Must run BEFORE the split's first collective is dispatched — a rank
    whose detection inputs diverged raises typed here instead of
    entering a different exchange plan alone."""
    from ..exec.recovery import skew_plan_consensus
    from ..obs import metrics as _metrics
    from ..utils import timing
    skew_plan_consensus(env.mesh, plan.plan_hash())
    record_plan(plan)
    timing.bump("join.skew_split")
    _metrics.counter("skew_split_joins").inc()
    _metrics.counter("skew_split_keys").inc(len(plan))


def split_exchange(probe: Table, probe_on, build: Table, build_on,
                   plan: SkewPlan):
    """Run the split's exchanges per the VOTED plan (docs/skew.md):

    * **probe**: one exchange with the salted order-preserving targets —
      light rows hash to their home shard exactly like the unsplit plan,
      each heavy key's rows land as fixed-stride global-order
      subsequences on its rank group
      (:func:`parallel.shuffle.skew_split_targets`);
    * **build**: light rows hash-shuffle; heavy rows duplicate-broadcast
      (allgather — the existing broadcast-join transport) then filter to
      the ranks serving the key's group, appended AFTER the light block
      so every shard's per-key row order stays the global (src, pos)
      order the unsplit hash exchange would have delivered — the
      bit-equality contract's build half.

    Returns ``(probe_out, build_out)``."""
    from ..parallel import shuffle as shf
    from ..parallel.collectives import allgather_table
    from .repart import (concat_tables, exchange_by_targets, filter_table,
                         shuffle_table)

    env = probe.env
    cols, datas, valids = _cmp_args(probe, probe_on)
    need_nf, narrow = plan.operand_statics(cols)
    tgt = shf.skew_split_targets(
        env.mesh, datas, valids, probe.valid_counts, len(plan), need_nf,
        narrow, plan.tuple_args(), plan.src_off, plan.fanout, plan.start)
    counts = shf.count_targets(env.mesh, tgt)
    probe_out = exchange_by_targets(probe, tgt, counts)

    flag = heavy_flag(build, build_on, plan)
    build_light = filter_table(build, ~flag)
    build_heavy = filter_table(build, flag)
    bh_all = allgather_table(build_heavy)
    keep = heavy_flag(bh_all, build_on, plan,
                      member=group_member_mask(plan))
    bh_mine = filter_table(bh_all, keep)
    build_out = concat_tables([shuffle_table(build_light, build_on),
                               bh_mine])
    return probe_out, build_out


def finalize_or_none(plan: SkewPlan, probe: Table, probe_on,
                     build: Table, build_on) -> SkewPlan | None:
    """Exact-count finalization: per-source probe counts + operand order
    matrix + build counts, then :meth:`SkewPlan.finalize`.  Returns the
    finalized plan or None (nothing worth splitting)."""
    probe_wk, ltmat = heavy_counts(probe, probe_on, plan, with_lt=True)
    build_wk, _ = heavy_counts(build, build_on, plan)
    if not plan.finalize(probe_wk, ltmat, build_wk,
                         int(build.valid_counts.sum())):
        return None
    return plan


# ---------------------------------------------------------------------------
# exact per-source counts + operand order (one pure-local program)
# ---------------------------------------------------------------------------

@program_cache()
def _heavy_count_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                    narrow: tuple):
    def per_shard(vc, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:]
        cap = datas[0].shape[0]
        mask = live_mask(vc, cap)
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        _gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        counts = jnp.sum(eq & mask[:, None], axis=0,
                         dtype=jnp.int32).reshape(1, k)
        # operand order among the tuples themselves: lt[i, j] = t_i < t_j
        gtt, _eqt = pack.rows_cmp_splitters(ko_t, ko_t.ops)
        return counts, gtt.T

    specs = (REP,) + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=(ROW, REP)))


def heavy_counts(table: Table, key_names, plan: SkewPlan,
                 with_lt: bool = False):
    """(W, K) exact per-source row counts of each heavy tuple in
    ``table``, plus (with_lt) the (K, K) operand-order matrix."""
    cols, datas, valids = _cmp_args(table, key_names)
    need_nf, narrow = plan.operand_statics(cols)
    fn = _heavy_count_fn(table.env.mesh, len(plan), len(cols), need_nf,
                         narrow)
    counts_d, lt_d = fn(np.asarray(table.valid_counts, np.int32),
                        *datas, *valids, *plan.tuple_args())
    counts = host_array(counts_d).reshape(table.env.world_size, len(plan))
    return counts.astype(np.int64), (host_array(lt_d) if with_lt else None)


# ---------------------------------------------------------------------------
# membership flags (build-side split + group-scoped broadcast filter)
# ---------------------------------------------------------------------------

@program_cache()
def _heavy_member_flag_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                          narrow: tuple):
    """Per-row bool: the row's key equals SOME heavy tuple whose (K, W)
    member mask covers THIS rank.  All-ones mask ⇒ the plain split flag;
    the group mask ⇒ the duplicate-broadcast's group-scoped filter."""

    def per_shard(vc, member, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:]
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        mask = live_mask(vc, cap)
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        _gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        return jnp.any(eq & member[:, my][None, :], axis=1) & mask

    specs = (REP, REP) + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=ROW))


def heavy_flag(table: Table, key_names, plan: SkewPlan, member=None):
    """Device bool flags: row's key is heavy (``member=None``) or heavy
    AND this rank belongs to the key's group (``member`` a (K, W) bool
    mask — :func:`group_member_mask`)."""
    cols, datas, valids = _cmp_args(table, key_names)
    need_nf, narrow = plan.operand_statics(cols)
    if member is None:
        member = np.ones((len(plan), plan.world), bool)
    fn = _heavy_member_flag_fn(table.env.mesh, len(plan), len(cols),
                               need_nf, narrow)
    return fn(np.asarray(table.valid_counts, np.int32), member,
              *datas, *valids, *plan.tuple_args())


def group_member_mask(plan: SkewPlan) -> np.ndarray:
    """(K, W) bool: rank w serves key k's group (contiguous mod W from
    the key's home anchor)."""
    k, w = len(plan), plan.world
    m = np.zeros((k, w), bool)
    for i in range(k):
        for j in range(int(plan.fanout[i])):
            m[i, (int(plan.start[i]) + j) % w] = True
    return m


# ---------------------------------------------------------------------------
# fused-pushdown heavy-partial combine (relational/fused.py)
# ---------------------------------------------------------------------------

@program_cache()
def _heavy_partial_sum_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                          narrow: tuple, nvals: int):
    """(W, K) per-source partial values of each heavy key's GROUP-SPACE
    result row (one matching row per member shard, zeros elsewhere) —
    the gather half of :func:`combine_heavy_partials`.  Pure-local."""

    def per_shard(vc, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:4 * nkeys]
        vals = args[4 * nkeys:]
        cap = datas[0].shape[0]
        mask = live_mask(vc, cap)
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        _gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        eq = eq & mask[:, None]
        return tuple(
            jnp.sum(jnp.where(eq, v[:, None], jnp.zeros((), v.dtype)),
                    axis=0).reshape(1, k)
            for v in vals)

    specs = (REP,) + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys) \
        + (ROW,) * nvals
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=(ROW,) * nvals))


@program_cache()
def _patch_heavy_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                    narrow: tuple, nvals: int):
    """Patch half of :func:`combine_heavy_partials`: heavy rows on the
    key's HOME rank take the combined value; heavy rows on the other
    group members are flagged for dropping.  Light rows pass through.
    Pure-local."""

    def per_shard(vc, home, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:4 * nkeys]
        vals = args[4 * nkeys:4 * nkeys + nvals]
        combined = args[4 * nkeys + nvals:]
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        mask = live_mask(vc, cap)
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        _gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        eq = eq & mask[:, None]
        heavy = jnp.any(eq, axis=1)
        kidx = jnp.argmax(eq, axis=1).astype(jnp.int32)
        is_home = heavy & (home[kidx] == my)
        keep = mask & (~heavy | is_home)
        outs = tuple(jnp.where(is_home, c[kidx], v)
                     for v, c in zip(vals, combined))
        return outs + (keep,)

    specs = (REP, REP) + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys) \
        + (ROW,) * nvals + (REP,) * nvals
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=(ROW,) * (nvals + 1)))


def combine_heavy_partials(out: Table, by, res_names, plan: SkewPlan):
    """Merge a fused join→groupby pushdown's heavy-key PARTIAL rows into
    the unsplit plan's single row per key (relational/fused.py).

    Under a skew plan each heavy key's probe rows span a rank group, so
    the fused kernel's group-space result holds one partial row per
    member — and for the pushdown-eligible-under-skew ops (sum/count/
    sumsq, whose finalized value is ADDITIVE in the probe chunks:
    ``S_chunk·R`` over members sums to ``S_g·R``) the combine is: sum
    each heavy key's member rows, write the total onto the key's HOME
    rank's row, drop the other members' rows.  The surviving per-shard
    group sets, row order and values are then exactly the unsplit fused
    plan's (the home rank is where plain hashing co-located the key),
    which is the skew route's bit-equality contract applied to the
    aggregated output — exact for integer accumulators; a FLOAT sum
    re-associates (per-chunk partials folded in rank order vs one
    shard's single pass) and may differ from the unsplit run in
    low-order bits, deterministically (docs/skew.md "Scope of the
    aggregated-output equality").  Two tiny pure-local programs +
    one (W, K)-sidecar host pull; the combined constants are identical
    on every rank because the pull allgathers."""
    from ..core.column import Column
    from ..obs import plan as _plan
    from ..utils import timing
    from .repart import filter_table

    env = out.env
    cols, datas, valids = _cmp_args(out, by)
    need_nf, narrow = plan.operand_statics(cols)
    vals = [out.column(n) for n in res_names]
    vdatas = tuple(c.data for c in vals)
    vc32 = np.asarray(out.valid_counts, np.int32)
    k, nk, w = len(plan), len(cols), plan.world
    with timing.region("skew.partial_combine"):
        parts = _heavy_partial_sum_fn(env.mesh, k, nk, need_nf, narrow,
                                      len(vals))(
            vc32, *datas, *valids, *plan.tuple_args(), *vdatas)
        # rank-order host fold — deterministic and rank-uniform
        combined = [np.ascontiguousarray(
            host_array(p).reshape(w, k).sum(axis=0)) for p in parts]
        outs = _patch_heavy_fn(env.mesh, k, nk, need_nf, narrow,
                               len(vals))(
            vc32, plan.home.astype(np.int32), *datas, *valids,
            *plan.tuple_args(), *vdatas, *combined)
        new_datas, keep = outs[:-1], outs[-1]
        newcols = dict(out.columns)
        for n, d in zip(res_names, new_datas):
            c = out.columns[n]
            # bounds dropped: the combined totals may exceed the partial
            # rows' recorded range
            newcols[n] = Column(d, c.type, c.validity, c.dictionary)
        patched = Table(newcols, env,
                        np.asarray(out.valid_counts, np.int64))
        res = filter_table(patched, keep)
    res.grouped_by = tuple(by)
    _plan.annotate(skew_partials_combined=k)
    timing.bump("skew.partial_combine")
    return res


# ---------------------------------------------------------------------------
# stitch: O-position of every output row in the UNSPLIT plan's order
# ---------------------------------------------------------------------------

@program_cache()
def _out_ltcount_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                    narrow: tuple):
    """(W, K) counts of MAIN-zone output rows whose key sorts strictly
    after tuple k ... transposed perspective: rows with t_k < rowkey."""

    def per_shard(vc, main, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:]
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        zone_a = jnp.arange(cap, dtype=jnp.int32) < main[my]
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        gt, _eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        return jnp.sum(gt & zone_a[:, None], axis=0,
                       dtype=jnp.int32).reshape(1, k)

    specs = (REP, REP) + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=ROW))


@program_cache()
def _stitch_pos_fn(mesh: Mesh, k: int, nkeys: int, need_nf: tuple,
                   narrow: tuple):
    """Per-row UNSPLIT-plan global position (int64) of the split join's
    output rows — the merge half of the skew route's bit/order-equality
    contract (module docstring, docs/skew.md):

    * light main row at shard r, slot p:
        ``segoff[r] + p + Σ_{t_j < key} coefA[r, j]``
      (coefA removes the heavy slices sorting before it and inserts the
      full heavy blocks HOMED at r that sort before it);
    * heavy row of key j: the member holds probe rows ``m, m+f, m+2f...``
      of the key (strided salt), each contributing ``per_row`` output
      rows, so output row ``within_run`` (probe ordinal ``i = within //
      per_row``, build ordinal ``b = within mod per_row``) sits at
        ``coefH[r, j] + i · (fanout_j · per_row_j) + b``
      (coefH = the key's global block base + this member's salt ordinal
      times ``per_row``; within_run from one run-boundary scan);
    * appended unmatched-right row (outer zone B):
        ``segoff[r] + seg_a[r] + (p - main[r])``.

    Padding slots get the ``total`` sentinel (they sort last and are
    dropped by the placement's valid counts)."""

    def per_shard(vc, main, segoff, seg_a, coef_a, coef_h, per_row, fan,
                  total, *args):
        datas = args[:nkeys]
        valids = args[nkeys:2 * nkeys]
        tup = args[2 * nkeys:]
        cap = datas[0].shape[0]
        my = jax.lax.axis_index(ROW_AXIS)
        p32 = jnp.arange(cap, dtype=jnp.int32)
        # born-wide int64 twin for position arithmetic (JX203): global
        # output positions legitimately exceed int32 at target scale
        p64 = jnp.arange(cap, dtype=jnp.int64)
        live = p32 < vc[my]
        zone_b = live & (p32 >= main[my])
        ko_t = _tuple_ops(tup, nkeys, need_nf, narrow)
        ko_r = _row_ops(datas, valids, need_nf, narrow)
        gt, eq = pack.rows_cmp_splitters(ko_r, ko_t.ops)
        heavy = jnp.any(eq, axis=1) & live & ~zone_b
        kidx = jnp.argmax(eq, axis=1).astype(jnp.int32)
        # run boundaries over the shard's (key-sorted) main zone: the
        # heavy key's rows form one contiguous run; within_run is the
        # row's offset inside it
        neq = jnp.zeros(cap, bool)
        for op, kind in zip(ko_r.ops, ko_r.kinds):
            d = pack.op_neq(op[1:], op[:-1], kind)
            neq = neq | jnp.concatenate([jnp.ones(1, bool), d])
        run_start = jax.lax.cummax(jnp.where(neq, p64, jnp.int64(0)))
        within = p64 - run_start
        # light: p + Σ_j [t_j < key] * coefA[my, j]
        corr = jnp.sum(jnp.where(gt, coef_a[my][None, :],
                                 jnp.int64(0)), axis=1)
        pos_light = segoff[my] + p64 + corr
        # pr=1 guard: a key with zero build rows emits no heavy output
        # rows at all (kidx then points at it only from non-heavy lanes
        # whose pos_heavy is discarded), but the division must not trap
        pr = jnp.maximum(per_row[kidx], jnp.int64(1))
        i = within // pr
        b = within - i * pr
        pos_heavy = coef_h[my, kidx] + i * (fan[kidx] * pr) + b
        pos_b = segoff[my] + seg_a[my] + (p64 - main[my])
        pos = jnp.where(zone_b, pos_b,
                        jnp.where(heavy, pos_heavy, pos_light))
        return jnp.where(live, pos, total)

    specs = (REP,) * 9 + (ROW,) * (2 * nkeys) + (REP,) * (2 * nkeys)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=specs,
                             out_specs=ROW))


def stitch_join_output(out: Table, key_out_names, plan: SkewPlan,
                       how: str, un_counts: np.ndarray | None) -> Table:
    """Merge the split join's output back into the UNSPLIT hash plan's
    global row order (bit- and order-equal), redistributed onto an even
    order-preserving layout via ``repart.place_by_global_pos``.

    ``key_out_names``: the output columns holding the PROBE side's key
    values.  ``un_counts``: per-shard appended unmatched-right counts
    (outer joins; None ⇒ zeros)."""
    from ..utils import timing
    from .repart import place_by_global_pos

    env = out.env
    w, k = plan.world, len(plan)
    out_counts = np.asarray(out.valid_counts, np.int64)
    total = int(out_counts.sum())
    un = np.zeros(w, np.int64) if un_counts is None \
        else np.asarray(un_counts, np.int64)
    main = out_counts - un

    per_row = plan.n_build if how == "inner" \
        else np.maximum(plan.n_build, 1)
    out_k = (plan.n_probe * per_row).astype(np.int64)      # (K,) blocks
    # slice_size[r, j]: heavy output rows of key j at member shard r
    ordinal = (np.arange(w)[:, None] - plan.start[None, :]) % w   # (W, K)
    in_group = ordinal < plan.fanout[None, :]
    chunk_rows = np.where(
        in_group, plan.chunk[np.arange(k)[None, :],
                             np.clip(ordinal, 0, w - 1)], 0)
    slice_size = chunk_rows * per_row[None, :]
    # strided salt: member ordinal m holds probe rows m, m+f, m+2f... of
    # the key, so its FIRST output row sits at block offset m * per_row
    # (the stride itself is applied per row in _stitch_pos_fn)
    slice_off = np.where(in_group, np.clip(ordinal, 0, w - 1), 0) \
        * per_row[None, :]

    light_main = main - slice_size.sum(axis=1)
    home_mat = (plan.home[None, :] == np.arange(w)[:, None])      # (W, K)
    seg = light_main + home_mat @ out_k + un                      # (W,)
    if int(seg.sum()) != total:
        raise ExecutionError(
            f"skew stitch accounting diverged: unsplit segments sum to "
            f"{int(seg.sum())} rows but the split output holds {total} — "
            "plan counts and join output disagree")
    segoff = np.concatenate([[0], np.cumsum(seg)[:-1]]).astype(np.int64)

    cols = [out.column(n) for n in key_out_names]
    need_nf, narrow = plan.operand_statics(cols)
    cap = cols[0].data.shape[0]
    datas = tuple(c.data for c in cols)
    valids = tuple(c.validity if c.validity is not None
                   else np.ones(cap, bool) for c in cols)
    vc32 = np.asarray(out_counts, np.int32)
    main32 = np.asarray(main, np.int32)
    with timing.region("skew.stitch_count"):
        jlt = _out_ltcount_fn(env.mesh, k, len(cols), need_nf, narrow)(
            vc32, main32, *datas, *valids, *plan.tuple_args())
        jlt = host_array(jlt).reshape(w, k).astype(np.int64)
    # light rows at the HOME shard sorting after key j's tuple (exclude
    # the other heavy keys' slices the joint count included: key j' at
    # shard d counts against tuple k iff t_k < t_j', i.e. lt[k, j'])
    light_lt = jlt - slice_size @ plan.lt.T.astype(np.int64)
    # the key's global block base in the UNSPLIT plan: its home segment's
    # offset + the light rows sorting BEFORE it there (light_main minus
    # the after-count — no light key ever equals a heavy tuple) + the
    # full blocks of heavy keys ALSO homed there that sort before it
    light_before = light_main[plan.home] - light_lt[plan.home,
                                                    np.arange(k)]
    block_base = (segoff[plan.home] + light_before
                  + ((plan.lt & (plan.home[:, None] == plan.home[None, :]))
                     .T @ out_k))
    coef_a = (-slice_size + home_mat * out_k[None, :]).astype(np.int64)
    coef_h = (block_base[None, :] + slice_off).astype(np.int64)

    with timing.region("skew.stitch_pos"):
        pos = _stitch_pos_fn(env.mesh, k, len(cols), need_nf, narrow)(
            vc32, main32, segoff, seg - un, coef_a, coef_h,
            per_row.astype(np.int64), plan.fanout.astype(np.int64),
            np.int64(total), *datas, *valids, *plan.tuple_args())
    with timing.region("skew.stitch_place"):
        stitched = place_by_global_pos(out, pos, total)
    from ..exec import integrity as _integrity
    if _integrity.armed():
        # armed audit (exec/integrity facade): the stitched table's
        # order-invariant fingerprint is voted rank-coherently — a
        # corrupted or mis-placed stitch surfaces typed at this stage
        # boundary instead of as a silently reordered answer downstream
        _integrity.audit_table(stitched, site="skew.stitch",
                               phase="post_stitch")
    return stitched


# ---------------------------------------------------------------------------
# trace-safety declarations: pure-local shard programs, no collective
# (the split's exchanges ride parallel/shuffle.py).  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _decl(mesh, k=2):
    w = int(mesh.devices.size)
    cap, S = 1024, jax.ShapeDtypeStruct
    vc = S((w,), np.int32)
    keys = (S((w * cap,), np.int64),)
    valids = (S((w * cap,), np.bool_),)
    tup = (S((k,), np.int64), S((k,), np.bool_))
    return w, cap, S, vc, keys, valids, tup


def _trace_heavy_count(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    fn = _unwrap(_heavy_count_fn(mesh, 2, 1, (True,), (False,)))
    return jax.make_jaxpr(fn)(vc, *keys, *valids, *tup)


def _trace_member_flag(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    fn = _unwrap(_heavy_member_flag_fn(mesh, 2, 1, (True,), (False,)))
    return jax.make_jaxpr(fn)(vc, S((2, w), np.bool_), *keys, *valids,
                              *tup)


def _trace_heavy_partial_sum(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    fn = _unwrap(_heavy_partial_sum_fn(mesh, 2, 1, (True,), (False,), 2))
    return jax.make_jaxpr(fn)(vc, *keys, *valids, *tup,
                              S((w * cap,), np.int64),
                              S((w * cap,), np.float64))


def _trace_patch_heavy(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    fn = _unwrap(_patch_heavy_fn(mesh, 2, 1, (True,), (False,), 2))
    return jax.make_jaxpr(fn)(vc, S((2,), np.int32), *keys, *valids, *tup,
                              S((w * cap,), np.int64),
                              S((w * cap,), np.float64),
                              S((2,), np.int64), S((2,), np.float64))


def _trace_out_ltcount(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    fn = _unwrap(_out_ltcount_fn(mesh, 2, 1, (True,), (False,)))
    return jax.make_jaxpr(fn)(vc, vc, *keys, *valids, *tup)


def _trace_stitch_pos(mesh):
    w, cap, S, vc, keys, valids, tup = _decl(mesh)
    i64 = np.int64
    fn = _unwrap(_stitch_pos_fn(mesh, 2, 1, (True,), (False,)))
    return jax.make_jaxpr(fn)(vc, vc, S((w,), i64), S((w,), i64),
                              S((w, 2), i64), S((w, 2), i64),
                              S((2,), i64), S((2,), i64),
                              S((), i64), *keys, *valids, *tup)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._heavy_count_fn", _trace_heavy_count,
                tags=("skew", "join"))
declare_builder(f"{__name__}._heavy_member_flag_fn", _trace_member_flag,
                tags=("skew", "join"))
declare_builder(f"{__name__}._heavy_partial_sum_fn",
                _trace_heavy_partial_sum, tags=("skew", "groupby"))
declare_builder(f"{__name__}._patch_heavy_fn", _trace_patch_heavy,
                tags=("skew", "groupby"))
declare_builder(f"{__name__}._out_ltcount_fn", _trace_out_ltcount,
                tags=("skew", "join"))
declare_builder(f"{__name__}._stitch_pos_fn", _trace_stitch_pos,
                tags=("skew", "join"))


# ---------------------------------------------------------------------------
# THE rule that decides a split (ISSUE 34, ROADMAP X13; docs/skew.md "The
# rule").  Host-side code of this file goes HERE, at its end: the programs
# above keep their line numbers (PERF.md, PR 30's lesson).
# ---------------------------------------------------------------------------

def split_rule(share: float, w: int) -> tuple:
    """``(owner_load, bound, split)`` for a key holding ``share`` of the
    probe rows on ``w`` chips - the one statement of the threshold, in the
    terms it protects.  Hash partitioning sends the key whole to one chip,
    which then holds its balanced ``1/w`` of the other rows plus the whole
    key: ``owner_load = 1 + share * (w - 1)`` times the balanced rows.
    Every whole-shard program after the exchange is compiled at the
    FULLEST chip's receive capacity, so that load is what the whole mesh
    pays.  The key is split when the load passes ``bound = 2 - 1/w``: the
    key BY ITSELF is then more than one chip's balanced rows (``share >
    1/w``; 1.75x at w = 4, 1.875x at w = 8).  The bound is no setting;
    docs/skew.md has the chip measurement behind it (PR 34: at a load of
    1.34 the split plan took twice the unsplit plan's time).
    :func:`detect` asks it of every sketched key, the legacy semi/anti
    spread (``relational/join._heavy_keys``) of its own estimate."""
    w = int(w)
    load = 1.0 + float(share) * (w - 1)
    bound = 2.0 - 1.0 / w
    return load, bound, load > bound


def _annotate_rule(est: list, w: int) -> None:
    """Tell the join's plan node what the rule saw of the hottest sketched
    key (the sketch lists heaviest first), so that an unsplit join says
    why.  A no-op outside ``obs.explain*``."""
    if not est:
        return
    from ..obs import plan as _plan
    share = float(est[0][1])
    load, bound, _split = split_rule(share, w)
    _plan.annotate(skew_top_share=round(share, 6),
                   skew_owner_load=round(load, 6),
                   skew_owner_load_bound=round(bound, 6))


#: registered at import, so that a snapshot shows all three whether or not
#: a join ever split: joins that ran the detector (took the sample), joins
#: that voted a plan, and the keys those plans split
from ..obs import metrics as _metrics  # noqa: E402

_DETECT_JOINS = _metrics.counter("skew_detect_joins")
_metrics.counter("skew_split_joins")
_metrics.counter("skew_split_keys")
