"""Local join kernel: single-sort merge + segmented-scan geometry.

TPU-native replacement for the reference's local join layer
(cpp/src/cylon/join/join.cpp:60 ``JoinTables`` dispatch, sort_join.cpp:66
``do_sorted_join``, hash_join.cpp:22-85).  The reference's default algorithm
is SORT (join_config.hpp:37); a pointer-chasing hash build/probe doesn't map
to XLA, so the sort path is *the* design here (SURVEY.md §7 hard-part 2),
engineered around the measured v5e cost model: ``lax.sort`` costs its
operand count (1.1-1.25 ns a row an operand: ledger, PRs 29-34), random
gathers are expensive (~20 ns/row/lane), segment reductions with large
segment counts are expensive — prefix scans are cheap.

  1. ``join_sort_state``: ONE stable sort of the concatenated (left ++
     right) packed key tuples (u32 lanes, :mod:`.pack`).  Stability makes
     left rows precede right rows within every equal-key run, so the sorted
     order itself encodes the merge.  What rides that sort beside the keys
     and the row index is said in ONE place, :class:`PayloadLayout`: the
     two sides' payload lanes share operands and a key column's lanes are
     read back from the sorted key operands, so every datum moves once.
  2. ``join_carry``: per-position geometry from *segmented scans* only
     (no segment reductions, no group-space gather; row liveness is a
     position compare against the live prefix, ``live_sides``):
     reverse segmented counts give every left row its group's right-count
     and the position where its matches start; forward counts give right
     rows their left-count (for right/outer emission).
  3. ``join_take``: output expansion — a scatter + ``cummax`` reconstructs
     "which emitting row owns output slot k" (offsets are strictly
     increasing over emitting rows), then ONE stacked (out, 4) meta gather +
     ONE 1-D gather produce the (l_take, r_take) index pairs.

Output size is data-dependent; callers run phase 1 (sort + carry + exact
count), pick a static pow2 capacity, then phase 2 — with the carry arrays
passed between the two compiled programs as device residents so the sort
and scans run once.

INNER / LEFT / RIGHT / FULL_OUTER all supported (join_config.hpp:25);
"right" emits from the right side over the same sorted state (left rows
lead every group, so right-row matches start at the group start).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.stages import stage, staged
from .pack import KeyOps, concat_keyops, key_operand_slots, neighbor_flags


class JoinCarry(NamedTuple):
    """Per-sorted-position state carried from count to materialize phase.
    All (n_l + n_r,) int32 device arrays."""
    offs: jax.Array    # exclusive prefix sum of eff (output offset)
    eff: jax.Array     # output rows this position emits
    cnt: jax.Array     # match count of the position's group (other side)
    mstart: jax.Array  # sorted position where this row's matches start
    idx_s: jax.Array   # concat-row index at this sorted position
    un: jax.Array      # outer only: 1 = unmatched right row (else zeros)


class PayloadLayout(NamedTuple):
    """What rides THE sort beside the key operands and ``idx`` - the one
    statement of the join's payload layout (static, hashable: it travels
    with the compiled programs beside ``lspec`` / ``rspec`` and in
    ``fused.JoinState``).  Two rules, both read off the specs:

    1. *The two sides share payload operands.*  Left rows are ``[0, n_l)``
       of the concat and right rows the rest, so physical payload ``j`` is
       ``concatenate([left lane, right lane])`` and the sort carries
       ``max`` of the two sides' lane counts where one operand a lane a
       side would be half zeros.  Every consumer reads a left lane only at
       left rows and a right lane only at right rows (``fused.value_of``
       masks by side, ``join_take`` gathers the emit side at the owning
       row and the match side at ``mpos`` under ``matched``), so after the
       sort left lane j and right lane j ARE the same array.
    2. *A key column's lanes are not sorted twice.*  Where a left output
       column is a join key whose lanes are bit-equal to its sort
       operands (:func:`payload_layout`), the lane stays out of the
       payloads and is the sorted key operand itself (on right rows that
       array holds the right row's key; nobody reads a left lane there).

    ``pl_s``, the tuple the count programs return and every consumer
    holds, is the PHYSICAL arrays: the sorted key operands named in
    ``kept_keys`` (as sorted: int32 / uint32), then the ``n_payloads``
    shared payload arrays.  :func:`payload_operands` builds the sort's
    payloads, :func:`payload_lanes` turns ``pl_s`` back into (left lanes,
    right lanes); nobody else slices it."""
    n_keys: int = 0    # key operands of the sort, liveness flag included
    nl: int = 0        # left lanes a consumer reads (0: the side does not ride)
    nr: int = 0        # right lanes
    alias: tuple = ()  # per left lane: the key operand that IS it, or -1

    @property
    def kept_keys(self) -> tuple:
        """Sort-operand indices of the sorted keys ``pl_s`` leads with."""
        return tuple(sorted({a for a in self.alias if a >= 0}))

    @property
    def riding(self) -> tuple:
        """Left lanes that ride as payload (not aliased to a key)."""
        return tuple(j for j, a in enumerate(self.alias) if a < 0)

    @property
    def n_payloads(self) -> int:
        return max(len(self.riding), self.nr)

    @property
    def n_arrays(self) -> int:
        """Length of ``pl_s``."""
        return len(self.kept_keys) + self.n_payloads

    @property
    def sort_operands(self) -> int:
        """Operands handed to the join's ``lax.sort``: keys, idx, payloads."""
        return self.n_keys + 1 + self.n_payloads


def payload_layout(lspec, rspec, key_cols: tuple, key_dtypes: tuple,
                   need_nf: tuple, narrow: tuple,
                   all_live: bool, fold: bool = False) -> PayloadLayout:
    """The layout for a join whose left / right lane matrices (``lspec`` /
    ``rspec``: :class:`~.lanes.LaneSpec`, None where that side does not
    ride) go through the sort of a key tuple of physical ``key_dtypes``
    packed with ``need_nf`` / ``narrow`` / ``fold``
    (:func:`.pack.key_operands`; no liveness operand when ``all_live``, or
    when ``fold`` put it into the leading key operand - a live row's
    operand is its key either way, and nobody reads a lane at padding).
    ``key_cols[i]``: the left lane column that IS key column i (None: no
    left output column is).

    Rule 2 takes a key's lanes from its sorted operands where the two are
    bit-equal, decided on the physical dtype alone: integer kinds (a string
    key is its int32 dictionary codes, and rides as them, so it is taken
    like any int32) with no null-flag operand (a nullable key's operand is
    zeroed under the flag while the lane keeps the raw datum) - one lane =
    the narrow / 32-bit operand, or ``lo`` of a wide pair; two lanes = the
    ``(hi, lo)`` pair.  Floats decline (their operands are canonicalised),
    and so does a two-lane column beside a narrow operand."""
    kinds, slots = key_operand_slots(key_dtypes, need_nf, narrow,
                                     row_mask=not all_live, fold=fold)
    nl = lspec.n_lanes if lspec is not None else 0
    alias = [-1] * nl
    for ci, dt, nf, ops in zip(key_cols if nl else (), key_dtypes, need_nf,
                               slots):
        if ci is None or nf or np.dtype(dt).kind not in "iu":
            continue
        col = lspec.cols[ci]
        if np.dtype(col.dtype) != np.dtype(dt):
            continue
        if len(col.lanes) == 1:
            alias[col.lanes[0]] = ops[-1]
        elif len(col.lanes) == len(ops) == 2:
            alias[col.lanes[0]], alias[col.lanes[1]] = ops
    return PayloadLayout(len(kinds), nl,
                         rspec.n_lanes if rspec is not None else 0,
                         tuple(alias))


@staged("pack")
def payload_operands(layout: PayloadLayout, lmat, rmat, n_l: int,
                     n_r: int) -> tuple:
    """The sort's payload operands, (n_l + n_r,) uint32 each: physical
    payload j is left riding lane j over the left rows and right lane j
    over the right rows, zeros where a side has no such lane.  ``lmat`` /
    ``rmat``: the sides' (n, L) lane matrices (not read, so None will do,
    where ``layout`` has no lane of that side)."""
    n = layout.n_payloads
    left = [lmat[:, j] for j in layout.riding]
    right = [rmat[:, j] for j in range(layout.nr)]
    left += [jnp.zeros(n_l, jnp.uint32)] * (n - len(left))
    right += [jnp.zeros(n_r, jnp.uint32)] * (n - len(right))
    return tuple(jnp.concatenate(pair) for pair in zip(left, right))


@staged("unpack")
def payload_lanes(layout: PayloadLayout, pl_s: tuple) -> tuple:
    """``(left lanes, right lanes)`` of a sorted state's physical arrays
    ``pl_s`` (:class:`PayloadLayout`): ``layout.nl`` and ``layout.nr``
    (N,) uint32 arrays, a left lane valid at left rows and a right lane at
    right rows only."""
    kept = layout.kept_keys
    keys, pay = pl_s[:len(kept)], pl_s[len(kept):]
    ride = iter(pay)
    left = tuple(
        jax.lax.bitcast_convert_type(keys[kept.index(a)], jnp.uint32)
        if a >= 0 else next(ride) for a in layout.alias)
    return left, tuple(pay[:layout.nr])


def join_sort_state(ko_l: KeyOps, ko_r: KeyOps, payloads: tuple = (),
                    keep: tuple = ()):
    """THE sort: stable lexicographic sort of the concatenated key tuples.

    Returns ``(bnd, idx_s, pl_s)`` — bnd/idx_s (n_l + n_r,) int32.
    ``idx_s[p]`` is the concat-row index occupying sorted position
    p (values < n_l are left rows); ``bnd[p]`` = 1 iff position p starts a
    new key group (p=0 -> 0).  Stability ⇒ within a group, left rows come
    first, each side in source order.  (XLA's stable-sort expansion adds
    no tie-break operand of its own here: it reuses an operand that
    already is an iota, and ``idx`` is one.)

    Invariant every consumer of the state relies on (:func:`live_sides`;
    the set operators', the groupby's and the sort's rank sorts keep the
    same one): *padding occupies sorted positions ``[n_live, N)`` ⇔
    ``n_live`` is not None* — by a liveness operand or by the sentinel.
    Callers build both sides' operands with a ``row_mask``
    (:func:`.pack.key_operands`) exactly when the tables are not at
    capacity: padding then carries 4 / 5 in a leading liveness operand
    (live rows 0), or - ``fold``, where ``pack.fold_room`` finds the
    leading key operand has room - that operand's two top values, which no
    live key reaches.  Either way live rows sort first whatever their keys
    and ``n_live`` is the two sides' valid counts summed — a per-shard
    scalar; the two tables' padding never compares equal.

    ``payloads``: optional (n_l+n_r,) arrays carried through the sort
    (:func:`payload_operands`) — every operand is one more pass of the
    sort's network, 1.1-1.25 ns a row on v5e (ledger, PRs 29-34), against
    ~20 ns/row for a later gather, so callers ride small column sets
    along.  ``keep``: key-operand indices whose SORTED arrays lead
    ``pl_s`` (:attr:`PayloadLayout.kept_keys`); the sorted payloads
    follow."""
    cat = concat_keyops(ko_l, ko_r)
    n = cat.n
    idx = jnp.arange(n, dtype=jnp.int32)
    with stage("sort_keys"):
        sorted_all = jax.lax.sort(cat.ops + (idx,) + tuple(payloads),
                                  num_keys=len(cat.ops), is_stable=True)
    nk = len(cat.ops)
    idx_s = sorted_all[nk]
    bnd = neighbor_flags(sorted_all[:nk], cat.kinds)
    return (bnd, idx_s,
            tuple(sorted_all[i] for i in keep) + tuple(sorted_all[nk + 1:]))


@staged("liveness")
def live_sides(idx_s, n_l: int, n_live=None):
    """The live prefix of a sorted join state: ``(lefts, rights, live)``
    bool arrays over sorted positions — live left rows, live right rows,
    and row liveness itself (None when ``n_live`` is None: every row is
    live).  Liveness at sorted position p is the position compare
    ``p < n_live`` (:func:`join_sort_state`'s invariant), never an
    N-length mask gathered through ``idx_s`` (a random 1-byte gather,
    ~10 ns/row measured on v5e: 0.63 s of a 2.2 s query at 65M rows)."""
    side = idx_s >= n_l
    if n_live is None:
        return ~side, side, None
    assert jnp.ndim(n_live) == 0, "n_live is a scalar, not a row mask"
    live = jnp.arange(idx_s.shape[0], dtype=jnp.int32) < n_live
    return ~side & live, side & live, live


@staged("join_count")
def join_carry(bnd, idx_s, n_live, n_l: int, how: str) -> tuple:
    """Phase-1 geometry: returns ``(total, JoinCarry)`` with ``total`` the
    exact output row count (device scalar int32).

    Segmented counts come from prefix sums + monotone-broadcast scans ONLY
    (cummax forward, reverse cummin backward over the non-decreasing
    prefixes) — no gathers at all (~15 ns/row each, measured, vs ~1 ns/row
    for a scan) and NOT ``associative_scan``, whose XLA:TPU compile time
    explodes superlinearly with array size (~200 s at 2M rows, measured).

    ``n_live``: int32 scalar, the live rows of the concat — they are the
    sorted prefix ``[0, n_live)`` because padding sorted last, by its
    liveness operand or by the sentinel in the leading key operand
    (:func:`join_sort_state`'s invariant), so row liveness is a position
    compare.  ``None`` asserts every concat row is live (host-known
    ``valid_counts == capacity``; no padding was sorted)."""
    n = bnd.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    lefts_b, rights_b, _live = live_sides(idx_s, n_l, n_live)
    with stage("liveness"):
        lefts = lefts_b.astype(jnp.int32)
        rights = rights_b.astype(jnp.int32)
    with stage("boundaries"):
        first = bnd.astype(bool) | (pos == 0)

    with stage("scan"):
        s_l = jnp.cumsum(lefts).astype(jnp.int32)  # inclusive prefix counts
        s_r = jnp.cumsum(rights).astype(jnp.int32)

    emit_right = how == "right"
    keep_unmatched = how in ("left", "right", "outer")
    need_fwd = emit_right or how == "outer"

    if need_fwd:
        # S_l exclusive at the group start, broadcast forward: s_l - lefts is
        # non-decreasing, so a cummax of its masked group-start values holds
        # each position's own-group start state
        with stage("scan"):
            b_l = jax.lax.cummax(jnp.where(first, s_l - lefts, jnp.int32(0)))

    if emit_right:
        # group left-count = S_l[p] - S_l[group start - 1]; for a right row
        # all group lefts precede it (stability), so s_l[p] includes them all
        cnt = (s_l - b_l).astype(jnp.int32)
        with stage("scan"):
            mstart = jax.lax.cummax(jnp.where(first, pos, jnp.int32(0)))
        emits = rights != 0
    else:
        # S_l/S_r at the group END, broadcast backward: the prefixes are
        # non-decreasing, so reverse-cummin of their masked group-end values
        # gives each position its own group's end state
        ebnd = jnp.concatenate([first[1:], jnp.ones(1, bool)])
        imax = jnp.int32(2**31 - 1)
        with stage("scan"):
            e_l = jax.lax.cummin(jnp.where(ebnd, s_l, imax), reverse=True)
            e_r = jax.lax.cummin(jnp.where(ebnd, s_r, imax), reverse=True)
        t_l = e_l - (s_l - lefts)            # lefts in [p .. end]
        cnt = e_r - (s_r - rights)           # rights in [p .. end]
        mstart = pos + t_l                   # first right position of group
        emits = lefts != 0

    eff = jnp.where(emits,
                    jnp.maximum(cnt, 1) if keep_unmatched else cnt,
                    0).astype(jnp.int32)
    with stage("scan"):
        csum = jnp.cumsum(eff)
    offs = (csum - eff).astype(jnp.int32)
    total = (csum[-1] if n > 0 else jnp.int32(0)).astype(jnp.int32)

    if how == "outer":
        grp_l = (s_l - b_l).astype(jnp.int32)
        un = ((rights != 0) & (grp_l == 0)).astype(jnp.int32)
        total = total + jnp.sum(un)
    else:
        un = jnp.zeros(n, jnp.int32)
    return total, JoinCarry(offs, eff, cnt, mstart, idx_s, un)


class JoinTake(NamedTuple):
    """Phase-2 expansion state, all (out_cap,) arrays over output slots.

    ``valid`` covers the MAIN emission only (slot < total excluding outer
    joins' appended unmatched-right rows, which occupy [main, total) with
    valid=False but a real ``r_take``) — outer-join callers must use the
    take arrays, not ``valid``, to mask real rows.  The carry_* fast paths
    that do rely on ``valid`` are restricted to inner/left joins, where
    valid exactly means "real output row"."""
    total: jax.Array      # scalar int32: exact output rows
    valid: jax.Array      # bool: slot holds a main-emission output row
    matched: jax.Array    # bool: slot's match-side row exists
    mpos: jax.Array       # int32: sorted position of the match-side row
    l_take: object        # left row index or -1; None if suppressed
    r_take: object        # right row index or -1; None if suppressed
    extra: tuple          # carried emit-side u32 lanes at the owning row


@staged("join_expand")
def join_take(carry: JoinCarry, n_l: int, how: str, out_cap: int,
              extra: tuple = (), carry_emit: bool = False,
              carry_match: bool = False, emit_idx: bool = False,
              match_idx: bool = False) -> JoinTake:
    """Phase-2 materialization over ``out_cap`` static output slots
    (``out_cap`` >= phase 1's total; slots past ``total`` are invalid).

    Output slot k is owned by the "emitting" sorted row (left rows for
    inner/left/outer, right rows for right joins) whose offs/eff interval
    contains k; ownership is reconstructed with one scatter (offs strictly
    increase over emitting rows, so plain ``set`` — no combiner needed) and
    a ``cummax`` fill.  ONE stacked (out, M) gather at the owner position
    then provides the slot's geometry AND any ``extra`` u32 lanes the
    caller rode through the phase-1 sort (the emit side's packed output
    columns — ``carry_emit``).

    Static specialization knobs (and the measured ~15 ns/slot gathers they
    remove):
      * ``carry_emit``: emit-side values arrive via ``extra`` → the owner's
        concat-row index (idx_s) drops out of the meta stack and the
        emit-side take array is None (no emit-side lane-matrix gather in
        the caller).
      * ``carry_match``: match-side values ride sorted payload lanes the
        caller gathers at ``mpos`` → the dependent ``idx_s[mpos]`` gather
        is skipped and the match-side take array is None.
      * ``how == "inner"``: every emitted slot is a real match, so
        ``matched == valid`` and the per-group match count drops out of the
        meta stack entirely.
      * ``emit_idx``/``match_idx`` (carry-LITE, f64 columns): laneable
        columns ride the sort but f64 cannot (TPU bitcast/sort-payload
        SIGSEGV), so the corresponding take array is kept alongside the
        carried lanes — the caller gathers just the f64 side columns by
        index.
    """
    offs, eff, cnt, mstart, idx_s, un = carry
    n = offs.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    total_main = (offs[-1] + eff[-1] if n > 0 else jnp.int32(0)).astype(
        jnp.int32)

    # emitting rows have strictly increasing offs -> distinct slots: set,
    # not max (measured ~8.8 vs ~12 ns/update); unscattered slots keep 0 and
    # the cummax fill assigns them their predecessor's owner
    scat = jnp.where(eff > 0, offs, jnp.int32(out_cap))
    p0 = jnp.zeros(out_cap, jnp.int32).at[scat].set(pos, mode="drop")
    with stage("scan"):
        p_of_k = jax.lax.cummax(p0)

    need_cnt = how != "inner"
    need_own_idx = (not carry_emit) or emit_idx
    meta_cols = [offs, mstart]
    if need_cnt:
        meta_cols.append(cnt)
    if need_own_idx:
        meta_cols.append(idx_s)
    for e in extra:
        meta_cols.append(jax.lax.bitcast_convert_type(e, jnp.int32))
    meta = jnp.stack(meta_cols, axis=1)[p_of_k]    # THE (out, M) gather
    k = jnp.arange(out_cap, dtype=jnp.int32)
    rel = k - meta[:, 0]
    valid = k < total_main
    matched = valid if how == "inner" else valid & (rel < meta[:, 2])
    mpos = jnp.clip(meta[:, 1] + rel, 0, max(n - 1, 0))
    ci = 2 + int(need_cnt)
    own_idx = meta[:, ci] if need_own_idx else None
    extra_out = tuple(
        jax.lax.bitcast_convert_type(meta[:, ci + int(need_own_idx) + j],
                                     jnp.uint32)
        for j in range(len(extra)))
    m_idx = None if (carry_match and not match_idx) else idx_s[mpos]

    l_take = r_take = None
    if how == "right":
        if need_own_idx:
            r_take = jnp.where(valid, own_idx - n_l, jnp.int32(-1))
        if m_idx is not None:
            l_take = jnp.where(matched, m_idx, jnp.int32(-1))
    else:
        if need_own_idx:
            l_take = jnp.where(valid, own_idx, jnp.int32(-1))
        if m_idx is not None:
            r_take = jnp.where(matched, m_idx - n_l, jnp.int32(-1))

    total = total_main
    if how == "outer":
        with stage("scan"):
            unpos = (jnp.cumsum(un) - un).astype(jnp.int32)
        slot = jnp.where(un > 0, total_main + unpos, jnp.int32(out_cap))
        r_take = r_take.at[slot].set(idx_s - n_l, mode="drop")
        total = total_main + jnp.sum(un, dtype=jnp.int32)
    return JoinTake(total, valid, matched, mpos, l_take, r_take, extra_out)
