"""Key canonicalization & dense-rank packing.

TPU-native replacement for the reference's row comparators / hashers
(cpp/src/cylon/arrow/arrow_comparator.hpp:59 ``ArrayIndexComparator``, :196
``TableRowIndexHash``, :238/270 dual-table variants) and the multi-column
flattener (util/flatten_array.cpp).  The reference compares rows via per-type
virtual comparators and pointer-chasing hash maps; on TPU we instead

1. canonicalize every key column into **sort operands** (``KeyOps``) such
   that ``jax.lax.sort``'s multi-operand lexicographic order implements the
   requested row order (ascending/descending, nulls first/last), and
2. replace "row equality/hash" with a **dense rank**: jointly sort the key
   tuples and assign consecutive group ids.  Two tables get comparable ids by
   ranking their concatenation (the dual-table comparator analog).

**u32 lane packing (the TPU fast path).**  TPU has no native 64-bit integer
compare — an int64 ``lax.sort`` operand runs through XLA's x64 emulation and
dominates every relational op.  So every sort operand is packed into native
32-bit lanes before it reaches ``lax.sort``:

* int64/uint64 → (hi int32/uint32, lo uint32) operand pair — lexicographic
  order over the pair equals the 64-bit numeric order;
* int8/16/32, bool → one int32 operand;
* float32 → one uint32 operand via the IEEE total-order bit flip
  (sign bit set → flip all bits, else set sign bit) after NaN/-0.0
  canonicalization, so plain unsigned compares implement float order and
  bit equality implements float equality (NaN == NaN);
* float64 → kept as one f64 operand (kind 'f'): XLA TPU does not implement
  u64 bitcast-convert and the (2,)-u32 bitcast half-order is platform
  ambiguous, so f64 keys stay on the (slower) emulated-compare path.

Descending order uses arithmetic transforms before packing (``~x`` for ints
— total, overflow-free — and ``-x`` for floats).  Null flags are emitted
only for columns that can actually hold nulls (callers coordinate the
static operand structure across tables with ``need_null_flags``).

**Row liveness (padding).**  A table under its capacity has padding rows
that must sort behind every live row.  They carry a pad key (``PAD_L`` /
``PAD_R``: distinct per table): in a leading liveness operand of their own,
or - where :func:`fold_room` proves the FIRST key column's first operand
never reaches its two top values (a null flag, a narrow / 32-bit integer
with host-known bounds, a float32 image) - inside that operand
(``key_operands(fold=True)``), one operand fewer through the sort.  After
the sort nobody reads it: liveness is the position compare ``p < n_live``.

Every downstream op (join, groupby, set ops, unique) then works on a single
int32 id column — the moral equivalent of the reference flattening multi-col
keys to one binary column before hashing.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.stages import stage, staged

NULL_FIRST = 0
NULL_LAST = 2

#: what a padding row carries where its table is ranked: distinct per table,
#: so padding never rank-equals across the two tables of a join.  In a
#: liveness operand or a null-flag operand (whose live values are 0 / 0-2)
#: the pad key itself; folded into a 32-bit value operand, the operand
#: type's ``max - 1`` / ``max`` (:func:`key_operands`, ``fold``).
PAD_L, PAD_R = 4, 5

#: The most operands (keys, index and payload lanes together) one
#: ``lax.sort`` is given where the caller has another way to move its
#: payload.  XLA:TPU compiles a sort in time that grows with its OPERAND
#: count and hardly with its rows (described v5e compiles, PR 41: 2 keys of
#: 3 / 4 / 6 / 8 / 10 operands 41 / 62 / 89 / 138 / 213 s at 4M rows, 116 s
#: for 8 at 65,536; TPC-H Q3's 13-operand sort of 57,344 rows 639 s), while
#: what riding saves at run time shrinks with the rows - so past this count
#: the join's sides stop riding (relational/join.py: their columns are
#: gathered at the take index), and a sort or a groupby rides the row index
#: alone and moves its lane matrix by ONE gather at the permutation
#: (relational/sort.py, relational/groupby._sort_state).  7 is the widest
#: sort of the benchmark's accepted cells.
SORT_OPERAND_BUDGET = 7


def sort_operand_nbytes(dtypes, need_nf, narrow, rows: int,
                        row_mask: bool = True, fold: bool = False) -> int:
    """Host-side static size of the operand set :func:`key_operands`
    materializes for ``rows`` rows — the per-piece sort scratch a join
    over this key structure will hold resident while it runs.  Mirrors
    the packing rules above (liveness flag, unless ``fold`` put it into
    the leading operand, + per-column null flag + one or two native value
    lanes; f64 stays a single 8-byte operand).

    This is the "registration at pack time" half of the HBM ledger
    (:mod:`cylon_tpu.exec.memory`): piece working-set sizing consults it
    so admission of a new packed source accounts for the transient
    operands its consumer will add on top of the resident matrices."""
    per_row = 4 if row_mask and not fold else 0
    for dt, nf, nw in zip(dtypes, need_nf, narrow):
        if nf:
            per_row += 4
        d = np.dtype(dt)
        if d.kind == "f" and d.itemsize == 8:
            per_row += 8          # f64 keys stay one emulated-compare operand
        elif d.itemsize == 8 and d.kind in ("i", "u") and not nw:
            per_row += 8          # (hi, lo) native lane pair
        else:
            per_row += 4          # one native 32-bit operand
    return per_row * int(rows)


class KeyOps(NamedTuple):
    """Lexicographic sort operands + per-operand kind ('i' int-like,
    'f' float — needs NaN-aware equality)."""

    ops: tuple
    kinds: tuple

    @property
    def n(self):
        return self.ops[0].shape[0]


def _canon_float(x: jax.Array) -> jax.Array:
    """Canonicalize float payloads for *equality*: -0.0 → +0.0 and all NaNs
    → one positive quiet NaN (so sort is deterministic and NaNs group)."""
    x = jnp.where(x == 0, jnp.zeros_like(x), x)
    return jnp.where(jnp.isnan(x), jnp.full_like(x, jnp.nan), x)


def _sort_value(x: jax.Array, descending: bool,
                narrow: bool = False) -> list[tuple[jax.Array, str]]:
    """Pack one key column into native-lane sort operands: a list of
    (operand, kind) pairs whose lexicographic order equals the column's
    requested order (see module docstring for the packing rules).

    ``narrow=True`` asserts (host-known ``Column.bounds``) that a 64-bit
    integer column's values fit in int32 — it then sorts as ONE native
    operand instead of a (hi, lo) pair."""
    dt = x.dtype
    if dt == jnp.bool_:
        v = x.astype(jnp.int32)
        return [((-v if descending else v), "i")]
    if jnp.issubdtype(dt, jnp.integer):
        if narrow and x.dtype.itemsize == 8:
            x = x.astype(jnp.int32)
        # ~x = -x-1: strictly decreasing, total, no overflow (INT_MIN→INT_MAX)
        if descending:
            x = ~x
        if x.dtype.itemsize <= 4:
            if x.dtype == jnp.uint32:
                return [(x, "i")]
            return [(x.astype(jnp.int32), "i")]
        # 64-bit: split into (hi, lo) native lanes.  Arithmetic >>32 keeps
        # the sign in hi (signed) / zero-extends (unsigned); lo compares
        # unsigned either way.
        signed = jnp.issubdtype(x.dtype, jnp.signedinteger)
        hi = (x >> 32).astype(jnp.int32 if signed else jnp.uint32)
        lo = (x & jnp.asarray(0xFFFFFFFF, x.dtype)).astype(jnp.uint32)
        return [(hi, "i"), (lo, "i")]
    if jnp.issubdtype(dt, jnp.floating):
        v = -x if descending else x
        # positive canonical NaN sorts after all numbers in XLA's total order
        v = _canon_float(v)
        if dt == jnp.float32:
            u = jax.lax.bitcast_convert_type(v, jnp.uint32)
            flip = jnp.where(u >> 31 != 0, jnp.uint32(0xFFFFFFFF),
                             jnp.uint32(0x80000000))
            return [(u ^ flip, "i")]
        return [(v, "f")]
    raise TypeError(f"unsortable dtype {dt}")


def fold_room(dtype, need_nf: bool, bounds, descending: bool = False) -> bool:
    """THE rule of whether row liveness folds into the leading key operand
    (:func:`key_operands`, ``fold``), in dtype space: has the FIRST key
    column's first operand two values above every live row's?  ``dtype``:
    the column's physical dtype; ``need_nf``: it sorts behind a null-flag
    operand; ``bounds``: the host-known ``(lo, hi)`` of that column in
    EVERY table ranked together (None where a table's are unknown).

    * a null-flag operand holds 0 / 1 / 2 and padding takes the pad key in
      it: always room, whatever follows;
    * a bool, an 8- / 16-bit integer and a float32 (whose order-preserving
      u32 image never passes the canonical NaN's ``0xFFC00000``) use a
      fraction of their 32-bit operand: always room;
    * a 32-bit integer, or a 64-bit one whose bounds fit int32 (the ONE
      operand of ``narrow32``), where the bounds prove ``hi <= max - 2`` of
      the operand's type - descending, where the operand is ``~x``,
      ``lo >= min + 2``;
    * a wide ``(hi, lo)`` pair, an f64 key and a 32-bit column whose bounds
      nobody knows keep the liveness operand."""
    if need_nf:
        return True
    d = np.dtype(dtype)
    if d.kind == "b" or (d.kind in "iu" and d.itemsize < 4):
        return True
    if d.kind == "f":
        return d.itemsize == 4
    if d.kind not in "iu" or any(b is None for b in bounds):
        return False
    lo = min(int(b[0]) for b in bounds)
    hi = max(int(b[1]) for b in bounds)
    info = np.iinfo(np.int32 if d.itemsize == 8 else d)
    if d.itemsize == 8 and not (info.min <= lo and hi <= info.max):
        return False            # not narrow: a (hi, lo) pair leads
    return lo >= info.min + 2 if descending else hi <= info.max - 2


def _fold_pad(op: jax.Array, row_mask, pad_key: int) -> jax.Array:
    """``op`` with padding rows at the operand type's ``max - 1``
    (:data:`PAD_L`) / ``max`` (:data:`PAD_R`): above every live value
    :func:`fold_room` admits, distinct per table."""
    if op.dtype.itemsize != 4 or not jnp.issubdtype(op.dtype, jnp.integer):
        raise ValueError(f"liveness cannot fold into a {op.dtype} operand "
                         "(ops/pack.fold_room decides; narrow32 must agree)")
    top = int(np.iinfo(op.dtype).max) - (PAD_R - pad_key)
    return jnp.where(row_mask, op, jnp.asarray(top, op.dtype))


@staged("pack")
def key_operands(datas, validities=None, row_mask=None, descendings=None,
                 nulls_position: int = NULL_LAST, pad_key: int = PAD_L,
                 need_null_flags=None, narrow32=None,
                 fold: bool = False) -> KeyOps:
    """Build the lexicographic sort-operand list for a key tuple.

    For each nullable key column: a null-flag operand then the packed value
    operand(s) — valid rows get flag 1, nulls get 0 (first) or 2 (last),
    matching pandas ``na_position`` independently of ascending/descending.

    With a ``row_mask`` padding rows sort last, behind every live row
    whatever its keys, and carry ``pad_key`` (use distinct pad keys per
    table so padding never matches across tables in a dense rank): in a
    leading row-liveness operand (live 0, padding ``pad_key``), or, with
    ``fold`` (:func:`fold_room` said the first column's first operand has
    room), INSIDE that operand - the null flag takes ``pad_key``, a 32-bit
    value operand its type's ``max - 1`` / ``max`` - and no liveness
    operand is built: one operand fewer through the sort, the same order.
    Either way the live rows are the sorted prefix.

    ``need_null_flags`` (tuple of bool per column) forces/suppresses the
    null-flag operand statically — callers ranking TWO tables together must
    pass the same tuple on both sides (operand structures must match even
    when only one side is nullable).  Default: emit iff the column has a
    validity mask.
    """
    ops, kinds = [], []
    n = datas[0].shape[0]
    fold = bool(fold) and row_mask is not None
    if row_mask is not None and not fold:
        ops.append(jnp.where(row_mask, jnp.int32(0), jnp.int32(pad_key)))
        kinds.append("i")
    for i, d in enumerate(datas):
        desc = bool(descendings[i]) if descendings is not None else False
        v = validities[i] if validities is not None else None
        need_nf = (v is not None) if need_null_flags is None \
            else bool(need_null_flags[i])
        if need_nf:
            if v is None:
                nf = jnp.ones(n, jnp.int32)
            else:
                nf = jnp.where(v, jnp.int32(1), jnp.int32(nulls_position))
                d = jnp.where(v, d, jnp.zeros_like(d))
            if fold and not ops:
                nf = jnp.where(row_mask, nf, jnp.int32(pad_key))
            ops.append(nf)
            kinds.append("i")
        nrw = bool(narrow32[i]) if narrow32 is not None else False
        vals = _sort_value(d, desc, narrow=nrw)
        if fold and not ops:
            if len(vals) != 1:
                raise ValueError("liveness cannot fold into a wide pair "
                                 "(ops/pack.fold_room decides)")
            vals = [(_fold_pad(vals[0][0], row_mask, pad_key), vals[0][1])]
        for val, kind in vals:
            ops.append(val)
            kinds.append(kind)
    return KeyOps(tuple(ops), tuple(kinds))


def key_operand_slots(dtypes, need_null_flags, narrow32,
                      row_mask: bool = True, fold: bool = False) -> tuple:
    """Static ``(kinds, slots)`` of the operand list :func:`key_operands`
    (ascending keys) produces for this key structure: ``kinds`` the
    operand KIND tuple - liveness flag (with a ``row_mask`` and no
    ``fold``), then per
    column an optional null flag plus the value operand kind(s) -
    and ``slots[i]`` the positions of column i's VALUE operand(s) in it
    (one, or ``(hi, lo)`` for a wide 64-bit integer).  This is
    :func:`_sort_value`'s packing rules in dtype space only (no arrays
    built): keep the two in lockstep - the Pallas probe's eligibility
    gate, exec/pipeline's static operand counts and the join's payload
    layout (ops/join.payload_layout) all read this."""
    kinds = ["i"] if row_mask and not fold else []
    slots = []
    for dt, nf, nrw in zip(dtypes, need_null_flags, narrow32):
        if nf:
            kinds.append("i")
        d = np.dtype(dt)
        if d.kind == "b":
            val = ("i",)
        elif d.kind in "iu":
            # wide 64-bit values split into a native (hi, lo) lane pair
            val = ("i",) if (d.itemsize <= 4 or nrw) else ("i", "i")
        elif d.kind == "f":
            # f32 sorts via the order-preserving uint32 bitcast ('i');
            # f64 keeps native NaN-aware float compares ('f')
            val = ("i" if d.itemsize <= 4 else "f",)
        else:
            raise TypeError(f"unsortable dtype {dt}")
        slots.append(tuple(range(len(kinds), len(kinds) + len(val))))
        kinds.extend(val)
    return tuple(kinds), tuple(slots)


def key_operand_kinds(dtypes, need_null_flags, narrow32,
                      fold: bool = False) -> tuple:
    """:func:`key_operand_slots`' kinds alone, liveness flag included
    (where ``fold`` did not put it into the leading operand)."""
    return key_operand_slots(dtypes, need_null_flags, narrow32,
                             fold=fold)[0]


def concat_keyops(a: KeyOps, b: KeyOps) -> KeyOps:
    assert a.kinds == b.kinds
    return KeyOps(tuple(jnp.concatenate([x, y]) for x, y in zip(a.ops, b.ops)),
                  a.kinds)


# -- float-aware elementwise comparisons (post-canonicalization) ------------

def op_neq(a, b, kind: str):
    if kind == "f":
        return (a != b) & ~(jnp.isnan(a) & jnp.isnan(b))
    return a != b


def op_gt(a, b, kind: str):
    if kind == "f":
        return (a > b) | (jnp.isnan(a) & ~jnp.isnan(b))
    return a > b


def op_eq(a, b, kind: str):
    if kind == "f":
        return (a == b) | (jnp.isnan(a) & jnp.isnan(b))
    return a == b


@staged("boundaries")
def neighbor_flags(sorted_ops, kinds):
    """int32 flags: row i != row i-1 under the key tuple (row 0 → 0)."""
    n = sorted_ops[0].shape[0]
    neq = jnp.zeros(n, jnp.int32)
    for op, kind in zip(sorted_ops, kinds):
        d = op_neq(op[1:], op[:-1], kind).astype(jnp.int32)
        neq = neq | jnp.concatenate([jnp.zeros(1, jnp.int32), d])
    return neq


def dense_rank(keyops: KeyOps):
    """Rank rows by their key tuple: returns ``(gids, n_groups)`` where
    ``gids[i]`` is the 0-based dense rank of row i's key (ids ordered like
    the keys — an order-preserving perfect hash over this batch)."""
    n = keyops.n
    idx = jnp.arange(n, dtype=jnp.int32)
    with stage("sort_keys"):
        sorted_all = jax.lax.sort(keyops.ops + (idx,),
                                  num_keys=len(keyops.ops), is_stable=True)
    sidx = sorted_all[-1]
    flags = neighbor_flags(sorted_all[:-1], keyops.kinds)
    with stage("scan"):
        gid_sorted = jnp.cumsum(flags)
    with stage("gather_rows"):
        gids = jnp.zeros(n, jnp.int32).at[sidx].set(
            gid_sorted.astype(jnp.int32))
    n_groups = (jnp.where(n > 0, gid_sorted[-1] + 1, 0).astype(jnp.int32)
                if n > 0 else jnp.int32(0))
    return gids, n_groups


@staged("boundaries")
def row_neq_prev(datas, validities=None, narrow32=None):
    """(n,) bool: row i's key tuple differs from row i-1's (row 0 -> False).
    Null-aware (null == null, null != value) and float-total (NaN == NaN,
    -0.0 == 0.0) — the same equality the dense rank implements, but computed
    directly on adjacent rows of an already-grouped table (no sort).
    ``narrow32[i]`` (host-known bounds fit int32) compares a 64-bit integer
    column in native int32 (x64-emulated i64 compares cost 2-4x)."""
    n = datas[0].shape[0]
    neq = jnp.zeros(max(n - 1, 0), bool)
    for i, d in enumerate(datas):
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = _canon_float(d)
            kind = "f"
        else:
            if narrow32 is not None and bool(narrow32[i]) \
                    and d.dtype.itemsize == 8:
                d = d.astype(jnp.int32)
            kind = "i"
        dn = op_neq(d[1:], d[:-1], kind)
        v = validities[i] if validities is not None else None
        if v is not None:
            dn = (v[1:] != v[:-1]) | (dn & v[1:] & v[:-1])
        neq = neq | dn
    return jnp.concatenate([jnp.zeros(min(n, 1), bool), neq])


def grouped_gids(datas, validities, mask, narrow32=None):
    """Dense group ids for an already-grouped (equal keys contiguous) shard:
    boundary flags + prefix sum — no sort.  Returns (gids, n_groups, first)
    with masked (padding) rows excluded from the id space (caller routes
    them); ``first`` marks each group's first live row."""
    n = datas[0].shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    first0 = pos == 0
    bnd = (row_neq_prev(datas, validities, narrow32) | first0) & mask
    with stage("scan"):
        gid = jnp.cumsum(bnd.astype(jnp.int32)).astype(jnp.int32) - 1
    n_groups = jnp.max(jnp.where(mask, gid, -1)) + 1
    return jnp.where(mask, gid, n), n_groups.astype(jnp.int32), bnd


def _rows_cmp_splitters(keyops: KeyOps, splitter_ops: tuple):
    n = keyops.n
    s = splitter_ops[0].shape[0]
    gt = jnp.zeros((n, s), bool)
    eq = jnp.ones((n, s), bool)
    for op, sop, kind in zip(keyops.ops, splitter_ops, keyops.kinds):
        a = op[:, None]
        b = sop[None, :]
        gt = gt | (eq & op_gt(a, b, kind))
        eq = eq & op_eq(a, b, kind)
    return gt, eq


def rows_cmp_splitters(keyops: KeyOps, splitter_ops: tuple):
    """(gt, eq) (n, S) bool pairs: row i's key tuple strictly greater
    than / exactly equal to splitter j's under the operand total order —
    the comparison primitive of the skew-split plan facade
    (relational/skew.py): heavy-key membership (eq) and key-rank
    corrections (gt ≡ "splitter sorts before row") both run in OPERAND
    space, so they agree bit-for-bit with the join sort's own key order
    (float canonicalization, null flags, narrow lanes and all)."""
    return _rows_cmp_splitters(keyops, splitter_ops)


def rows_gt_splitters(keyops: KeyOps, splitter_ops: tuple):
    """(n, S) bool: row i's key tuple strictly greater than splitter j's.
    Used by sample-sort range partitioning (reference table.cpp:564-609
    split-point binary search).  ``splitter_ops`` parallel ``keyops.ops``
    with shape (S,) each."""
    gt, _ = _rows_cmp_splitters(keyops, splitter_ops)
    return gt


def rows_ge_splitters(keyops: KeyOps, splitter_ops: tuple):
    """(n, S) bool: row i's key tuple >= splitter j's under the same total
    order as :func:`rows_gt_splitters`.  Used by the range-partitioned
    pipeline (exec/pipeline.py): splitters are key-GROUP STARTS of the
    sorted build side, so a probe key equal to splitter j belongs to the
    range j opens — assignment must be >=, not >."""
    gt, eq = _rows_cmp_splitters(keyops, splitter_ops)
    return gt | eq
