"""u32 lane-matrix packing: move many columns in ONE gather/collective.

TPU cost model (measured on v5e): a random row gather of an (n, L) matrix
costs ~(1 + 0.2·(L-1))× a 1-D gather — far cheaper than L separate 1-D
gathers.  So whenever an operator must move whole rows by index (join/filter
materialization, shuffle exchange), the table's columns are first bitcast
into one (n, L) uint32 lane matrix, moved in one pass, and unpacked after.

This is the TPU analog of the reference's row-wise serializer: Arrow buffer
triplets per column (serialize/table_serialize.hpp:23-59) become u32 lanes —
  * int64/uint64/datetime64 → 2 lanes (hi, lo via shifts — no 64-bit
    bitcasts: XLA's TPU x64 rewriter does not implement them)
  * int32/uint32/float32/int16/int8/string-codes → 1 lane (bitcast/widen)
  * bool → 1 lane (0/1)
  * validity masks → bit-packed, 32 columns per lane
  * float64 → NOT laneable (its bit split would need a 64-bit bitcast);
    planned as a side column and moved as a raw f64 array — XLA's gather
    handles f64 under x64 fine, it's only bitcast that is missing.
Bitcasts are bit-exact roundtrips on the same device, so no ordering or
canonicalization concerns apply (unlike sort-operand packing in pack.py).

The static :class:`LaneSpec` travels with compiled programs (hashable), the
matrix with the data.  :func:`gather_columns` is the one-stop row-move:
one (n, L) matrix gather for every laneable column + validity, plus one
1-D gather per f64 column.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.stages import staged


class ColLanes(NamedTuple):
    """Static description of one column's slot in the lane matrix."""
    dtype: str       # numpy dtype name of the column data
    lanes: tuple     # lane indices (1 or 2 entries; 64-bit = (hi, lo));
                     # empty tuple = non-laneable (f64 side column)
    valid_bit: int   # bit position in the validity lane block, or -1
    narrow: bool = False  # 64-bit int whose host-known bounds fit int32:
                          # packed as ONE sign-extending lane instead of two


class LaneSpec(NamedTuple):
    cols: tuple          # tuple[ColLanes]
    n_lanes: int         # total lanes incl. validity lanes
    valid_lane0: int     # first validity lane index (== n_lanes if none)


def plan_lanes(dtypes, has_valid, narrow=None) -> LaneSpec:
    """Build the static lane layout for columns of ``dtypes`` (numpy dtype
    names) where ``has_valid[i]`` marks nullable columns.  float64 columns
    get no lanes (side-channel); their validity still rides the matrix.
    ``narrow[i]`` (host-known ``Column.bounds`` fit int32) packs a 64-bit
    integer column as ONE lane — every pass that moves the matrix gets
    proportionally cheaper."""
    cols = []
    lane = 0
    vbit = 0
    for i, (dt, hv) in enumerate(zip(dtypes, has_valid)):
        ndt = np.dtype(dt)
        nrw = bool(narrow[i]) if narrow is not None else False
        nrw = nrw and ndt.itemsize == 8 and ndt.kind in ("i", "u")
        if ndt.itemsize == 8 and np.issubdtype(ndt, np.floating):
            lanes = ()
        else:
            width = 1 if (ndt.itemsize < 8 or nrw) else 2
            lanes = tuple(range(lane, lane + width))
            lane += width
        cols.append(ColLanes(dt, lanes, vbit if hv else -1, nrw))
        if hv:
            vbit += 1
    valid_lane0 = lane
    n_valid_lanes = (vbit + 31) // 32
    return LaneSpec(tuple(cols), lane + n_valid_lanes, valid_lane0)


def _to_lanes(x, narrow: bool = False):
    """Column data array -> list of u32 lane arrays (hi, lo for 64-bit
    ints; f64 never reaches here — it is planned laneless)."""
    dt = x.dtype
    if dt == jnp.bool_:
        return [x.astype(jnp.uint32)]
    if dt.itemsize == 8:
        if narrow:  # host-known bounds fit int32: one sign-carrying lane
            return [jax.lax.bitcast_convert_type(x.astype(jnp.int32),
                                                 jnp.uint32)]
        xi = x.astype(jnp.int64) if dt != jnp.uint64 else x
        hi = (xi >> 32).astype(jnp.uint32)
        lo = (xi & jnp.asarray(0xFFFFFFFF, xi.dtype)).astype(jnp.uint32)
        return [hi, lo]
    if jnp.issubdtype(dt, jnp.floating):  # f32 (f16 widened by caller)
        return [jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                             jnp.uint32)]
    if jnp.issubdtype(dt, jnp.signedinteger):
        return [jax.lax.bitcast_convert_type(x.astype(jnp.int32),
                                             jnp.uint32)]
    return [x.astype(jnp.uint32)]


def _from_lanes(lanes, dtype: str, narrow: bool = False):
    dt = np.dtype(dtype)
    jdt = jnp.dtype(dt)
    if dt == np.bool_:
        return lanes[0] != 0
    if dt.itemsize == 8:
        if narrow:
            return jax.lax.bitcast_convert_type(
                lanes[0], jnp.int32).astype(jdt)
        hi, lo = lanes
        x = (jax.lax.bitcast_convert_type(hi, jnp.int32).astype(jnp.int64)
             << 32) | lo.astype(jnp.int64)
        return x.astype(jdt)
    if np.issubdtype(dt, np.floating):
        return jax.lax.bitcast_convert_type(lanes[0], jnp.float32).astype(jdt)
    if np.issubdtype(dt, np.signedinteger):
        return jax.lax.bitcast_convert_type(lanes[0], jnp.int32).astype(jdt)
    return lanes[0].astype(jdt)


def _lane_list(spec: LaneSpec, datas, valids) -> list:
    """The ``spec.n_lanes`` u32 lane arrays of parallel column arrays, in
    lane order (laneless f64 columns contribute only their validity bit).
    ``valids[i]`` may be None for columns planned with valid_bit == -1."""
    n = datas[0].shape[0]
    lanes = [None] * spec.n_lanes
    n_valid_lanes = spec.n_lanes - spec.valid_lane0
    vlanes = [jnp.zeros(n, jnp.uint32) for _ in range(n_valid_lanes)]
    for col, d, v in zip(spec.cols, datas, valids):
        if col.lanes:
            for li, arr in zip(col.lanes, _to_lanes(d, col.narrow)):
                lanes[li] = arr
        if col.valid_bit >= 0:
            vb = jnp.ones(n, jnp.uint32) if v is None else v.astype(jnp.uint32)
            slot = col.valid_bit // 32
            vlanes[slot] = vlanes[slot] | (vb << jnp.uint32(col.valid_bit % 32))
    for i, vl in enumerate(vlanes):
        lanes[spec.valid_lane0 + i] = vl
    return lanes


@staged("pack")
def pack_lanes(spec: LaneSpec, datas, valids):
    """(n, spec.n_lanes) uint32 lane matrix from parallel column arrays
    (:func:`_lane_list`, a lane a COLUMN of the matrix)."""
    return jnp.stack(_lane_list(spec, datas, valids), axis=1)


@staged("pack")
def pack_lane_rows(spec: LaneSpec, datas, valids, multiple: int = 1):
    """The same lanes LANE-MAJOR: an (L, n) matrix, a lane a ROW, with the
    zero rows that bring L to a multiple of ``multiple`` inside the one
    stack (the windowed take wants a sublane multiple; a ``jnp.pad`` after
    the stack would copy the whole operand again).  An axis-0 stack is a
    plain concat; a transpose of the (n, L) matrix costs what its gather
    does (ops/pallas_gather)."""
    lanes = _lane_list(spec, datas, valids)
    pad = -len(lanes) % multiple
    zero = [jnp.zeros_like(lanes[0])] * pad
    return jnp.stack(lanes + zero, axis=0)


def _unpack(spec: LaneSpec, lane):
    """(datas, valids) of the lanes ``lane(i)`` hands out: laneless (f64)
    columns yield None data (moved separately); valids entries are None
    for columns planned without validity."""
    datas, valids = [], []
    for col in spec.cols:
        if col.lanes:
            datas.append(_from_lanes([lane(li) for li in col.lanes],
                                     col.dtype, col.narrow))
        else:
            datas.append(None)
        if col.valid_bit >= 0:
            vl = lane(spec.valid_lane0 + col.valid_bit // 32)
            valids.append(((vl >> jnp.uint32(col.valid_bit % 32)) & 1) != 0)
        else:
            valids.append(None)
    return tuple(datas), tuple(valids)


@staged("unpack")
def unpack_lanes(spec: LaneSpec, mat):
    """Inverse of :func:`pack_lanes` (:func:`_unpack` of the columns of the
    (n, L) matrix)."""
    return _unpack(spec, lambda li: mat[:, li])


@staged("unpack")
def unpack_lane_rows(spec: LaneSpec, mat_t):
    """Inverse of :func:`pack_lane_rows`: a lane is a row of ``mat_t``."""
    return _unpack(spec, lambda li: mat_t[li])


def slice_lanes(spec: LaneSpec, mat, start, window: int):
    """Contiguous window ``[start, start+window)`` of the lane matrix as a
    dynamic slice (no gather).  The caller guarantees the matrix is padded
    so the window never clamps (see exec/pipeline piece sources)."""
    return jax.lax.dynamic_slice(mat, (start, jnp.int32(0)),
                                 (window, spec.n_lanes))


@staged("unpack")
def unpack_column(spec: LaneSpec, mat, i: int):
    """Lazily unpack ONE column ``i`` from the lane matrix: ``(data,
    valid)``, either None when the column is laneless (f64 side channel) /
    planned without validity.  The point versus :func:`unpack_lanes`: a
    consumer that reads only the key columns of a packed piece touches
    only their lanes — every other column's unpack never enters the
    program (XLA sees no use of those lanes)."""
    col = spec.cols[i]
    d = _from_lanes([mat[:, li] for li in col.lanes], col.dtype,
                    col.narrow) if col.lanes else None
    v = None
    if col.valid_bit >= 0:
        vl = mat[:, spec.valid_lane0 + col.valid_bit // 32]
        v = ((vl >> jnp.uint32(col.valid_bit % 32)) & 1) != 0
    return d, v


@staged("gather_rows")
def gather_laneless(spec: LaneSpec, datas, take) -> dict:
    """{col_index: gathered data} for ONLY the laneless (f64) columns of
    ``spec`` — one batched (n, K) f64 matrix gather.  Used by the join's
    carry-LITE path: laneable columns ride the sort, f64 columns gather
    by take index."""
    idxs = [i for i, c in enumerate(spec.cols) if not c.lanes]
    if not idxs:
        return {}
    n = datas[idxs[0]].shape[0]
    sel = jnp.clip(take, 0, max(n - 1, 0))
    if len(idxs) == 1:
        return {idxs[0]: datas[idxs[0]][sel]}
    fmat = jnp.stack([datas[i] for i in idxs], axis=1)[sel]
    return {i: fmat[:, j] for j, i in enumerate(idxs)}


@staged("gather_rows")
def gather_columns(spec: LaneSpec, datas, valids, take):
    """Move whole rows by index: ONE (n, L) matrix gather for every laneable
    column + validity bits, plus ONE (n, K) f64 matrix gather batching all
    laneless (f64) columns (measured v5e: ~6 ns/row/col at K=5 vs ~16 for
    separate 1-D gathers).  ``take`` entries < 0 select row 0 (callers mask
    via validity).  Returns (datas, valids) aligned with the input order."""
    if not spec.cols:
        return (), ()
    n = datas[0].shape[0]
    sel = jnp.clip(take, 0, max(n - 1, 0))
    if spec.n_lanes:
        mat = pack_lanes(spec, datas, valids)
        out_d, out_v = unpack_lanes(spec, mat[sel])
        out_d, out_v = list(out_d), list(out_v)
    else:
        out_d = [None] * len(spec.cols)
        out_v = [None] * len(spec.cols)
    for i, d in gather_laneless(spec, datas, take).items():
        out_d[i] = d
    return tuple(out_d), tuple(out_v)
