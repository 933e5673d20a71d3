"""Packed piece handles: window views over a lane-packed resident table.

The range-partitioned pipeline (exec/pipeline.py) packs each resident
sorted table into ONE u32 lane matrix (+ f64 side arrays) up front; every
range piece is then a contiguous per-shard window of that matrix.  The
seed materialized each window back into a full Table — dynamic-slice,
unpack EVERY column to full-width HBM arrays — only for the join to
immediately re-pack the keys into sort operands and the payloads into a
lane matrix.  That unpack→repack round trip was the single largest phase
of the pipelined join at the 125M-row operating point (round 5:
``pipe.piece_slice`` 3.74 s of 12.75 s).

:class:`PackedPiece` removes the wall: it is a pure HOST-SIDE descriptor
``(LaneSpec, lane matrix + f64 side arrays, per-shard starts/lens)`` —
producing one costs no device work at all.  ``join_tables`` /
``try_begin_join_groupby`` accept it in place of a materialized Table
(relational/join.py packed entry): the window slice and the lane unpack
happen *inside* the jitted join program, fused with key-operand
construction — keys unpack first, payload lanes ride the phase-1 sort and
unpack lazily in the carry/materialize stage, and columns the consumer
never reads are never unpacked (ops/lanes.unpack_column).

Ownership contract: the SOURCE (:class:`PieceSource`) owns the lane
matrix; every piece aliases it.  Pieces stay valid as long as the source's
arrays are alive — the pipeline holds the source for the whole range loop
and pieces never outlive it.  ``to_table()`` is the materialized escape
hatch (and the reference semantics the packed path is tested against).

Memory-pressure contract (exec/memory, docs/robustness.md): the packed
arrays register with the HBM ledger at pack time (spillable, LRU-touched
on every piece access).  A source whose registration has been EVICTED is
host-resident: ``packed()`` then uploads just the requested window back
to the device (``memory.upload_window`` — byte-identical to the resident
path's in-program dynamic slice, so results stay bit-equal) and the
pipelined range loop double-buffers those uploads against piece compute.
All residency changes go through the ledger (lint rule TS106).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .. import config
from ..core.column import Column
from ..core.table import Table
from ..ctx.context import ROW_AXIS
from ..utils.cache import jit, program_cache
from ..utils.stages import staged
from .common import REP, ROW

shard_map = jax.shard_map


@program_cache()
def _piece_pack_fn(mesh: Mesh, spec, pad: int, donate: bool = False):
    """Laneless (f64) columns pass ``None`` data — :func:`cylon_tpu.ops.
    lanes.pack_lanes` reads only their validity, and a dead donated
    buffer would otherwise be invalidated while :func:`_pad_rows_fn`
    still needs it (use-after-donate, lint rule TS108).  ``donate``
    consumes the caller's column buffers: the pack is their last reader
    (the pipeline deletes the sorted table right after), so XLA may
    free/reuse them DURING the pack instead of holding input + matrix
    live together."""
    from ..ops import lanes

    @staged("piece_pack")
    def per_shard(datas, valids):
        mat = lanes.pack_lanes(spec, list(datas), list(valids))
        if pad:
            mat = jnp.concatenate(
                [mat, jnp.zeros((pad, mat.shape[1]), mat.dtype)])
        return mat

    jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jit(shard_map(per_shard, mesh=mesh, in_specs=(ROW, ROW),
                             out_specs=ROW), **jit_kwargs)


@program_cache()
def _pad_rows_fn(mesh: Mesh, pad: int, donate: bool = False):
    @staged("piece_pad")
    def per_shard(d):
        return jnp.concatenate([d, jnp.zeros((pad,), d.dtype)]) if pad else d

    jit_kwargs = {"donate_argnums": (0,)} if donate else {}
    return jit(shard_map(per_shard, mesh=mesh, in_specs=ROW,
                             out_specs=ROW), **jit_kwargs)


@program_cache()
def _piece_slice_fn(mesh: Mesh, spec, piece_cap: int):
    """Each shard's contiguous window [start, start+piece_cap) of the
    once-packed lane matrix (+f64 side arrays): dynamic slices, no gathers.
    The matrix is padded by the max piece capacity, so slices never clamp."""
    from ..ops import lanes

    has_mat = spec.n_lanes > 0
    n_f64 = sum(1 for cl in spec.cols if not cl.lanes)

    @staged("piece_slice")
    def per_shard(starts, *arrs):
        my = jax.lax.axis_index(ROW_AXIS)
        s = starts[my]
        if has_mat:
            mat, f64s = arrs[0], arrs[1:]
            sub = lanes.slice_lanes(spec, mat, s, piece_cap)
            datas, valids = lanes.unpack_lanes(spec, sub)
            datas, valids = list(datas), list(valids)
        else:
            f64s = arrs
            datas = [None] * len(spec.cols)
            valids = [None] * len(spec.cols)
        j = 0
        for i, cl in enumerate(spec.cols):
            if not cl.lanes:
                datas[i] = jax.lax.dynamic_slice(f64s[j], (s,), (piece_cap,))
                j += 1
        return tuple(datas), tuple(valids)

    in_specs = (REP,) + (ROW,) * (int(has_mat) + n_f64)
    return jit(shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                             out_specs=(ROW, ROW)))


class PackedPiece:
    """A per-shard window ``[starts[s], starts[s]+piece_cap)`` over a
    :class:`PieceSource`'s packed arrays, of which the first ``lens[s]``
    rows are live.  Pure descriptor: holds references to the SOURCE's
    device arrays (no slice is dispatched until a consumer runs).

    ``meta`` entries are ``(name, LogicalType, dictionary, bounds)``
    parallel to ``spec.cols``.  ``reg`` (optional) is the source's HBM
    ledger registration — consumers LRU-touch it on access so eviction
    order tracks the piece loop (exec/memory)."""

    __slots__ = ("env", "spec", "meta", "arrs", "starts", "lens",
                 "piece_cap", "reg")

    def __init__(self, env, spec, meta, arrs, starts: np.ndarray,
                 lens: np.ndarray, piece_cap: int, reg=None):
        self.env = env
        self.spec = spec
        self.meta = meta
        self.arrs = arrs
        self.reg = reg
        self.starts = np.asarray(starts, np.int32)
        self.lens = np.asarray(lens, np.int64)
        self.piece_cap = int(piece_cap)
        if int(self.lens.max(initial=0)) > self.piece_cap:
            # a window wider than its static cap would silently truncate
            # live rows inside the jitted slice — typed so the consensus
            # ladder can take its deterministic cap-halving step
            from ..status import CapacityOverflowError
            raise CapacityOverflowError(
                f"piece window of {int(self.lens.max())} live rows exceeds "
                f"the pow2 piece cap {self.piece_cap}",
                site="join.piece_cap")

    @property
    def column_names(self) -> list[str]:
        return [n for n, _, _, _ in self.meta]

    @property
    def valid_counts(self) -> np.ndarray:
        return self.lens

    @property
    def row_count(self) -> int:
        return int(self.lens.sum())

    @property
    def capacity(self) -> int:
        return self.piece_cap

    def to_table(self) -> Table:
        """Materialize the window into a plain Table (dynamic slice + full
        unpack) — the reference path the packed consumers are exactly
        equal to, and the fallback when a consumer has no packed entry."""
        fn = _piece_slice_fn(self.env.mesh, self.spec, self.piece_cap)
        out_d, out_v = fn(self.starts, *self.arrs)
        cols = {}
        for (n, t, dc, nb), d, v in zip(self.meta, out_d, out_v):
            cols[n] = Column(d, t, v, dc, bounds=nb)
        return Table(cols, self.env, self.lens)


class PieceSource:
    """Range-piece provider over a resident sorted table: the table's
    columns pack into ONE u32 lane matrix up front (padded by the largest
    piece capacity so windows never clamp); each piece is then a pure
    host-side :class:`PackedPiece` window descriptor — producing a piece
    costs NO device work; the window slice runs inside whatever jitted
    program consumes it.  The caller should drop its reference to the
    source table: the matrix (plus f64 side arrays) carries everything.

    The packed arrays live in an HBM-ledger registration (spillable; see
    module docstring): ``scratch_bytes`` lets the caller fold the
    consumer's transient working set (sort operands,
    :func:`cylon_tpu.ops.pack.sort_operand_nbytes`) into the admission
    decision — the piece-cap-sizing consult of the ledger."""

    def __init__(self, table: Table, pad: int, drop: tuple = (),
                 scratch_bytes: int = 0, donate: bool = False):
        from ..exec import memory
        from .common import table_lane_spec
        self.env = table.env
        items = [(n, c) for n, c in table.columns.items() if n not in drop]
        cols = [c for _, c in items]
        self.spec = table_lane_spec(cols)
        self.meta = tuple(
            (n, c.type, c.dictionary,
             (min(c.bounds[0], 0), max(c.bounds[1], 0))
             if c.bounds is not None else None)
            for n, c in items)
        mesh = self.env.mesh
        w = self.env.world_size
        rows = w * (table.capacity + int(pad))
        # laneless (f64) columns contribute no data lane: their data rides
        # the side-array path (_pad_rows_fn) and must NOT enter the pack
        # program at all — under donation, a dead donated buffer would be
        # invalidated before _pad_rows_fn reads it (TS108)
        lane_datas = tuple(c.data if cl.lanes else None
                           for c, cl in zip(cols, self.spec.cols))
        valids = tuple(c.validity for c in cols)
        reuse = 0
        if donate:
            # donated column buffers are consumed by the pack programs —
            # the ledger must not count them AND the matrices they become
            # as simultaneous peak (docs/pipeline.md donation rules).
            # Count exactly what is donated: lane data + validity through
            # the pack program (only built when lanes exist), f64 side
            # data through the pad program.
            donated = list(c.data for c, cl in zip(cols, self.spec.cols)
                           if not cl.lanes)
            if self.spec.n_lanes:
                donated += [a for a in (*lane_datas, *valids)
                            if a is not None]
            reuse = sum(int(a.nbytes) for a in donated)
        # admission is SCHEDULER-mediated (lint rule TS109): the serving
        # tier attributes the bytes to the current tenant before routing
        # to the ledger's consensus-coherent admission path
        from ..exec import scheduler
        scheduler.admit_allocation(
            self.env, rows * memory.spec_row_bytes(self.spec),
            scratch=int(scratch_bytes), reuse=reuse)
        arrs = []
        if self.spec.n_lanes:
            arrs.append(_piece_pack_fn(mesh, self.spec, pad, donate)(
                lane_datas, valids))
        for c, cl in zip(cols, self.spec.cols):
            if not cl.lanes:
                arrs.append(_pad_rows_fn(mesh, pad, donate)(c.data))
        self._reg = memory.register("piece_src", tuple(arrs),
                                    spillable=True,
                                    sharding=self.env.sharding(),
                                    anchor=self)

    @property
    def arrs(self) -> tuple | None:
        """Device arrays while resident, None while spilled to host."""
        from ..exec import memory
        return memory.device_arrays(self._reg)

    @property
    def spilled(self) -> bool:
        return self._reg.spilled

    def packed(self, starts: np.ndarray, lens: np.ndarray,
               piece_cap: int | None = None) -> PackedPiece:
        from ..exec import memory
        if piece_cap is None:
            piece_cap = config.pow2ceil(max(int(lens.max(initial=0)), 1))
        memory.touch(self._reg)
        if not self._reg.spilled:
            return PackedPiece(self.env, self.spec, self.meta, self.arrs,
                               starts, lens, piece_cap, reg=self._reg)
        # host-resident source: upload ONLY this window (async dispatch —
        # the pipelined loop prefetches piece r+1 so this overlaps piece
        # r's compute); the uploaded arrays ARE the window, so the
        # in-program slice starts at 0
        w = self.env.world_size
        arrs = memory.upload_window(self._reg, np.asarray(starts, np.int64),
                                    int(piece_cap))
        return PackedPiece(self.env, self.spec, self.meta, arrs,
                           np.zeros(w, np.int32), lens, piece_cap,
                           reg=self._reg)

    def piece(self, starts: np.ndarray, lens: np.ndarray) -> Table:
        """Materialized window (seed behavior): slice + full unpack."""
        return self.packed(starts, lens).to_table()


# ---------------------------------------------------------------------------
# trace-safety declarations (cylon_tpu.analysis.registry): the piece
# programs are pure-local shard programs — slices and lane (un)packing
# only, no collectives, no host callbacks.  docs/trace_safety.md.
# ---------------------------------------------------------------------------

def _decl_spec():
    from ..ops import lanes
    # one nullable int32 lane column + one f64 side column: exercises the
    # matrix, the validity lane, and the side-array path without any
    # int64 lane reconstruction (which would trip JX203 by design)
    return lanes.plan_lanes(("int32", "float64"), (True, False))


def _trace_piece_pack(mesh):
    import jax as _jax
    w = int(mesh.devices.size)
    cap, S = 1024, _jax.ShapeDtypeStruct
    spec = _decl_spec()
    fn = _unwrap(_piece_pack_fn(mesh, spec, 8))
    # laneless (f64) data never enters the pack program (None leaf —
    # its buffer rides _pad_rows_fn and may be donated there, TS108)
    datas = (S((w * cap,), np.int32), None)
    valids = (S((w * cap,), np.bool_), None)
    return _jax.make_jaxpr(fn)(datas, valids)


def _trace_pad_rows(mesh):
    import jax as _jax
    w = int(mesh.devices.size)
    cap, S = 1024, _jax.ShapeDtypeStruct
    fn = _unwrap(_pad_rows_fn(mesh, 8))
    return _jax.make_jaxpr(fn)(S((w * cap,), np.float64))


def _trace_piece_slice(mesh):
    import jax as _jax
    w = int(mesh.devices.size)
    cap, S = 1024, _jax.ShapeDtypeStruct
    spec = _decl_spec()
    fn = _unwrap(_piece_slice_fn(mesh, spec, 256))
    starts = S((w,), np.int32)
    mat = S((w * (cap + 8), spec.n_lanes), np.uint32)
    f64 = S((w * (cap + 8),), np.float64)
    return _jax.make_jaxpr(fn)(starts, mat, f64)


from ..analysis.registry import declare_builder, unwrap as _unwrap  # noqa: E402

declare_builder(f"{__name__}._piece_pack_fn", _trace_piece_pack,
                tags=("pipeline",))
declare_builder(f"{__name__}._pad_rows_fn", _trace_pad_rows,
                tags=("pipeline",))
# keyed on (lane spec x pow2 piece capacity) — a wider legitimate family
# than the mesh-keyed builders, like join._count_fn
declare_builder(f"{__name__}._piece_slice_fn", _trace_piece_slice,
                tags=("pipeline",), retrace_budget=64)
