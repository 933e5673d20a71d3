"""What the family files (and ``scripts/hash_programs.py``) share: the
benchmark join's specs and arguments, the standalone groupby's programs,
and what is read off a compiled program's text."""

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

#: rows per side per shard of the four-chip cell: 17 * 2^19, the receive
#: capacity of a 2^23-row shuffle (a capacity of config.pow2ceil's family
#: that is no power of two); 17,825,792 concat rows
_ROWS4 = 17 << 19


def _fold(builder) -> dict:
    """``fold=True`` for a builder of this tree: the cells' leading keys
    are int64 within int32 bounds, so padding sorts last INSIDE the key
    operand and no liveness operand is built (ISSUE 50).  A tree from
    before has no such word and sorts the operand
    (``scripts/hash_programs.py`` runs this module against both)."""
    import inspect
    known = "fold" in inspect.signature(builder).parameters
    return {"fold": True} if known else {}


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def _scan(shown: str):
    """``ops/groupby.SumScan`` from the way it prints: ``val32/64``."""
    from cylon_tpu.ops.groupby import SumScan
    word, _, block = shown.partition("/")
    return SumScan(word, int(block or 1))


def _wide_scans(compiled) -> list:
    """The ``reduce-window`` instructions of the optimised text that scan
    an (hi, lo) PAIR - what a 64-bit ``cumsum`` is on XLA:TPU."""
    import re
    return re.findall(r"(?m)^.* = \(.+\) reduce-window\(", compiled.as_text())


def _fused_args(mesh, n_side: int, layout):
    """vcl, vcr, idx_s, bnd and ``pl_s`` as ``layout`` lays it out: the
    kept sorted key (int32: a narrow key's operand), then the payload
    operands the two sides share."""
    from cylon_tpu.ctx.context import ROW_AXIS
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32, sharding=rep)
    idx = S((w * 2 * n_side,), np.int32, sharding=row)
    lane = S((w * 2 * n_side,), np.uint32, sharding=row)
    return vc, vc, idx, idx, (idx,) * len(layout.kept_keys) \
        + (lane,) * layout.n_payloads


def _join_specs(n_sums: int = 2):
    """Lane specs and payload layout of the benchmark's join, all int64
    within int32 bounds, tables under capacity: left (k, a), right (b) -
    or, for four sums, left (k, a, c), right (b, d).  The key's lane is
    the sorted key operand, padding's sentinel inside it; the others
    share one operand a pair."""
    from cylon_tpu.ops import join as joink, lanes
    nl = n_sums // 2
    lspec = lanes.plan_lanes(("int64",) * (1 + nl), (False,) * (1 + nl),
                             (True,) * (1 + nl))
    rspec = lanes.plan_lanes(("int64",) * nl, (False,) * nl, (True,) * nl)
    fold = _fold(joink.payload_layout)
    layout = joink.payload_layout(lspec, rspec, (0,), ("int64",), (False,),
                                  (True,), False, **fold)
    assert layout.sort_operands == 3 - len(fold) + nl
    assert layout.n_arrays == 1 + nl
    return lspec, rspec, layout


def _fused_static(n_sums: int = 2):
    """:func:`_join_specs` and the aggregations of the benchmark's query:
    sum(a), sum(b) by k (or the four sums)."""
    nl = n_sums // 2
    vspecs = tuple(("l", 1 + i, "sum") for i in range(nl)) \
        + tuple(("r", i, "sum") for i in range(nl))
    return _join_specs(n_sums) + (vspecs, (0,), (True,))


# ---- the standalone groupby with the window (ISSUE 31) ----------------------
# relational/groupby's two dispatch sites ask for the windowed gather under
# the fused path's rule; these are their programs as an eligible callsite
# runs them: one int64 sum by a narrow int64 key (benchmark cell
# groupby_sort_25m's query: 3 u32 lanes, padded to 8 for the kernel).


def _groupby_args(mesh, cap: int):
    from cylon_tpu.ctx.context import ROW_AXIS
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    col = S((w * cap,), np.int64, sharding=row)
    return S((w,), np.int32, sharding=rep), (col,), (None,), (col,), (None,)


def _groupby_program(mesh, site: str, seg_cap: int, window: int,
                     form: str = "val32/64"):
    """``form``: how the sum is scanned; ``val32`` in blocks of 64 is what
    the cell's bounded column gets (relational/groupby.sum_scan_form)."""
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import groupby as rel_gb
    vspec = lanes.plan_lanes(("int64", "int64"), (False, False), (True, True))
    if site == "combine":
        return rel_gb._combine_fn(mesh, ("sum",), seg_cap, False, (True,),
                                  (_scan(form),), vspec, (0,), window,
                                  **_fold(rel_gb._combine_fn))
    return rel_gb._raw_fn(mesh, (("sum", 0.5),), seg_cap, 1, False, (True,),
                          (_scan(form),), vspec, (0,), window,
                          **_fold(rel_gb._raw_fn))


# ---- the distributed groupby -> sort (ISSUE 44) ------------------------------
# What benchmark cell groupby_sort_25m_x4 launches on a mesh of four beside
# phase 1 (``_combine_fn``: test_exchange_four_chips.py): phase 2 of the
# two-phase groupby, whose sums scan as ``pair64`` - partial sums have no
# bounds - in blocks of 128 (PR 28: XLA:TPU's scan rewriter dies on long
# 64-bit scans of a multi-device program), and the sample sort's three
# builders with a two-operand int64 key.  A small shard: what the compiler
# refuses it refuses at any size, and its time grows with the rows.

_GS_SHARD = 17408
#: the cell's own phase 2 (ISSUE 45): a shard is the hash exchange's receive
#: capacity, the segment space the bucket of its ~15.09M groups a chip -
#: density 0.69, so ``pick_window`` gives 1024
_GS_CELL_SHARD, _GS_CELL_SEG = 22020096, 15204352


def _dist_sort_program(mesh, which: str, cap: int):
    """``(program, abstract args)`` of one builder of the cell's query: an
    int64 ``sum`` by a narrow int64 key, then a sort by the sum."""
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.ops import lanes, pack
    from cylon_tpu.relational import groupby as rel_gb, sort as rel_sort
    w = int(mesh.devices.size)
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((w,), np.int32, sharding=rep)
    col = S((w * cap,), np.int64, sharding=row)
    fold = _fold(rel_gb._final_fn)      # the key's bounds reach phase 2
    if which == "final":
        return (rel_gb._final_fn(mesh, ("sum",), cap, 1, (True,), **fold),
                (vc, (col,), (None,), ((col,),)))
    if which == "final_windowed":
        return (rel_gb._final_fn(mesh, ("sum",), _GS_CELL_SEG, 1, (True,),
                                 1024, **fold),
                (vc, (col,), (None,), ((col,),)))
    desc, npos, narrow = (False,), pack.NULL_LAST, (False,)
    if which == "sample":
        return (rel_sort._sample_fn(mesh, 64, desc, npos, narrow),
                (vc, (col,), (None,)))
    if which == "target":
        splitters = (S((w - 1,), np.int32, sharding=rep),
                     S((w - 1,), np.uint32, sharding=rep))
        return (rel_sort._target_fn(mesh, desc, npos, narrow),
                (vc, (col,), (None,), splitters))
    # the result of phase 2: the key and the sum, both wide by then (a
    # wide pair leads: the liveness operand stays)
    vspec = lanes.plan_lanes(("int64", "int64"), (False, False),
                             (False, False))
    return (rel_sort._local_sort_fn(mesh, desc, npos, narrow, vspec, (),
                                    (1,), False),
            (vc, (col, col), (None, None)))


# ---- the set operators (ISSUE 49) -------------------------------------------
# benchmark cell setops_dedup_32m's six programs at the cell's shapes: two
# int64 columns within int32 bounds (2 lanes, 8 rows for the kernel),
# 32,505,856-row shards, the seed-1 output capacities, window 2048 (the
# widest tiles span ~1,000 / ~620 / ~620 source rows: PERF.md §6, PR 49).

_SETOP_CAP = 32505856
_SETOP_OUT_CAP = {"unique": 19922944, "union": 50331648, "subtract": 22020096}


def _setop_programs(mesh, op: str, window: int = 2048):
    """``(count program, its arguments, materialize program, its
    arguments)`` of one operator of the cell (``unique`` keeps first by
    ``k``)."""
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.ops import lanes
    from cylon_tpu.relational import setops
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    vc = S((1,), np.int32, sharding=rep)
    col = S((_SETOP_CAP,), np.int64, sharding=row)
    two, none2 = (col, col), (None, None)
    spec = lanes.plan_lanes(("int64",) * 2, (False,) * 2, (True,) * 2)
    out_cap = _SETOP_OUT_CAP[op]
    if op == "unique":
        return (setops._unique_count_fn(mesh, "first", (True,),
                                        **_fold(setops._unique_count_fn)),
                (vc, (col,), (None,)),
                setops._unique_mat_fn(mesh, spec, out_cap, window),
                (vc, S((_SETOP_CAP,), np.int32, sharding=row), two, none2))
    srt = S((2 * _SETOP_CAP,), np.int32, sharding=row)
    return (setops._setop_count_fn(mesh, op, (True, True),
                                   **_fold(setops._setop_count_fn)),
            (vc, vc, two, none2, two, none2),
            setops._setop_mat_fn(mesh, op, spec, out_cap, window),
            (vc, srt, vc, two, none2, two, none2) if op == "union"
            else (vc, srt, two, none2))


def _check_setop_programs(mesh, op: str, rank_operands: int) -> None:
    """Both programs of ``op`` compile for the described chip: the count
    program holds the rank sort and ONE one-operand s32 sort and no
    scatter, the materialize program the kernel and neither."""
    import re
    from cylon_tpu.analysis.registry import unwrap
    count, cargs, mat, margs = _setop_programs(mesh, op)
    text = jax.jit(unwrap(count)).lower(*cargs).compile().as_text()
    sorts = re.findall(r"(?m)^.* = (\S+(?:, \S+)*) sort\(", text)
    assert sorted(s.count("[") for s in sorts) == [1, rank_operands], sorts
    assert " scatter(" not in text and "reduce-window" not in text
    compiled = jax.jit(unwrap(mat)).lower(*margs).compile()
    assert _has_kernel(compiled)
    text = compiled.as_text()
    assert " sort(" not in text and " scatter(" not in text
