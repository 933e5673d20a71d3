"""Four described chips: the exchange's programs, and the standalone
groupby's two sites with the window (the rules: this package's docstring)."""

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .helpers import (_groupby_args, _groupby_program, _has_kernel,
                      _wide_scans)


def _hlo_ops(text: str, opcode: str) -> int:
    """Instructions of one opcode in compiled HLO text (async pairs count
    once, by their ``-start``)."""
    import re
    return len(re.findall(rf"= \S+ {opcode}(?:-start)?\(", text))


@pytest.mark.parametrize("cap,lanes,block,out_cap,rounds", [
    (1 << 23, 2, 17 << 17, 17 << 19, 1),   # dist_join_groupby_8m_x4's shapes
    (1 << 21, 4, 17 << 13, 17 << 17, 3),   # groupby_sort_25m_x4's four lanes
    (1 << 21, 2, 17 << 15, 17 << 17, 1),
])
def test_shuffle_round_compiles_for_four_chips(mesh4, cap, lanes, block,
                                               out_cap, rounds):
    """One table's exchange, one u32 lane matrix: ``_prep_fn`` with the
    lanes riding its sort by target — ONE sort of ``1 + lanes`` operands,
    the (target, position) key in one word, so the sort is not stable and
    XLA:TPU adds no tie-break operand of its own — then ``_round_fn``: the
    send blocks as slices of the target-sorted rows, the all_to_all, each
    received block's valid prefix copied to its final place (``out_cap``
    the next capacity of config.pow2ceil's family).  Neither program holds
    a gather of rows or a scatter, and the rounds exactly the one
    all_to_all."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.parallel import shuffle
    w = 4
    rep, row = NamedSharding(mesh4, P()), NamedSharding(mesh4, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    mat = S((w * cap, lanes), np.uint32, sharding=row)
    assert shuffle.ride_rule((mat,)) == (1 + lanes, None)
    prep = compiler.aot_compile(
        shuffle._prep_fn(mesh4, w, True),
        S((w * cap,), np.int32, sharding=row), (mat,)).as_text()
    sorts = re.findall(r"^.* = (.*?) sort\(", prep, re.M)
    assert len(sorts) == 1, sorts
    assert re.findall(r"[su]32\[\d+\]", sorts[0]) \
        == ["u32[%d]" % cap] * (1 + lanes)
    text = compiler.aot_compile(
        shuffle._round_fn(mesh4, w, block, out_cap, rounds),
        S((w, w), np.int32, sharding=rep),
        (S((w * out_cap, lanes), np.uint32, sharding=row),), (mat,)).as_text()
    assert _hlo_ops(text, "all-to-all") == 1
    for program in (prep, text):
        # the count matrix's row and column for the members are picked by
        # two gathers of ``w`` numbers; no gather of rows
        assert set(re.findall(r"= (\S+?)\{\S* gather\(", program)) \
            <= {"s32[%d]" % w}
        assert "scatter" not in program


def test_shuffle_count_compiles_for_four_chips(mesh4):
    """The count sidecar at the cell's shard size: a dense
    compare-and-reduce, no scatter-add."""
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.parallel import shuffle
    row = NamedSharding(mesh4, P(ROW_AXIS))
    text = compiler.aot_compile(
        shuffle._count_fn(mesh4, 4),
        jax.ShapeDtypeStruct((4 << 23,), np.int32, sharding=row)).as_text()
    assert "scatter" not in text


@pytest.mark.parametrize("site,form", [("combine", "val32/64"),
                                       ("raw", "val32"), ("raw", "pair64")])
def test_windowed_groupby_compiles_for_four_chips(mesh4, monkeypatch, site,
                                                  form):
    """Phase 1 of the distributed associative groupby and the raw route on
    a mesh of four, with the kernel inside: forms no chip has run yet."""
    from cylon_tpu.exec import compiler
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compiler.aot_compile(
        _groupby_program(mesh4, site, 16384, 1024, form),
        *_groupby_args(mesh4, 17408))
    assert _has_kernel(compiled)
    assert bool(_wide_scans(compiled)) == (form == "pair64")
