"""Four described chips: phase 2 of the distributed groupby, at a small
shard and as the cell settles it (the rules: this package's docstring)."""

import pytest

import jax

from .helpers import (_GS_CELL_SHARD, _GS_SHARD, _dist_sort_program,
                      _has_kernel, _wide_scans)


@pytest.mark.parametrize("which", ["final", "final_windowed"])
def test_dist_groupby_sort_compiles_for_four_chips(mesh4, monkeypatch, which):
    """``groupby__final_fn`` for four described chips.  Phase 2's 64-bit
    scans are all in blocks: no (hi, lo) pair ``reduce-window`` runs the
    length of a shard, the form the rewriter dies on.  ``final_windowed``:
    phase 2 as ``dispatch_at_bucket`` settles it in the cell, at the cell's
    own shapes - the segment space at the groups' bucket, the windowed
    Pallas take inside (about a minute of XLA:TPU)."""
    import re
    from cylon_tpu.exec import compiler
    from cylon_tpu.ops import groupby as gbk
    windowed = which == "final_windowed"
    shard = _GS_CELL_SHARD if windowed else _GS_SHARD
    if windowed:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    program, args = _dist_sort_program(mesh4, which, shard)
    compiled = compiler.aot_compile(program, *args)
    assert _has_kernel(compiled) == windowed
    wide = _wide_scans(compiled)
    assert wide                       # pair64: the sums ARE 64-bit scans
    for line in wide:
        shapes = re.findall(r"[su]32\[([\d,]+)\]", line.split(
            " reduce-window(")[0])
        assert shapes and all(
            max(int(d) for d in s.split(",")) * gbk._SCAN_BLOCK
            <= 2 * shard for s in shapes), line
