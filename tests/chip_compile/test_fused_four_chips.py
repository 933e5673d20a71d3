"""Four described chips: the fused join->groupby at first sight and as
settled (the rules: this package's docstring)."""

import pytest

import jax

from .helpers import (_ROWS4, _fused_args, _fused_static, _has_kernel, _scan,
                      _wide_scans)

# ---- four chips (ISSUE 28) --------------------------------------------------
# The distributed join->groupby's per-shard programs on the described 2x2
# mesh at the benchmark cell's size: 8,912,896 rows per side per shard
# (17 * 2^19, the receive capacity of a 2^23-row shuffle: a capacity of
# config.pow2ceil's family that is no power of two), 17,825,792 concat rows.
# XLA:TPU's scan rewriter dies with SIGSEGV, in-process and within a second
# of starting, on a fused program for four devices that holds about four
# LONG 64-bit scans (described compiles, PR 28; PERF.md): the parent
# (efc7d6d: two int64 sums + the two counts widened to int64) at 512 slots
# with the plain gather and with the windowed one alike; with the counts as
# int32 scans and nothing else changed, two and three int64 sums compile
# and four sums, or a mean beside a var, die again.  A death kills the test
# process, so only this tree's programs (ops/groupby.blocked_cumsum on a
# mesh of more than one device) are compiled here.


@pytest.mark.parametrize("n_sums,form", [(2, "pair64"), (4, "pair64"),
                                         (2, "val32/128")])
def test_first_sight_compiles_for_four_chips(mesh4, n_sums, form):
    """The first dispatch of a fused callsite: 512 segment slots, always
    XLA's gather (relational/groupby._FIRST_SEG_CAP).  Every four-chip run
    meets this program first - with the sums scanned as the cells' bounded
    columns are (``val32`` in blocks of 128, their values being under
    2^24: no 64-bit scan in the program at all) and as an unbounded
    column's (``pair64``, in blocks)."""
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import fused
    static = _fused_static(n_sums)
    prog = fused._fused_fn(mesh4, _ROWS4, False, *static, 512, 1,
                           sum_forms=(_scan(form),) * n_sums)
    compiled = compiler.aot_compile(
        prog, *_fused_args(mesh4, _ROWS4, static[2]))
    assert not _has_kernel(compiled)
    assert bool(_wide_scans(compiled)) == (form == "pair64")


@pytest.mark.parametrize("window,n_sums,form", [
    (4096, 2, "val32/128"), (0, 2, "val32/128"), (4096, 4, "val32"),
    (4096, 4, "pair64")])
def test_fused_compiles_for_four_chips(mesh4, monkeypatch, window, n_sums,
                                       form):
    """The settled dispatch at segment space 3,407,872 (density 0.2): with
    the windowed Pallas gather inside, as an eligible callsite runs it
    (the cell's two sums, and four), and with XLA's gather, as one below
    the density floor does.  The cells' sums are ``val32`` (ISSUE 40):
    32-bit scans - in blocks of 128, or flat as a column that uses int32's
    width gets them - and no (hi, lo) pair scan left for PR 28's rewriter
    fault to meet; four ``pair64`` sums are what the fault was found on
    and stay in blocks."""
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import fused
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    static = _fused_static(n_sums)
    prog = fused._fused_fn(mesh4, _ROWS4, False, *static, 3407872, 1, window,
                           sum_forms=(_scan(form),) * n_sums)
    compiled = compiler.aot_compile(
        prog, *_fused_args(mesh4, _ROWS4, static[2]))
    assert _has_kernel(compiled) == bool(window)
    assert bool(_wide_scans(compiled)) == (form == "pair64")
