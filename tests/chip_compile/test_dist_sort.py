"""Four described chips: the sample sort's three builders (the rules: this
package's docstring)."""

import pytest

from .helpers import _GS_SHARD, _dist_sort_program, _has_kernel


@pytest.mark.parametrize("which", ["sample", "target", "local_sort"])
def test_dist_groupby_sort_compiles_for_four_chips(mesh4, which):
    """``sort__sample_fn``, ``sort__target_fn`` and ``sort__local_sort_fn``
    for four described chips, at a small shard (``local_sort``: two
    minutes of XLA:TPU all the same)."""
    import re
    from cylon_tpu.exec import compiler
    program, args = _dist_sort_program(mesh4, which, _GS_SHARD)
    compiled = compiler.aot_compile(program, *args)
    text = compiled.as_text()
    assert not _has_kernel(compiled)
    sorts = re.findall(r"(?m)^.* = (.+) sort\(", text)
    if which == "local_sort":
        # ONE sort of eight operands, the one-chip cell's: liveness, the
        # key's (hi, lo), four payload lanes, XLA's own index for stability
        assert len(sorts) == 1 and sorts[0].count("[") == 8, sorts
    else:
        assert not sorts and " all-to-all(" not in text
