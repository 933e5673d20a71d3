"""``join__count_fn`` on one described chip and on four (the rules: this
package's docstring)."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .helpers import _ROWS4, _fold, _join_specs


@pytest.mark.parametrize("world,cap", [(1, 1 << 16), (4, _ROWS4)])
def test_join_count_compiles_with_one_sort_of_three(topo, world, cap):
    """``join__count_fn`` (slim: the deferred join's) in the shared-operand
    layout (ISSUE 35) at the benchmark's schema, on one described chip at
    the rehearsal's rows and on four at the cell's 8,912,896 a side: the
    optimised text holds ONE sort, of the layout's 3 operands - the key
    with padding's sentinel inside it (ISSUE 50: no liveness operand),
    ``idx``, the operand ``a`` and ``b`` share - so the stable sort's
    expansion added no tie-break ``iota`` of its own (``idx`` is one)."""
    import re
    from cylon_tpu.ctx.context import ROW_AXIS
    from cylon_tpu.exec import compiler
    from cylon_tpu.relational import join
    mesh = Mesh(np.array(topo.devices[:world]), (ROW_AXIS,))
    rep, row = NamedSharding(mesh, P()), NamedSharding(mesh, P(ROW_AXIS))
    S = jax.ShapeDtypeStruct
    lspec, rspec, layout = _join_specs(2)
    vc = S((world,), np.int32, sharding=rep)
    col = S((world * cap,), np.int64, sharding=row)
    prog = join._count_fn(mesh, "inner", (True,), lspec, rspec, layout,
                          False, True, **_fold(join._count_fn))
    text = compiler.aot_compile(
        prog, vc, vc, (col,), (None,), (col,), (None,), (col, col),
        (None, None), (col,), (None,)).as_text()
    sorts = re.findall(r"^.* = (.*?) sort\(", text, re.M)
    assert len(sorts) == 1, sorts
    results = re.findall(r"[su]32\[\d+\]", sorts[0])
    assert results == ["s32[%d]" % (2 * cap)] * 2 + ["u32[%d]" % (2 * cap)]
    assert len(results) == layout.sort_operands == 3
