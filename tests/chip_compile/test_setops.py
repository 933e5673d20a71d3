"""One described chip: the set operations' programs of benchmark cell
``setops_dedup_32m`` at the cell's shapes - ``union`` and ``subtract``, a
count and a materialize program each (``unique``'s pair is
``test_one_chip.py``: a 3-operand sort of 65M rows compiles in 1-2 min, and
no file of this package holds a worker past ~300 s)."""

import pytest

import jax

from .helpers import _check_setop_programs


@pytest.mark.parametrize("op", ["union", "subtract"])
def test_setop_programs_compile_for_v5e(mesh1, monkeypatch, op):
    """The rank sort of 3 operands (``k`` with padding's sentinel inside it,
    ``v``, the row index) over both tables, the one-operand sort of the
    kept positions, no scatter; the union's source built in the
    materialize program and the windowed take inside it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    _check_setop_programs(mesh1, op, 3)
