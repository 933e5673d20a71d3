"""Set-semantic kernels: unique / union / intersect / subtract.

TPU-native replacement for the reference's row-set operators
(cpp/src/cylon/table.cpp ``Union`` :925, ``Subtract`` :997, ``Intersect``
:1051, ``Unique`` :1306) which build ska::bytell hash sets of row indices over
``TableRowIndexHash/EqualTo`` comparators.  Hash sets don't map to XLA; the
rank sort (keys, then the row index: stable) does the same work, and every
answer is read off the SORTED order, where each group of equal rows is one
run and its rows stand in source order:

* "first / last row of its group" is a comparison of a sorted row with its
  neighbour (:func:`~cylon_tpu.ops.pack.neighbor_flags`);
* "has the group a row of ``b``" is where ``b``'s rows stand in the run: the
  set operations that ask it rank ``[b; a]``, so a run that holds any row
  of ``b`` STARTS with one and ``a``'s rows follow in ``a``'s order.

No group id, no scan over segments, no scatter and no gather: the flags are
element-wise in the sorted order and leave it as sorted kept positions
(relational/setops.py, two-phase count -> materialize like :mod:`.join`).
"""

from __future__ import annotations

import jax.numpy as jnp

from ..utils.stages import staged


def _prev(x):
    """``x`` of the previous sorted row (row 0: False)."""
    return jnp.concatenate([jnp.zeros(1, bool), x[:-1]])


@staged("setop_flags")
def unique_flags(first, live, keep: str = "first"):
    """Flag the kept occurrence of each distinct row (reference Unique
    :1306 keep-first/last), in the rank sort's order.  ``first``: the sorted
    row starts its run; ``live``: it is no padding row (never flagged)."""
    if keep == "last":          # the next sorted row starts a run
        first = jnp.concatenate([first[1:], jnp.ones(1, bool)])
    return first & live


@staged("setop_flags")
def set_op_flags(first, live, is_b, op: str):
    """Flags in the rank sort's order selecting the output rows of a set
    operation (distinct semantics, matching the reference).  ``is_b``: the
    sorted row is one of ``b``'s.

    * union (ranked ``[a; b]``): first occurrence of each group (A
      preferred — A's rows come first in a run)
    * subtract (ranked ``[b; a]``): first A-occurrence of groups absent from
      B — an A row that starts its run
    * intersect (ranked ``[b; a]``): first A-occurrence of groups present in
      both — an A row inside a run, behind a row of B
    """
    if op == "union":
        return first & live
    if op == "subtract":
        return first & live & ~is_b
    if op == "intersect":
        return ~first & live & ~is_b & _prev(is_b)
    raise ValueError(f"unknown set op {op}")
