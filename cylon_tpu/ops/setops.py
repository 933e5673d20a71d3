"""Set-semantic kernels: unique / union / intersect / subtract.

TPU-native replacement for the reference's row-set operators
(cpp/src/cylon/table.cpp ``Union`` :925, ``Subtract`` :997, ``Intersect``
:1051, ``Unique`` :1306) which build ska::bytell hash sets of row indices over
``TableRowIndexHash/EqualTo`` comparators.  Hash sets don't map to XLA; the
dense-rank (:mod:`.pack`) turns "row set membership" into integer segment
logic:

* rows of both tables are dense-ranked together → group id == row value;
* per-group presence flags (``in_a``/``in_b``) come from segment ORs;
* the surviving representative row per group is a segment-min of row index;
* compaction to the output is a stable sort by flag (static capacity).

All kernels are two-phase (count → materialize) like :mod:`.join`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..utils.cache import jit
from ..utils.stages import stage


@partial(jit, static_argnames=("keep",))
def unique_flags(gids, mask=None, keep: str = "first"):
    """Flag the kept occurrence of each distinct row (reference Unique
    :1306 keep-first/last).  gids: dense rank per row; masked rows never
    flagged."""
    n = gids.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    cap = n + 1
    g = gids if mask is None else jnp.where(mask, gids, jnp.int32(n))
    with stage("setop_flags"):
        if keep == "last":
            rep = jax.ops.segment_max(idx, g, num_segments=cap)
        else:
            rep = jax.ops.segment_min(idx, g, num_segments=cap)
        flag = rep[g] == idx
    if mask is not None:
        flag = flag & mask
    return flag


@partial(jit, static_argnames=("op",))
def set_op_flags(gids_cat, side_is_b, op: str, mask=None):
    """Flags over the concatenated rows of A then B selecting the output rows
    of a set operation (distinct semantics, matching the reference):

    * union:     first occurrence of each group (A preferred — A rows come
                 first in the concat, segment_min picks them)
    * intersect: first A-occurrence of groups present in both
    * subtract:  first A-occurrence of groups absent from B
    """
    if op not in ("union", "intersect", "subtract"):
        raise ValueError(f"unknown set op {op}")
    n = gids_cat.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    cap = n + 1
    g = gids_cat if mask is None else jnp.where(mask, gids_cat, jnp.int32(n))
    if op == "union":
        with stage("setop_flags"):
            first_any = jax.ops.segment_min(idx, g, num_segments=cap)
            flag = (first_any[g] == idx)
        if mask is not None:
            flag = flag & mask
        return flag
    a_row = (~side_is_b) if mask is None else ((~side_is_b) & mask)
    b_row = side_is_b if mask is None else (side_is_b & mask)
    with stage("setop_flags"):
        in_b = jax.ops.segment_max(b_row.astype(jnp.int32), g,
                                   num_segments=cap)
        # first A row of each group (n when group has no A row)
        first_a = jax.ops.segment_min(jnp.where(a_row, idx, jnp.int32(n)), g,
                                      num_segments=cap)
        if op == "intersect":
            flag = (first_a[g] == idx) & (in_b[g] > 0)
        else:
            flag = (first_a[g] == idx) & (in_b[g] == 0)
    return flag & a_row
