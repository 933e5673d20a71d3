"""Multi-key sort + gather kernels.

TPU-native replacement for the reference's type-dispatched sort kernels
(cpp/src/cylon/arrow/arrow_kernels.hpp:53 ``IndexSortKernel``, :121
``SortIndicesMultiColumns``, util/sort.hpp introsort).  The reference emits a
per-type C++ comparator sort on the host; here ``jax.lax.sort`` is already a
multi-operand lexicographic bitonic sort on the VPU — multi-column ascending/
descending/nulls-first/last all become key-operand transforms built by
:func:`cylon_tpu.ops.pack.key_operands`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..utils.cache import jit
from ..utils.stages import staged


@staged("sort_keys")
def sort_permutation(keyops) -> jax.Array:
    """Stable argsort of rows under a :class:`~cylon_tpu.ops.pack.KeyOps`
    lexicographic operand list."""
    n = keyops.n
    idx = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort(keyops.ops + (idx,), num_keys=len(keyops.ops),
                       is_stable=True)
    return out[-1]


@staged("gather_rows")
def take_data(data: jax.Array, idx: jax.Array) -> jax.Array:
    """Gather rows; idx must be in-bounds (a permutation/selection)."""
    return data[idx]


@staged("gather_rows")
def take_with_nulls(data: jax.Array, validity, idx: jax.Array):
    """Gather rows where idx == -1 yields a null (outer-join null side).
    Returns (data, validity) with validity None when provably all-valid."""
    n = data.shape[0]
    safe = jnp.clip(idx, 0, max(n - 1, 0))
    g = data[safe]
    v = idx >= 0
    if validity is not None:
        v = v & validity[safe]
    return g, v


@partial(jit, static_argnames=("out_cap",))
@staged("compact")
def compact_by_flag(flag: jax.Array, out_cap: int):
    """Indices of rows with flag set, in original row order, padded to
    ``out_cap`` with -1; plus the true count.  The static-shape analog of the
    reference's growing Arrow index builders.  Sort-free: output positions
    are the exclusive prefix sum of the flags, materialized by one scatter."""
    n = flag.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    fi = flag.astype(jnp.int32)
    pos = (jnp.cumsum(fi) - fi).astype(jnp.int32)
    total = jnp.sum(fi, dtype=jnp.int32)
    scat = jnp.where(flag, pos, jnp.int32(out_cap))
    out = jnp.full(out_cap, -1, jnp.int32).at[scat].set(idx, mode="drop")
    return out, total
