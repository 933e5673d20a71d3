"""Segment starts are one one-operand sort (ISSUE 33):
``ops/groupby.grouped_starts`` equals the scatter it replaced, kept here
as the plain reference, element for element.  What the programs hold
under ``segment_starts`` is ``tests/test_stages.py``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cylon_tpu.ops import groupby as gbk


def _scatter_starts(gids, first, mask, n_live, seg_cap):
    """The statement until PR 33 (``ops/groupby.py`` and, written a
    second time, ``relational/fused.py``): one candidate update a row."""
    pos = jnp.arange(gids.shape[0], dtype=jnp.int32)
    scat = jnp.where(first & mask, gids, jnp.int32(seg_cap))
    return jnp.full(seg_cap, n_live, jnp.int32).at[scat].set(pos,
                                                             mode="drop")


def _grouped(n, n_live, first):
    """(gids, first, mask) as the three producers make them: ids are
    ``cumsum(first & mask) - 1``, dead rows routed to ``n``."""
    first = np.asarray(first, bool)
    mask = np.arange(n) < n_live
    gid = np.cumsum(first & mask).astype(np.int32) - 1
    return np.where(mask, gid, n).astype(np.int32), first, mask


def _random_first(n, density, seed=33):
    first = np.random.default_rng(seed).random(n) < density
    first[:1] = True
    return first


# name -> (n, n_live, first flags BEFORE masking, seg_cap)
_CASES = {
    "random_dead_tail": (4096, 3000, _random_first(4096, 0.2), 1024),
    "random_dense": (4096, 4000, _random_first(4096, 0.6), 4096),
    "n_live_0": (1024, 0, _random_first(1024, 0.3), 512),
    "n_live_1": (1024, 1, _random_first(1024, 0.3), 512),
    "n_live_N": (1024, 1024, _random_first(1024, 0.3), 512),
    "one_group": (1024, 900, np.arange(1024) == 0, 512),
    "every_row_its_own_group": (1024, 1000, np.ones(1024, bool), 1024),
    # first sight of a callsite: 512 slots, more groups than that
    "seg_cap_below_groups": (8192, 8000, _random_first(8192, 0.5), 512),
    "seg_cap_above_N": (100, 80, _random_first(100, 0.4), 512),
    # first flags set on dead rows too: the mask has to take them out
    "masked_first_rows": (2048, 1500, np.ones(2048, bool), 2048),
    "no_first_at_0": (1024, 1000,
                      np.arange(1024) % 7 == 3, 512),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_grouped_starts_equals_the_scatter(case):
    n, n_live, first, seg_cap = _CASES[case]
    gids, first, mask = _grouped(n, n_live, first)
    want = _scatter_starts(jnp.asarray(gids), jnp.asarray(first),
                           jnp.asarray(mask), jnp.int32(n_live), seg_cap)
    got = jax.jit(gbk.grouped_starts, static_argnums=3)(
        jnp.asarray(first), jnp.asarray(mask), jnp.int32(n_live), seg_cap)
    assert got.dtype == jnp.int32 and got.shape == (seg_cap,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    n_groups = int((first & mask).sum())
    assert (np.asarray(got)[min(n_groups, seg_cap):] == n_live).all()
