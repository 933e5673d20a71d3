"""Skewed keys: exact bounded Zipf over ``N = int(fraction * rows)`` keys,
``P(rank r) ~ r**-s`` for ``r = 1..N``, drawn by inverse CDF in float64
(no rejection, no approximation of the tail).  The ranks are then renamed
through a permutation of ``[0, N)`` drawn from the same stream, so the hot
set is fixed by the seed and is not the keys 0, 1, 2, ...: the domain is
the uniform columns' ``[0, fraction * rows)``
(rivanna/scripts/cylon_scaling.py:31-37), only the frequencies differ.

At 32,000,000 rows, fraction 0.9, s = 1.1: H(N, s) = 8.789, the hottest
key holds 1/H = 11.4% of the rows, the top 1,000 keys 63%, and 3.16M of
the 28.8M keys appear at all (numpy, seed 7; ~14 s a column)."""

import numpy as np


def cdf(n_keys: int, s: float) -> np.ndarray:
    """``cdf[r] = P(rank <= r + 1)``; ``cdf[-1]`` is 1 up to rounding."""
    c = np.cumsum(np.arange(1, n_keys + 1, dtype=np.float64) ** -float(s))
    c /= c[-1]
    return c


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    n_keys = max(int(rows * float(spec["fraction"])), 1)
    u = rng.random(rows)
    rank = np.searchsorted(cdf(n_keys, spec["s"]), u, side="right")
    np.minimum(rank, n_keys - 1, out=rank)    # cdf[-1] a rounding under 1
    return rng.permutation(n_keys)[rank].astype(np.dtype(spec["dtype"]))
