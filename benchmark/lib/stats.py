"""The end-to-end arithmetic.  A rate is all the work over all the time of
the window; a tail is the tail of every query.  No best-of-N, no median of
pieces (``bench.py`` took ``min(times)``, which hides stalls: not copied)."""

from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """Nearest-rank percentile ``q`` in (0, 1] of ``values``; with fewer
    than ``1 / (1 - q)`` values it is the largest."""
    s = sorted(values)
    if not s:
        raise ValueError("no values")
    return s[min(len(s), max(1, math.ceil(q * len(s)))) - 1]
