"""Keys and values of the upstream scaling driver: int64, uniform in
``[0, fraction * rows)`` (rivanna/scripts/cylon_scaling.py:31-37,
``np.random.randint(0, max_val, ...)`` with ``max_val = rows * unique``)."""

import numpy as np


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    high = max(int(rows * float(spec["fraction"])), 1)
    return rng.integers(0, high, rows).astype(np.dtype(spec["dtype"]))
