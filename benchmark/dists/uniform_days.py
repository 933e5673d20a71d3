"""Dates uniform over the days of ``[lo, hi)`` (ISO dates), as
``datetime64[ns]`` at midnight - ``o_orderdate`` as ``cylon_tpu/tpch.py``
draws it (1992-01-01 up to 1998-08-02)."""

import numpy as np


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    lo = np.datetime64(spec["lo"], "D")
    days = int((np.datetime64(spec["hi"], "D") - lo).astype(np.int64))
    if days < 1:
        raise ValueError(f"uniform_days: [{spec['lo']}, {spec['hi']}) is empty")
    return (lo + rng.integers(0, days, rows)).astype("datetime64[ns]")
