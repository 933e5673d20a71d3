"""Whole numbers uniform over the closed range ``lo, lo + step, ..., hi``:
money in cents (``l_extendedprice`` 900.00-105,000.00 is ``lo`` 90000,
``hi`` 10500000), a discount in hundredths (0-10), a foreign key over a
dense primary key (``o_custkey``: 0 to customers - 1), a delay in days."""

import numpy as np


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    lo, hi, step = int(spec["lo"]), int(spec["hi"]), int(spec.get("step", 1))
    if step < 1 or hi < lo or (hi - lo) % step:
        raise ValueError(f"uniform_int: {lo}..{hi} step {step}")
    k = rng.integers(0, (hi - lo) // step + 1, rows)
    return (lo + step * k).astype(np.dtype(spec["dtype"]))
