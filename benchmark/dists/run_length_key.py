"""A foreign key in runs: key ``g`` of ``0 .. groups - 1`` repeated
``min .. max`` times (uniform), keys in order, EXACTLY ``rows`` long -
``l_orderkey``: every order has 1-7 lines, and with 4 lines an order on
average 30,000,000 lines are 7,500,000 orders.  The drawn run lengths sum
to ``rows`` only to within a few thousand (a standard deviation of
``2 sqrt(groups)``); the difference is spread over as many runs drawn from
the same stream, one line each, that have the room - so every seed has the
same sizes, which the generator (``lib/generate.py``) and one set of
compiled shapes need."""

import numpy as np


def run_lengths(rng: np.random.Generator, rows: int, groups: int,
                lo: int, hi: int) -> np.ndarray:
    if not groups * lo <= rows <= groups * hi:
        raise ValueError(f"run_length_key: {groups} runs of {lo}..{hi} "
                         f"cannot be {rows} rows")
    lengths = rng.integers(lo, hi + 1, groups)
    while (diff := rows - int(lengths.sum())) != 0:
        step = 1 if diff > 0 else -1
        room = np.flatnonzero(lengths < hi if diff > 0 else lengths > lo)
        take = rng.choice(room, min(abs(diff), len(room)), replace=False)
        lengths[take] += step
    return lengths


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    groups = int(spec["groups"])
    lengths = run_lengths(rng, rows, groups, int(spec["min"]),
                          int(spec["max"]))
    return np.repeat(np.arange(groups, dtype=np.dtype(spec["dtype"])),
                     lengths)
