"""A uniform choice from a closed vocabulary of ``n`` words, as its code
``0 .. n - 1`` (int32): a dictionary-encoded column as it is born.  The
words are the deployment's and live with the query's parameters
(``query.vocabulary`` of the configuration), in code order; no string is
made a row (30M ``<U17`` values would be 2 GB)."""

import numpy as np


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    n = int(spec["n"])
    if n < 1:
        raise ValueError("choice: an empty vocabulary")
    return rng.integers(0, n, rows).astype(np.int32)
