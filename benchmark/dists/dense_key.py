"""A dense primary key: ``0, 1, ..., rows - 1`` in row order (TPC-H's
``c_custkey``, ``o_orderkey``, ``s_suppkey``, ``n_nationkey``,
``r_regionkey`` as ``cylon_tpu/tpch.py`` makes them).  Nothing is drawn:
the key is the row's place, for every seed."""

import numpy as np


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    return np.arange(rows, dtype=np.dtype(spec["dtype"]))
