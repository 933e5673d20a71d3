"""The one general generator: host tables from a configuration file and
``--seed``.

A configuration's ``tables`` block lists, per table, its row count and its
columns in order; each column names a distribution, and a distribution is a
file ``dists/<name>.py`` with ``draw(rng, rows, spec)``.  One
``numpy.random.default_rng(seed)`` stream feeds every column in file order,
so the same seed gives the same tables, and every seed the same sizes.
(After ``bench.py:87-110``, which seeded a constant 42; for the join
configuration and a given seed the arrays equal ``chip_smoke.make_inputs``'.)
"""

from __future__ import annotations

import numpy as np

from . import files


def host_tables(bench_dir: str, config: dict, seed: int) -> dict:
    """``{table: {column: array}}`` for ``config`` from ``seed``."""
    rng = np.random.default_rng(int(seed))
    out = {}
    for tname, tspec in config["tables"].items():
        rows = int(tspec["rows"])
        cols = {}
        for cname, cspec in tspec["columns"]:
            dist = files.load_module(bench_dir, "dists", cspec["dist"])
            col = dist.draw(rng, rows, cspec)
            if col.shape != (rows,):
                raise ValueError(f"{tname}.{cname}: {col.shape} != ({rows},)")
            cols[cname] = col
        out[tname] = cols
    return out


def input_rows(config: dict) -> int:
    """Input rows of one query: every table's rows."""
    return sum(int(t["rows"]) for t in config["tables"].values())
