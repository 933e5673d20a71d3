"""The comparison that decides ``correct``: every number compared has a
name, a value and a limit of its own, and a run is correct when no value
is over its limit.  Results are exact int64, so every limit here is 0."""

from __future__ import annotations

import numpy as np


def columns(got: dict, want: dict) -> list:
    """Exact, in order, column by column.  ``got``/``want``: name -> 1-d
    array, already in the order the query module's ``canonical`` and
    ``reference`` agree on.  Returns ``[(name, value, limit)]``."""
    out = [("columns_missing", len(set(want) - set(got)), 0)]
    n_got = {len(v) for v in got.values()}
    n_want = {len(v) for v in want.values()}
    rows_got = max(n_got) if n_got else 0
    rows_want = max(n_want) if n_want else 0
    out.append(("rows_diff", abs(rows_got - rows_want), 0))
    for name, w in want.items():
        g = got.get(name)
        if g is None:
            out.append((f"cells_differ.{name}", len(w), 0))
            continue
        g = np.asarray(g)
        w = np.asarray(w)
        n = min(len(g), len(w))
        bad = int(np.count_nonzero(g[:n] != w[:n])) + abs(len(g) - len(w))
        out.append((f"cells_differ.{name}", bad, 0))
        out.append((f"not_int64.{name}", int(g.dtype != np.int64), 0))
    return out


def verdict(numbers: list) -> bool:
    return all(v <= lim for _, v, lim in numbers)


def as_lines(numbers: list) -> list:
    return [f"compared {name} = {value} (limit {limit})"
            f"{'' if value <= limit else '  <-- OVER'}"
            for name, value, limit in numbers]


def as_dict(numbers: list) -> dict:
    return {name: {"value": value, "limit": limit}
            for name, value, limit in numbers}
