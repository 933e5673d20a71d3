"""The lower-precision control comes out not correct, at the cells' own
sizes (float32 is exact below 2^24, so a tiny table cannot show it; numpy
only, about ten seconds a cell), and the sound reference comes out correct
under the same comparison."""

from __future__ import annotations

import os

import pytest

import control
from lib import compare, files, generate

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("cell", ["join_groupby_32m", "groupby_sort_25m"])
def test_float32_sums_are_caught(cell):
    numbers = control.control_numbers(BENCH_DIR, cell, seed=2**31 + 3)
    assert not compare.verdict(numbers)
    over = {n: v for n, v, lim in numbers if v > lim}
    # rounding moves hundreds of thousands of sums, never the membership
    assert min(v for n, v in over.items() if "_sum" in n) > 100_000
    assert dict((n, v) for n, v, _ in numbers)["rows_diff"] == 0


def test_sound_reference_passes_its_own_comparison():
    cell = files.load_json(BENCH_DIR, "workloads", "groupby_sort_25m")
    cfg = files.load_json(BENCH_DIR, "configs", cell["config"])
    for t in cfg["tables"].values():
        t["rows"] = 200_000
    qm = files.load_module(BENCH_DIR, "queries", cell["query"])
    host = generate.host_tables(BENCH_DIR, cfg, 9)
    ref = qm.reference(host, cfg["query"], 9)
    numbers = compare.columns(qm.canonical(ref, cfg["query"], 9), ref) \
        + qm.extra_numbers(host, ref, cfg["query"])
    assert compare.verdict(numbers), numbers
