"""The upstream scaling driver's keys and values (``dists/uniform_fraction``:
int64, uniform in ``[0, fraction * rows)``) with the table's ROWS belonging
to the DEPLOYMENT, not to the run: the columns are the ones
``numpy.random.default_rng(spec["data_seed"])`` gives ``uniform_fraction``
(this column the ``spec["column"]``-th of that stream, every column of the
table the same distribution), and ``--seed`` decides the ORDER in which
each of ``spec["blocks"]`` contiguous blocks holds its rows - a chip's
partition: ``Table.from_pydict`` gives chip ``i`` the rows
``[i * chunk, (i + 1) * chunk)``, ``chunk = ceil(rows / blocks)``.  A block
is cut into ``PIECES`` runs of rows (a partition's row groups) and
the run's seed draws the order of the pieces, one permutation a block.
Which rows a chip holds does not change with the seed; where each lies in
the chip's memory does, and every column of a table takes the same order
(it is drawn from the run's seed, not from the stream's position), so a
row stays a row.  The pieces are copied whole: the ingest of 100M rows
costs two passes over memory, not 100M random reads.

Across chips that is what makes a groupby -> sort cell measurable.  The
engine's programs run at shapes and speeds that follow the data in steps:
the sample sort picks its splitters from 64 rows a chip, so the fullest
chip's share - and with it the capacity bucket that every chip's local
sort is compiled for and runs at - is another for another table (three
buckets over ten tables of one distribution, ISSUE 44), and the final
reduce's gather has two speeds by how many partial rows a chip received,
in steps of about a thousand.  A table drawn per seed gives runs that
differ by several percent (the check of PR 44 measured a spread of 2.6%
against a bound of 1%); the same rows in another order give every seed
the same work, because a chip's first step is a sort by key.  The
configuration's file says how its ``data_seed`` was chosen."""

import os
import sys

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(_DIR) not in sys.path:
    sys.path.insert(0, os.path.dirname(_DIR))

from lib import files  # noqa: E402

_UNIFORM = files.load_module(os.path.dirname(_DIR), "dists",
                             "uniform_fraction")

#: pieces a block is cut into: 48,828 or 48,829 rows each at 25M rows a chip
PIECES = 512


def fixed_column(rows: int, spec: dict) -> np.ndarray:
    """The deployment's own column: what ``lib/generate`` draws for it at
    ``--seed`` = ``data_seed`` from a table of ``uniform_fraction`` columns."""
    rng = np.random.default_rng(int(spec["data_seed"]))
    for _ in range(int(spec["column"])):
        _UNIFORM.draw(rng, rows, spec)
    return _UNIFORM.draw(rng, rows, spec)


def piece_spans(run_seed: int, rows: int, blocks: int):
    """``(lo, hi)`` of the source rows in the order the run holds them:
    inside each block its pieces in an order from the run's seed (one
    stream a block); no row leaves its block."""
    chunk = -(-rows // blocks)
    for i, lo in enumerate(range(0, rows, chunk)):
        cuts = lo + np.linspace(0, min(chunk, rows - lo), PIECES + 1).astype(
            np.int64)
        for p in np.random.default_rng([int(run_seed), i]).permutation(PIECES):
            yield cuts[p], cuts[p + 1]


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    col = fixed_column(rows, spec)
    run_seed = rng.bit_generator.seed_seq.entropy
    return np.concatenate([col[lo:hi] for lo, hi in piece_spans(
        run_seed, rows, int(spec["blocks"]))])
