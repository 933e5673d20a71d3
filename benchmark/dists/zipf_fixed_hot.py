"""Skewed keys whose hot set belongs to the DEPLOYMENT, not to the run:
``dists/zipf.py``'s exact bounded Zipf over ``N = int(fraction * rows)``
keys (``P(rank r) ~ r**-s``, inverse CDF in float64, that file's ``cdf``),
with the rank -> key permutation of ``[0, N)`` drawn from
``numpy.random.default_rng(spec["hot_seed"])`` and not from the run's
stream.  The uniforms ``u`` still come from ``--seed``'s stream, so the
rows, their order, every key's count and the tail's sampling change with
the seed; which key is the hottest, the second hottest, ... does not - a
deployment's hot customers are the same from run to run.

Across chips that is what makes a cell measurable: the hash partitioner
sends each hot key whole to one chip, so WHICH chips own ranks 1, 2, 3, ...
sets the fullest chip's rows and with them the capacity bucket every
whole-shard program is compiled for.  With the permutation drawn per seed
the fullest of four chips holds 9.70M-15.18M of 33.5M probe rows over 24
seeds, seven buckets (ISSUE 34); with ``hot_seed`` fixed it holds the same
rows to within the draw's noise (a few thousand).  The configuration's
file says how its ``hot_seed`` was chosen."""

import os
import sys

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(_DIR) not in sys.path:
    sys.path.insert(0, os.path.dirname(_DIR))

from lib import files  # noqa: E402

_ZIPF = files.load_module(os.path.dirname(_DIR), "dists", "zipf")


def rank_to_key(n_keys: int, hot_seed: int) -> np.ndarray:
    """``key[r]`` of rank ``r + 1``: the deployment's own permutation."""
    return np.random.default_rng(int(hot_seed)).permutation(n_keys)


def draw(rng: np.random.Generator, rows: int, spec: dict) -> np.ndarray:
    n_keys = max(int(rows * float(spec["fraction"])), 1)
    u = rng.random(rows)
    rank = np.searchsorted(_ZIPF.cdf(n_keys, spec["s"]), u, side="right")
    np.minimum(rank, n_keys - 1, out=rank)    # cdf[-1] a rounding under 1
    return rank_to_key(n_keys, spec["hot_seed"])[rank].astype(
        np.dtype(spec["dtype"]))
