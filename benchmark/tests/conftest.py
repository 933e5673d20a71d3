"""The benchmark's own tests run on the CPU (the sandbox has no chip):
pinned here, before anything imports jax, as ``tests/conftest.py`` does for
the program's tests.  Nothing in ``run.py`` selects the CPU."""

import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
for _p in (REPO_DIR, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)
