"""The skewed-key cell across chips (PR 34), on the CPU: the distribution
whose hot set is the configuration's (``dists/zipf_fixed_hot.py``), the
configuration against its uniform sibling, the chosen ``hot_seed``'s rows a
chip recomputed under the engine's own routing hash, and the cell's tiny
twin through ``run.py`` on four CPU devices (a process of its own: the
device count is fixed when jax starts), traced, with the exchange's new
numbers on its line."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import helpers
from lib import files

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "cylon_join_zipf_8m_x4"
CELL = "dist_join_groupby_8m_zipf_x4"
ROWS = 400_000


@pytest.fixture(scope="module")
def dist():
    return files.load_module(BENCH_DIR, "dists", "zipf_fixed_hot")


@pytest.fixture(scope="module")
def spec():
    cfg = files.load_json(BENCH_DIR, "configs", CONFIG)
    return cfg["tables"]["left"]["columns"][0][1]


def _counts(dist, spec, seed, rows=ROWS):
    keys = dist.draw(np.random.default_rng(seed), rows, spec)
    assert keys.dtype == np.int64 and keys.shape == (rows,)
    return keys, np.bincount(keys, minlength=int(rows * spec["fraction"]))


def test_hot_set_is_the_configurations_and_rows_are_the_seeds(dist, spec):
    """One ``hot_seed``, two ``--seed``s: the same keys are hottest, in
    the same order, each with its share ``r**-s / H`` to 1-2%; the rows
    differ.  Another ``hot_seed`` renames them."""
    n_keys, s = int(ROWS * spec["fraction"]), spec["s"]
    h = float(np.sum(np.arange(1, n_keys + 1, dtype=np.float64) ** -s))
    ka, ca = _counts(dist, spec, 2**31 + 5)
    kb, cb = _counts(dist, spec, 2**31 + 6)
    assert not np.array_equal(ka, kb) and not np.array_equal(ca, cb)
    hot = dist.rank_to_key(n_keys, spec["hot_seed"])[:5]
    np.testing.assert_array_equal(np.argsort(ca)[::-1][:5], hot)
    np.testing.assert_array_equal(np.argsort(cb)[::-1][:5], hot)
    for counts in (ca, cb):
        for rank, tol in ((1, 0.01), (2, 0.02), (3, 0.02)):
            want = rank ** -s / h
            assert abs(counts[hot[rank - 1]] / ROWS - want) < tol * want
    assert not set(hot.tolist()) & set(range(5))      # not keys 0, 1, 2 ...
    _, other = _counts(dist, dict(spec, hot_seed=spec["hot_seed"] + 1), 2**31 + 5)
    assert len(set(np.argsort(other)[-5:].tolist()) & set(hot.tolist())) <= 1
    # the same --seed and hot_seed give the same array
    np.testing.assert_array_equal(ka, _counts(dist, spec, 2**31 + 5)[0])


def test_configuration_is_the_uniform_siblings_but_for_the_probe_key(spec):
    cfg = files.load_json(BENCH_DIR, "configs", CONFIG)
    sibling = files.load_json(BENCH_DIR, "configs", "cylon_join_uniform_8m_x4")
    one_chip = files.load_json(BENCH_DIR, "configs", "cylon_join_zipf_32m")
    for key in ("world_size", "query", "guarantees"):
        assert cfg[key] == sibling[key], key
    for tname, tab in cfg["tables"].items():
        assert tab["rows"] == sibling["tables"][tname]["rows"] == 1 << 25
        for (name, got), (sname, want) in zip(
                tab["columns"], sibling["tables"][tname]["columns"]):
            assert name == sname
            if (tname, name) != ("left", "k"):
                assert got == want, (tname, name)
    # the probe key: the one-chip skewed configuration's, hot set fixed
    z = dict(one_chip["tables"]["left"]["columns"][0][1])
    assert spec == dict(z, dist="zipf_fixed_hot", hot_seed=spec["hot_seed"])
    assert sorted(cfg["reduced"]) == ["rows", "world_size"]
    assert {"hot_set", "hot_seed", "s", "skew_side"} <= set(cfg["assumed"])
    assert cfg["assumed"]["hot_seed"].startswith(f"{spec['hot_seed']}:")
    cell = files.load_json(BENCH_DIR, "workloads", CELL)
    uniform_cell = files.load_json(BENCH_DIR, "workloads",
                                   "dist_join_groupby_8m_x4")
    for key in ("query", "loop", "chips", "traffic"):
        assert cell[key] == uniform_cell[key], key
    assert cell["expect"]["exchange"] == uniform_cell["expect"]["exchange"]
    assert cell["expect"]["windowed_gather_if_eligible"] is True


def _chip_of(keys: np.ndarray, world: int = 4) -> np.ndarray:
    """The chip the engine's routing hash sends each int64 key to
    (``ops/hashing.hash_rows`` + ``partition_targets``, on the CPU)."""
    import jax
    from cylon_tpu.ops import hashing
    return np.asarray(jax.jit(lambda k: hashing.partition_targets(
        hashing.hash_rows([k], [None]), world))(keys))


def _fullest_chip(dist, spec, hot_seed, rows=1 << 25, world=4, top=2_000_000):
    """Expected probe rows a chip: the ``top`` hottest ranks placed by the
    engine's routing hash, the tail spread evenly."""
    n_keys = int(rows * spec["fraction"])
    p = np.arange(1, n_keys + 1, dtype=np.float64) ** -spec["s"]
    p /= p.sum()
    tgt = _chip_of(dist.rank_to_key(n_keys, hot_seed)[:top].astype(np.int64),
                   world)
    return np.bincount(tgt, weights=rows * p[:top], minlength=world) \
        + rows * p[top:].sum() / world


def test_hot_seed_puts_the_fullest_chip_inside_the_stated_bucket(dist, spec):
    """``hot_seed`` is the smallest whose fullest chip falls in the
    receive-capacity bucket 11,534,336, 100,000 rows clear of both edges;
    the numbers are the configuration file's."""
    from cylon_tpu import config
    lo, hi = 11_010_048, 11_534_336
    assert config.pow2ceil(lo + 1) == hi and config.pow2ceil(lo) == lo
    per = _fullest_chip(dist, spec, spec["hot_seed"])
    assert lo + 100_000 < per.max() <= hi - 100_000
    assert 1.31 < per.max() / (1 << 23) < 1.37
    assert int(np.argmax(per)) == 1
    said = files.load_json(BENCH_DIR, "configs", CONFIG)["assumed"]["hot_seed"]
    assert f"{int(per.max()):,}" in said
    for earlier in range(spec["hot_seed"]):
        m = _fullest_chip(dist, spec, earlier).max()
        assert not lo + 100_000 < m <= hi - 100_000, earlier
        assert f"{int(m):,}" in said, earlier


_DRIVER = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
bench_dir, tests_dir, repo_dir = sys.argv[1:4]
sys.path[:0] = [repo_dir, bench_dir, tests_dir]
import helpers
run = helpers.load_run(bench_dir)
import cylon_tpu as ct
from cylon_tpu.ctx.context import CPUMeshConfig
run.check_device = lambda chips: jax.devices("cpu")[:chips]
run.make_env = lambda chips: ct.CylonEnv(config=CPUMeshConfig(world_size=chips))
traced = run._traced_queries
def traced_on_cpu(one, n, spans, trace_dir):
    # the real profiler, so that the program's host spans are in a trace
    # file of this run; a CPU trace has no device plane to reduce
    try:
        return traced(one, n, spans, trace_dir)
    except RuntimeError as e:
        assert "no device operation" in str(e), e
        return {"n_queries": n, "n_chips": 4, "busy_s": 0.9, "window_s": 1.0,
                "idle_share": 0.1, "op_seconds": [], "gap_seconds": []}
run._traced_queries = traced_on_cpu
sys.exit(run.main(["--workload", sys.argv[4], "--seed", sys.argv[5],
                   "--seconds", "0.5", "--trace", sys.argv[6]]))
"""


def _twin(tmp_path, seed: int, trace: int):
    bench_dir = helpers.copy_with_tiny_cells(tmp_path)
    helpers.twin_metrics_of(bench_dir, CELL)
    proc = subprocess.run(
        [sys.executable, "-c", _DRIVER, bench_dir,
         os.path.dirname(os.path.abspath(__file__)),
         os.path.dirname(BENCH_DIR), "tiny_" + CELL, str(seed), str(trace)],
        capture_output=True, text=True, timeout=600,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    return helpers.last_json_line(proc.stdout), proc.stderr, bench_dir


def test_tiny_twin_on_four_cpu_devices_traced(tmp_path):
    """65,536 rows a side over four devices, ``--trace 1``: correct, the
    routes of the workload file with no environment variable set, two
    exchanges a query, and the cell's metrics that need no device plane -
    ``recv_max`` equal to numpy's own per-destination count under the
    engine's hash."""
    seed = 2**31 + 34
    line, err, bench_dir = _twin(tmp_path, seed, trace=1)
    assert line["correct"] is True, line["compared"]
    assert all(v["value"] <= v["limit"] for v in line["compared"].values())
    assert line["compared"]["exchanges_per_query_off"]["value"] == 0
    assert 'routes: [["join", "hash"], ["shuffle", null], ["shuffle", null], ' \
        '["groupby", "fused_pushdown"]]' in err
    m = {k[5:]: v["value"] for k, v in line["metrics"].items()
         if k.startswith("tiny_")}
    assert {"dzipf_recv_max_mrows_per_query", "dzipf_recv_cap_mrows_per_query",
            "dzipf_exchange_block_mrows_per_query",
            "dzipf_split_keys_per_join", "exchange_mb_per_query",
            # the registry's counters, which every operator metric of the
            # cell that reads one finds on the CPU too
            "exchange_ride_share", "join_sort_operands_per_join",
            "sum_scans_32bit_share", "key_sort_folded_share",
            # the query module's own spans, on the host's clock
            "join_call_ms", "groupby_call_ms"} == set(m)   # the rest: the chip's
    assert m["dzipf_split_keys_per_join"] == 0.0

    # the reference's own count: the same tables, the engine's hash
    from cylon_tpu import config
    from lib import generate
    cfg = files.load_json(bench_dir, "configs", "tiny_" + CONFIG)
    host = generate.host_tables(bench_dir, cfg, seed)
    recv_max = recv_cap = block = 0
    for t in ("left", "right"):
        tgt = _chip_of(host[t]["k"])
        per_dest = np.bincount(tgt, minlength=4)
        cells = np.stack([np.bincount(c, minlength=4)
                          for c in np.split(tgt, 4)])
        recv_max += int(per_dest.max())
        recv_cap += config.pow2ceil(int(per_dest.max()))
        block += config.pow2ceil(int(cells.max()))
    assert m["dzipf_recv_max_mrows_per_query"] == pytest.approx(
        recv_max * 1e-6, rel=1e-12)
    assert m["dzipf_recv_cap_mrows_per_query"] == pytest.approx(
        recv_cap * 1e-6, rel=1e-12)
    assert m["dzipf_exchange_block_mrows_per_query"] == pytest.approx(
        block * 1e-6, rel=1e-12)
    # skew: the probe side's fullest chip is well over the balanced 16,384
    assert recv_max > 1.2 * 2 * 16384


def test_split_keys_metric_on_a_tree_without_the_detect_counter(monkeypatch):
    """The parent has no ``skew_detect_joins``: the reader returns None and
    the line leaves the metric out (as ``trace_host_span_arg`` does where the
    exchange's span carries no ``recv_max``: ``args["arg"] in a``).  On this
    tree the three counters are in a snapshot from the import on, so an
    unsplit cell reads 0 and not nothing."""
    import cylon_tpu.relational.skew  # noqa: F401 - registers its counters
    from cylon_tpu.obs import metrics
    reader = files.load_module(BENCH_DIR, "readers", "registry_counter")
    with open(os.path.join(BENCH_DIR, "metrics",
                           "dzipf_split_keys_per_join.json")) as f:
        args = json.load(f)["args"]
    real = metrics.snapshot()
    assert {"skew_detect_joins", "skew_split_joins", "skew_split_keys"} \
        <= set(real)
    monkeypatch.setattr(metrics, "snapshot", lambda: {
        k: v for k, v in real.items() if k != "skew_detect_joins"})
    assert reader.read({}, args) is None
    monkeypatch.setattr(metrics, "snapshot", lambda: dict(
        real, skew_detect_joins=8, skew_split_keys=0))
    assert reader.read({}, args) == 0.0
    monkeypatch.setattr(metrics, "snapshot", lambda: dict(
        real, skew_detect_joins=8, skew_split_keys=8))
    assert reader.read({}, args) == 1.0
