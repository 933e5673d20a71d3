"""Shared by the tests: a throw-away copy of ``benchmark/`` with tiny cells
added to it as files only, and ``run.py`` of that copy steered onto the CPU
(the steering lives here, in the tests; ``run.py`` has no such option)."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY_ROWS = 65536


def _write(path: str, obj: dict) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(obj, f)


def copy_with_tiny_cells(tmp_path) -> str:
    """``<tmp>/benchmark``: the benchmark's files, plus for each cell a
    tiny twin (``tiny_<cell>``: the same query at 65,536 rows) and one
    metric more - written as new files, with no edit to any file there."""
    dst = os.path.join(str(tmp_path), "benchmark")
    shutil.copytree(BENCH_DIR, dst, ignore=shutil.ignore_patterns(
        "out", "__pycache__", ".pytest_cache"))
    for cell_file in sorted(os.listdir(os.path.join(BENCH_DIR, "workloads"))):
        with open(os.path.join(BENCH_DIR, "workloads", cell_file)) as f:
            cell = json.load(f)
        with open(os.path.join(BENCH_DIR, "configs",
                               cell["config"] + ".json")) as f:
            cfg = json.load(f)
        cfg["name"] = "tiny_" + cfg["name"]
        for t in cfg["tables"].values():
            t["rows"] = TINY_ROWS
        cell["name"] = "tiny_" + cell["name"]
        cell["config"] = cfg["name"]
        _write(os.path.join(dst, "configs", cfg["name"] + ".json"), cfg)
        _write(os.path.join(dst, "workloads", cell["name"] + ".json"), cell)
    _write(os.path.join(dst, "metrics", "query_mean_ms.json"), {
        "name": "query_mean_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "entry", "moves": "rows_per_s",
        "workloads": ["tiny_join_groupby_32m"],
        "reader": "span_mean_ms", "args": {"span": "query"}})
    return dst


def twin_metrics_of(bench_dir: str, cell: str) -> None:
    """A cell's operator metrics list it by name, so its tiny twin gets
    twins of them, as a file-only PR would write them: every metric file of
    ``bench_dir`` whose ``workloads`` names ``cell`` again as
    ``tiny_<name>``, listing ``tiny_<cell>`` alone.  The list-less metrics
    reach the twin with no file."""
    mdir = os.path.join(bench_dir, "metrics")
    for name in sorted(os.listdir(mdir)):
        with open(os.path.join(mdir, name), encoding="utf-8") as f:
            m = json.load(f)
        if cell in m.get("workloads", ()):
            m.update(name="tiny_" + m["name"], workloads=["tiny_" + cell])
            _write(os.path.join(mdir, "tiny_" + name), m)


def load_run(bench_dir: str):
    """``run.py`` of ``bench_dir`` as a fresh module (its ``lib`` too)."""
    for name in [m for m in sys.modules if m == "lib" or m.startswith("lib.")
                 or m.startswith("_bench_")]:
        del sys.modules[name]
    sys.path[:] = [p for p in sys.path
                   if os.path.basename(p.rstrip("/")) != "benchmark"]
    spec = importlib.util.spec_from_file_location(
        "_bench_run_under_test", os.path.join(bench_dir, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def steer_to_cpu(run, monkeypatch) -> None:
    """Skip the harness's look for a chip; a one-device CPU mesh stands in."""
    import jax
    import cylon_tpu as ct
    from cylon_tpu.ctx.context import CPUMeshConfig
    monkeypatch.setattr(run, "check_device",
                        lambda chips: jax.devices("cpu")[:chips])
    monkeypatch.setattr(run, "make_env", lambda chips: ct.CylonEnv(
        config=CPUMeshConfig(world_size=chips)))


def last_json_line(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])
