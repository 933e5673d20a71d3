"""Finding a cell's files by name.  Everything that belongs to one
configuration, workload, query, distribution, metric or reader is a file of
its own under the benchmark's directory; nothing here lists them."""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def check_name(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"not a name: {name!r}")
    return name


def load_json(bench_dir: str, kind: str, name: str) -> dict:
    path = os.path.join(bench_dir, kind, check_name(name) + ".json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(bench_dir: str, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module, loaded once per path."""
    path = os.path.join(bench_dir, kind, check_name(name) + ".py")
    key = "_bench_" + re.sub(r"\W", "_", os.path.abspath(path))
    if key in sys.modules:
        return sys.modules[key]
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[key]
        raise
    return mod


def metric_files(bench_dir: str) -> list:
    """Every ``metrics/*.json``, by name."""
    d = os.path.join(bench_dir, "metrics")
    return [load_json(bench_dir, "metrics", f[:-5])
            for f in sorted(os.listdir(d)) if f.endswith(".json")]
