"""``BENCHMARK.json`` against the limits of the benchmark's contract that can
be checked without a chip, and against the benchmark's own files: every
cell, configuration and per-layer metric it names is a file here that says
the same, so the two cannot drift apart."""

from __future__ import annotations

import json
import os
import re

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head|expansion|experts_per_tok")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO_DIR, "BENCHMARK.json"), encoding="utf-8") as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def _line(s, lo=1, hi=200):
    return isinstance(s, str) and lo <= len(s) <= hi and "\n" not in s \
        and "\t" not in s


def _load(kind, name):
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) for w in bench["command"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    # a full check at the full 24 cells fits into 43200 s
    assert (2 + 14 * 24) * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 \
        <= 43200


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) and not WIDTH.search(k)
                   for k in c["reduced"])
        on_disk = _load("configs", c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert on_disk["name"] == c["name"]
        assert on_disk["source"] == c["source"]
        assert sorted(on_disk["reduced"]) == sorted(c["reduced"])
        assert on_disk["guarantees"] and on_disk["assumed"]


def test_workloads(bench):
    cells = bench["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        on_disk = _load("workloads", w["name"])
        assert {k: on_disk[k] for k in w} == w
        assert os.path.isfile(os.path.join(BENCH_DIR, "queries",
                                           on_disk["query"] + ".py"))
        assert _load("configs", w["config"])["world_size"] == w["chips"]


def test_end_to_end(bench):
    e2e = bench["end_to_end"]
    assert [m["name"] for m in e2e] == ["rows_per_s", "query_s_p95",
                                        "setup_s"]
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_per_layer_matches_the_metric_files(bench):
    per = bench["per_layer"]
    assert 1 <= len(per) <= 128
    names = [m["name"] for m in per]
    assert len(set(names + [m["name"] for m in bench["end_to_end"]])) \
        == len(names) + len(bench["end_to_end"])
    on_disk = sorted(f[:-5] for f in os.listdir(
        os.path.join(BENCH_DIR, "metrics")) if f.endswith(".json"))
    assert sorted(names) == on_disk
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    layers = set()
    with open(os.path.join(REPO_DIR, "PERF.md"), encoding="utf-8") as f:
        perf_md = f.read()
    for m in per:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert _line(m["layer"]) and f"| {m['layer']} |" in perf_md
        assert set(m.get("workloads", cells)) <= cells
        layers.add(m["layer"])
        f = _load("metrics", m["name"])
        assert {k: f[k] for k in m} == m
        assert os.path.isfile(os.path.join(BENCH_DIR, "readers",
                                           f["reader"] + ".py"))
    # every cell reports at least one metric of its own operators (one with
    # a list: README.md, the two classes), not only what every cell gets
    # for nothing
    for c in cells:
        assert any(c in m.get("workloads", ()) for m in per), c


def test_one_reading_has_one_name_in_a_cell(bench):
    """Two metric files with the same ``(reader, args)`` never list the same
    cell (a file with no list lists all): a later file-only PR can still
    give its cell a twin of an operator metric, but no cell prints one
    reading under two names."""
    cells = [w["name"] for w in bench["workloads"]]
    seen = {}
    for m in bench["per_layer"]:
        f = _load("metrics", m["name"])
        reading = (f["reader"], json.dumps(f.get("args", {}), sort_keys=True))
        for cell in m.get("workloads", cells):
            assert (reading, cell) not in seen, \
                (m["name"], seen[reading, cell], cell)
            seen[reading, cell] = m["name"]


def test_files_under_paths_are_named_from_name_characters():
    for root, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in ("out", "__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), REPO_DIR)
            assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_span_metrics_read_spans_their_cells_open(bench):
    """A ``span_mean_ms`` metric names a span that the query module of every
    cell reporting it declares in ``SPANS`` (or the harness's own ``query``)."""
    import importlib.util
    cells = {w["name"]: _load("workloads", w["name"])
             for w in bench["workloads"]}
    for m in bench["per_layer"]:
        f = _load("metrics", m["name"])
        if f["reader"] != "span_mean_ms":
            continue
        for cell in m.get("workloads", list(cells)):
            path = os.path.join(BENCH_DIR, "queries",
                                cells[cell]["query"] + ".py")
            spec = importlib.util.spec_from_file_location("_q_" + cell, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            assert f["args"]["span"] in mod.SPANS + ("query",), (m, cell)
