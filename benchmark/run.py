"""One cell of the benchmark, in one fresh process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``workloads/<cell>.json``: a configuration (``configs/``), a query
module (``queries/``) and a closed loop of one client.  Nothing here names a
cell, a configuration or a metric: a later PR adds files (README.md).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``); everything else goes to standard
error, which is also kept in ``out/<cell>.<seed>.stderr`` with
``faulthandler`` on, so that a death says where.  No TPU, or fewer chips
than the cell asks for: non-zero exit and no result line.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()   # the first thing this process does

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import shutil     # noqa: E402
import sys        # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
for _p in (REPO_DIR, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import checks, compare, files, generate, stats  # noqa: E402
from lib import tables as device_tables, xplane                 # noqa: E402
from lib.spans import Spans                                      # noqa: E402
from lib.stderr_file import StderrFile                           # noqa: E402

OUT_DIR = os.path.join(BENCH_DIR, "out")
END_TO_END = {"rows_per_s": "rows/s", "query_s_p95": "s", "setup_s": "s"}


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _seconds_since_process_start() -> float:
    """By the kernel's record of when this process started; where that
    cannot be read, since this module's first line."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        since = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= since < 3600.0:
            return since
    except (OSError, ValueError, IndexError):
        pass
    return time.perf_counter() - _T_IMPORT


def _peaks() -> dict:
    with open(os.path.join(BENCH_DIR, "peaks.json"), encoding="utf-8") as f:
        return json.load(f)


def check_device(chips: int):
    """The devices this cell runs on, or None: the chip, and nothing else.
    A chip that ``peaks.json`` does not know is an error, not a default."""
    import jax
    devs = jax.devices()   # no platform set: jax takes the accelerator
    say(f"jax {jax.__version__}; devices: {devs}")
    if devs[0].platform != "tpu":
        print(f"benchmark: no TPU found - jax reports platform "
              f"{devs[0].platform!r} ({devs[0].device_kind}); the benchmark "
              "runs on the chip only", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"benchmark: the cell needs {chips} TPU device(s), jax "
              f"reports {len(devs)}", file=sys.stderr)
        return None
    if devs[0].device_kind not in _peaks():
        raise KeyError(f"peaks.json has no device {devs[0].device_kind!r}")
    return devs[:chips]


def make_env(chips: int):
    import cylon_tpu as ct
    from cylon_tpu.ctx.context import TPUConfig
    return ct.CylonEnv(config=TPUConfig(world_size=chips))


def _cache_entries(d: str) -> int:
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def _pull(table) -> dict:
    """The result's live rows, column by column, on the host."""
    return {name: data for name, (data, _valid)
            in table.host_columns().items()}


def _traced_queries(one_query, n: int, spans: Spans, trace_dir: str) -> dict:
    """``n`` queries under the profiler; the reduced trace.  A trace in
    which no operation ran on the device inside a query is an error."""
    import jax
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0       # the benchmark's spans, not frames
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    spans.annotate = True
    try:
        for _ in range(n):
            one_query()
    finally:
        spans.annotate = False
        jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    say(f"trace: {path} ({os.path.getsize(path)} bytes)")
    reduced = xplane.reduce(xplane.read_events(path))
    if reduced is None:
        raise RuntimeError("the trace holds no device operation inside a "
                           "query span")
    return reduced


def _per_layer(ctx: dict, cell: str) -> dict:
    """Every ``metrics/*.json`` that lists this cell (or lists none), read
    by its reader; a reader that finds nothing returns None and the metric
    is left out."""
    out = {}
    for m in files.metric_files(BENCH_DIR):
        if "workloads" in m and cell not in m["workloads"]:
            continue
        reader = files.load_module(BENCH_DIR, "readers", m["reader"])
        value = reader.read(ctx, m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(args, t_start: float) -> int:
    """``t_start``: ``time.perf_counter()`` as it stood when the process
    started."""
    cell = files.load_json(BENCH_DIR, "workloads", args.workload)
    cfg = files.load_json(BENCH_DIR, "configs", cell["config"])
    loop = cell["loop"]
    if loop["mode"] != "closed" or int(loop["clients"]) != 1:
        raise ValueError(f"loop {loop}: this harness drives a closed loop "
                         "of one client")
    q = cfg["query"]
    chips = int(cell["chips"])
    if int(cfg["world_size"]) != chips:
        raise ValueError("the configuration's world_size is the cell's chips")
    qm = files.load_module(BENCH_DIR, "queries", cell["query"])

    devs = check_device(chips)
    if devs is None:
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": chips}

    from cylon_tpu import config, obs
    from cylon_tpu.exec import compiler
    from cylon_tpu.native import native_available
    cache_dir = config.jax_cache_dir()
    say(f"cell {cell['name']} config {cfg['name']} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace}")
    say(f"native string hash built: {native_available()}; jax compilation "
        f"cache: {cache_dir or 'off'} ({_cache_entries(cache_dir)} entries "
        "before the run)")
    checks.reset()
    env = make_env(chips)

    # ---- set-up: tables from the seed, on the device; warm-up -------------
    t0 = time.perf_counter()
    host = generate.host_tables(BENCH_DIR, cfg, args.seed)
    rows_per_query = generate.input_rows(cfg)
    say(f"tables from the seed in {time.perf_counter() - t0:.2f} s: "
        + ", ".join(f"{n}={len(next(iter(c.values())))} rows x {len(c)}"
                    for n, c in host.items()))
    t0 = time.perf_counter()
    tables = qm.make_tables(env, host, q)
    device_tables.ready(*tables.values())
    ingest_s = time.perf_counter() - t0
    say(f"tables on the device in {ingest_s:.2f} s (capacity "
        f"{[t.capacity for t in tables.values()]})")

    spans = Spans()

    def one_query():
        with spans.span(xplane.QUERY):
            return qm.query(tables, q, spans.span)

    # every warm-up but the last runs plain; the last runs under EXPLAIN
    # ANALYZE (no key sampling: that adds programs of its own), whose plan
    # tree names the route the query took
    for i in range(int(loop["warmups"]) - 1):
        t0 = time.perf_counter()
        one_query()
        say(f"warm-up {i + 1}: {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    qplan = obs.explain_analyze(one_query, profile_keys=False)
    say(f"warm-up {int(loop['warmups'])} (explain analyze): "
        f"{time.perf_counter() - t0:.3f} s")
    routes = checks.plan_routes(qplan)
    del qplan          # it keeps the warm-up's result alive on the device
    say(f"routes: {json.dumps(routes)}")

    reduced = None
    spans.records.clear()              # the warm-ups' spans are not read
    if args.trace:
        reduced = _traced_queries(
            one_query, int(loop["traced_queries"]), spans,
            os.path.join(OUT_DIR, f"trace.{cell['name']}.{args.seed}"))
    traced_spans = list(spans.records)
    spans.records.clear()

    cstats = compiler.stats()
    compile_s, compiles_before = cstats["compile_seconds"], \
        cstats["compile_events"]
    setup_s = time.perf_counter() - t_start

    # ---- the window: queries back to back, one client ---------------------
    durations, group_counts, result = [], [], None
    w0 = time.perf_counter()
    while True:
        result = None              # the client is done with the last result
        t0 = time.perf_counter()
        result = one_query()
        t1 = time.perf_counter()
        durations.append(t1 - t0)
        group_counts.append(result.row_count)
        if t1 - w0 >= args.seconds:
            break
    window_s = t1 - w0
    # -----------------------------------------------------------------------
    window_compiles = compiler.stats()["compile_events"] - compiles_before
    peak_bytes = _peak_bytes(devs)
    n = len(durations)
    say(f"window: n={n} queries in {window_s:.3f} s; per query min "
        f"{min(durations):.4f} max {max(durations):.4f} s; window_compiles="
        f"{window_compiles}; peak_bytes_in_use={peak_bytes}")
    say("queries, ms: " + " ".join(f"{1e3 * d:.1f}" for d in durations)
        + f"; between queries {1e3 * (window_s - sum(durations)):.1f} ms")
    say(f"compiles before the window: {compiles_before} events, "
        f"{compile_s:.1f} s; cache holds {_cache_entries(cache_dir)} entries")

    numbers = checks.degradation()
    numbers.append(("route_mismatches", checks.route_mismatches(
        routes, cell["expect"]["routes"]), 0))
    numbers.append(("window_compiles", int(window_compiles), 0))
    numbers += qm.own_checks(env, tables, q, result.row_count,
                             cell["expect"], say)

    # ---- the comparison: outside the window and outside setup_s, after the
    # peak was read, with the program's state freed ---------------------------
    t0 = time.perf_counter()
    got = _pull(result)
    result = tables = None
    pull_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    want = qm.reference(host, q, args.seed)
    numbers.append(("queries_with_other_group_count", sum(
        1 for g in group_counts if g != group_counts[-1]), 0))
    numbers += compare.columns(qm.canonical(got, q, args.seed), want)
    numbers += qm.extra_numbers(host, got, q)
    say(f"result pulled in {pull_s:.2f} s ({len(next(iter(got.values())))} "
        f"rows); reference and comparison in "
        f"{time.perf_counter() - t0:.2f} s ({len(next(iter(want.values())))} "
        "rows compared cell by cell)")
    correct = compare.verdict(numbers)

    if args.trace:
        ctx = {"spans": traced_spans, "trace": reduced, "peaks": _peaks(),
               "counters": {"ingest_s": ingest_s, "compile_s": compile_s,
                            "window_compiles": window_compiles,
                            "peak_bytes": peak_bytes}}
        metrics = _per_layer(ctx, cell["name"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        values = {
            # all the work over all the time of the window
            "rows_per_s": rows_per_query * n / window_s,
            "query_s_p95": stats.nearest_rank(durations, 0.95),
            "setup_s": setup_s}
        metrics = {k: {"value": v, "unit": END_TO_END[k]}
                   for k, v in values.items()}
    device["memory_peak_bytes"] = peak_bytes
    line = {"correct": correct, "attempted": n, "failed": 0,
            "metrics": metrics, "device": device}
    if reduced is not None:
        line["breakdown"] = {
            "device_ops": [list(x) for x in reduced["op_seconds"][:10]],
            "idle_gaps": [list(x) for x in reduced["gap_seconds"][:10]]}
    line["compared"] = compare.as_dict(numbers)
    for ln in compare.as_lines(numbers):
        print(ln, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    t_start = time.perf_counter() - _seconds_since_process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    files.check_name(args.workload)
    with StderrFile(os.path.join(
            OUT_DIR, f"{args.workload}.{args.seed}.stderr")):
        try:
            return run(args, t_start)
        except Exception:   # noqa: BLE001 - the boundary: into the file too
            traceback.print_exc()
            return 1


if __name__ == "__main__":
    sys.exit(main())
