"""A round trip, both ends, of some processes side by side.

    python scripts/round_trips_report.py <trace-or-ring-or-stderr>...

Each argument is one of: a profiler trace (``*.xplane.pb``, or a directory
``jax.profiler`` wrote one under; ``*.trips.json`` is a trace's reduction
kept by :func:`save`, for a machine whose traces are too large to bring
back), the flight recorder's Chrome JSON
(``*.json``: what ``CYLON_TPU_TRACE=path`` exports at exit) or
``benchmark/run.py``'s ``out/<cell>.<seed>.stderr`` (for the window's
per-query milliseconds).  A process is named by its file's name up to the
first ``.xplane.pb`` / ``.trips.json`` / ``.ring.json`` / ``.json`` /
``.stderr``, so
``p3.xplane.pb`` and ``p3.stderr`` are one process.

From the traces: per process one line - the traced queries' milliseconds,
the speed class, and the ten numbers of ``benchmark/readers/
trace_round_trips.py`` - and, between the slow class and the fast one, the
round trips of a query ranked by the difference in the device's idle time,
with the differences in ``late``, ``late_after_return``, ``wake`` and host
milliseconds beside it, by name (docs/observability.md, "A round trip, both
ends").  The classes are the two sides of the widest gap between the
processes' median query, where that gap is over 3%; else there is one.

From a ring (no device side there): launch / pull / turn host milliseconds
per operator call as a series over the whole untraced window, in ten bins,
and by span name the first bin against the last - how "fast, then slow"
inside one process is seen.  Numbers are the processes' own; a run on the
CPU rig gives no device number.
"""

from __future__ import annotations

import json
import os
import re
import statistics
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
for _p in (REPO, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import files, xplane, xspace   # noqa: E402

RT = files.load_module(BENCH, "readers", "trace_round_trips")
METRICS = tuple(RT._WHAT)
SUFFIXES = (".xplane.pb", ".trips.json", ".ring.json", ".json", ".stderr")
BINS = 10


def tag_of(path: str) -> str:
    base = os.path.basename(path.rstrip("/"))
    for suf in SUFFIXES:
        if suf in base:
            return base[:base.index(suf)]
    return base


def classify(medians: dict) -> dict:
    """``{tag: "fast" | "slow" | "one"}``: the two sides of the widest
    relative gap between the sorted medians, where it is over 3%."""
    order = sorted(medians, key=medians.get)
    gaps = [(medians[b] / medians[a] - 1.0, i + 1)
            for i, (a, b) in enumerate(zip(order, order[1:]))]
    if not gaps or max(gaps)[0] <= 0.03:
        return dict.fromkeys(order, "one")
    cut = max(gaps)[1]
    return {t: "fast" if i < cut else "slow" for i, t in enumerate(order)}


# ---- a profiler trace -------------------------------------------------------

def trace(path: str) -> dict | None:
    if path.endswith(".trips.json"):
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    return RT.round_trips(xspace.read_events(path))


def save(trace_path: str, out_path: str) -> None:
    """The reduction of ``trace_path`` as ``out_path`` (``*.trips.json``)."""
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(trace(trace_path), f)


def trace_line(tag: str, cls: str, red: dict) -> str:
    nums = " ".join(
        f"{m}={'None' if v is None else format(v, '.3f')}"
        for m, v in ((m, RT._WHAT[m](red)) for m in METRICS))
    q = " ".join(f"{x:.1f}" for x in red["query_ms"])
    return (f"{tag}: traced queries, ms: {q}; class {cls}; op_ms="
            f"{red['op_ms']:.3f} launch_ms={red['host_ms'][RT.LAUNCH]:.3f} "
            f"pull_ms={red['host_ms'][RT.PULL]:.3f} {nums}")


TRIP_KEYS = ("idle_ms", "turn_idle_ms", "late_ms", "late_after_return_ms",
             "skew_ms", "wake_ms", "host_ms", "turn_host_ms")


def rank(slow: list, fast: list) -> list:
    """Per round trip (by place and name) the class means and slow - fast,
    ranked by the difference in idle (the boundary's and its turn's)."""
    def mean_by_trip(reds):
        acc: dict = {}
        for red in reds:
            for t in red["trips"]:
                a = acc.setdefault((t["place"], t["name"]),
                                   {"named": set(), **dict.fromkeys(
                                       TRIP_KEYS, 0.0)})
                for k in TRIP_KEYS:
                    a[k] += t.get(k, 0.0) / len(reds)
                a["named"] |= set(t["turn_named"])
        return acc

    s, f = mean_by_trip(slow), mean_by_trip(fast)
    rows = []
    for key in sorted(set(s) | set(f)):
        zero = {"named": set(), **dict.fromkeys(TRIP_KEYS, 0.0)}
        a, b = s.get(key, zero), f.get(key, zero)
        rows.append({"place": key[0], "name": key[1],
                     "named": sorted(a["named"] | b["named"]),
                     **{k: (a[k], b[k], a[k] - b[k]) for k in TRIP_KEYS}})
    rows.sort(key=lambda r: -abs(r["idle_ms"][2] + r["turn_idle_ms"][2]))
    return rows


def rank_lines(rows: list) -> list:
    out = ["place name | slow - fast, ms a query: idle(in it) idle(turn "
           "after) late late_after_return skew wake host(it) host(turn "
           "after) | turn's cylon.host.* spans"]
    for r in rows:
        d = " ".join(f"{r[k][2]:+8.3f}" for k in TRIP_KEYS)
        out.append(f"{r['place']:3d} {r['name']:34s} | {d} | "
                   f"{','.join(r['named']) or '-'}")
    tot = " ".join(f"{sum(r[k][2] for r in rows):+8.3f}" for k in TRIP_KEYS)
    out.append(f"    {'all round trips':34s} | {tot} |")
    return out


# ---- the flight recorder's ring ---------------------------------------------

def ring_calls(path: str) -> list:
    """Per outermost operator call of the ring, in order: ``{"op", "t0_s",
    "op_s", "launch_s", "pull_s", "turn_s", "by_name": {span: seconds}}``;
    launch + pull + turn is the call's span (turn is what is in neither)."""
    with open(path, encoding="utf-8") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    spans = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
             for e in events]
    ops = RT.outermost([s for s in spans if s[0].startswith("op.")])
    bounds = sorted(((cls, n, a, b) for n, a, b in spans
                     for cls in (RT.LAUNCH, RT.PULL)
                     if n.startswith(cls + ".")),
                    key=lambda b: (b[2], -b[3]))
    named = sorted((s for s in spans if s[0].startswith("host.")),
                   key=lambda s: s[1])
    if not ops:
        return []
    segs = RT.tile(ops, bounds, ops[0][1], max(o[2] for o in ops))
    calls, i, j = [], 0, 0
    for op, t0, t1 in ops:
        call = {"op": op, "t0_s": t0, "op_s": t1 - t0, "launch_s": 0.0,
                "pull_s": 0.0, "turn_s": 0.0, "by_name": {}}
        while i < len(segs) and segs[i][1] <= t0:
            i += 1
        while i < len(segs) and segs[i][0] < t1:
            s0, s1, cls, idx = segs[i]
            call[cls + "_s"] += s1 - s0
            if cls != RT.TURN:
                name = bounds[idx][1]
                call["by_name"][name] = call["by_name"].get(name, 0.0) \
                    + s1 - s0
            i += 1
        while j < len(named) and named[j][1] < t0:
            j += 1
        k = j
        while k < len(named) and named[k][1] < t1:
            n, a, b = named[k]
            call["by_name"][n] = call["by_name"].get(n, 0.0) + b - a
            k += 1
        calls.append(call)
    return calls


def ring_lines(tag: str, calls: list) -> list:
    out = []
    for op in sorted({c["op"] for c in calls}):
        mine = [c for c in calls if c["op"] == op]
        n = len(mine)
        size = max(n // BINS, 1)
        bins = [mine[k:k + size] for k in range(0, n, size)][:BINS]

        def med(cs, key):
            return 1e3 * statistics.median(c[key] for c in cs)

        out.append(f"{tag}: {op} x{n}, {len(bins)} bins of {size} calls, "
                   "median ms a call")
        for key in ("op_s", "launch_s", "pull_s", "turn_s"):
            out.append(f"  {key[:-2]:7s} " + " ".join(
                f"{med(b, key):8.3f}" for b in bins))
        first, last = bins[0], bins[-1]
        for name in sorted({k for c in first + last for k in c["by_name"]}):
            a, b = (1e3 * statistics.median(c["by_name"].get(name, 0.0)
                                            for c in cs)
                    for cs in (first, last))
            out.append(f"    {name:34s} first bin {a:8.3f} last bin "
                       f"{b:8.3f} ({b - a:+.3f})")
    return out


# ---- run.py's standard error ------------------------------------------------

def window_ms(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        m = re.search(r"queries, ms: ([0-9. ]+);", f.read())
    return [float(x) for x in m[1].split()] if m else []


def main(argv) -> int:
    traces, rings, windows = {}, {}, {}
    for path in argv[1:]:
        tag = tag_of(path)
        if path.endswith(".stderr"):
            windows[tag] = window_ms(path)
        elif path.endswith(".json") and not path.endswith(".trips.json"):
            rings[tag] = ring_calls(path)
        else:
            red = trace(path)
            if red is None:
                print(f"{tag}: no device operation or no query span in the "
                      "trace", file=sys.stderr)
            else:
                traces[tag] = red
    if traces:
        cls = classify({t: statistics.median(r["query_ms"])
                        for t, r in traces.items()})
        for tag in sorted(traces):
            print(trace_line(tag, cls[tag], traces[tag]))
        slow = [traces[t] for t in sorted(traces) if cls[t] == "slow"]
        fast = [traces[t] for t in sorted(traces) if cls[t] == "fast"]
        if slow and fast:
            print(f"slow ({len(slow)}) - fast ({len(fast)}):")
            print("\n".join(rank_lines(rank(slow, fast))))
        else:
            print(f"one speed only over {len(traces)} traced processes")
    if windows:
        cls = classify({t: statistics.median(ms)
                        for t, ms in windows.items() if ms})
        for tag in sorted(windows):
            ms = windows[tag]
            if ms:
                print(f"{tag}: window n={len(ms)} median "
                      f"{statistics.median(ms):.1f} ms, first ten "
                      f"{statistics.median(ms[:10]):.1f}, last ten "
                      f"{statistics.median(ms[-10:]):.1f}; class {cls[tag]}")
    for tag in sorted(rings):
        print("\n".join(ring_lines(tag, rings[tag])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
