"""The profiler's trace read *with* the event metadata's stats: device
seconds by builder and by stage, and the program's own spans.

``jax.profiler.ProfileData`` (what ``lib/xplane.py`` reads through) hands out
an event's own stats only.  What says *which part of which program* an
operation is lives one level up, in the stats of the event's **metadata**: an
``XLA Ops`` event's metadata carries ``tf_op``, the JAX name stack of the HLO
instruction (``jit(join__count_fn)/.../cylon.scan/jit(cumsum)/...`` once the
program opens ``jax.named_scope("cylon.<stage>")``; a fusion carries its root
instruction's).  So this module reads the ``.xplane.pb`` wire format itself:
five message types of ``tsl/profiler/protobuf/xplane.proto`` (XSpace, XPlane
with its ``event_metadata``/``stat_metadata`` maps, XLine, XEvent, XStat),
varints and length-delimited fields, nothing but the standard library, so it
reads the same on the chip machine from a fresh checkout.

What a trace of this program holds (read by hand, PR 25's chip trace and my
chip runs, PR 26): per chip a plane ``/device:TPU:<n>`` whose line ``XLA
Modules`` has one event per executed program, named ``jit_<module>_<builder>
(<fingerprint>)`` since ``utils/cache.program_cache`` names the jitted
callable, and whose line ``XLA Ops`` has one event per executed instruction.
The host plane ``/host:CPU`` has the ``TraceAnnotation`` spans on the line of
the thread that opened them: the benchmark's ``bench.*`` and the program's
``cylon.*`` (every ``utils/timing.region``; ``cylon.launch.<builder>`` around
the enqueue of a program, ``cylon.pull.<kind>`` around a host pull), with
their arguments as stats (``session``, ``bytes``).  A device line's
``timestamp_ns`` is 0 and a host line's is the trace's start; an event starts
at ``timestamp_ns + offset_ps / 1000`` on one common clock.

**The stage of an operation** is the innermost ``cylon.<stage>`` of its
``tf_op`` (every builder opens one root stage around its whole per-shard body
and finer ones inside, so the last one is the most exact), ``None`` where
there is none: XLA's own copies and reshards at the program's boundary.

**Which trace.**  ``run.py`` hands a reader ``ctx`` and ``ctx`` carries no
path (it may not grow one in a PR that only adds files), so the readers take
the newest ``out/trace.*/**/*.xplane.pb`` written since this process started
(:func:`newest_trace`) and parse it once, cached on path and mtime
(:func:`reduced_of_this_run`).  ``ctx`` should carry the trace's path:
``PERF.md`` §7.
"""

from __future__ import annotations

import glob
import os
import re
import struct
import time

from . import xplane

MODULES_LINE = "XLA Modules"
CYLON = "cylon."


def _process_start() -> float:
    """Wall-clock time at which this process started, by the kernel's
    record (as ``run.py`` reckons ``setup_s``); 0.0 where that cannot be
    read, which leaves "newest" alone to pick the trace."""
    try:
        with open("/proc/self/stat", encoding="ascii") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime", encoding="ascii") as f:
            uptime = float(f.read().split()[0])
        since = uptime - ticks / os.sysconf("SC_CLK_TCK")
        return time.time() - since if 0.0 <= since < 86400.0 else 0.0
    except (OSError, ValueError, IndexError):
        return 0.0


_T_PROCESS = _process_start()
_STAGE = re.compile(r"(?:^|/)cylon\.([A-Za-z0-9_]+)(?=/|:|$)")
_MODULE = re.compile(r"^jit_(?P<builder>.+?)(?:\(\d+\))?$")


# ---- the wire format --------------------------------------------------------

def _varint(b, i: int):
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        if c < 0x80:
            return r, i
        s += 7


def _fields(b):
    """``(field number, wire type, value)`` of one message: an int for a
    varint, a memoryview for length-delimited and fixed fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wt = key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 1:
            v, i = b[i:i + 8], i + 8
        elif wt == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt} is not in an xplane")
        yield key >> 3, wt, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(raw: list, stat_names: dict) -> dict:
    """XStat messages -> ``{stat name: value}``; a ``ref_value`` is the
    name of the stat metadata it points at (how strings are interned)."""
    out = {}
    for sb in raw:
        name, val = None, None
        for f, _wt, v in _fields(sb):
            if f == 1:
                name = stat_names.get(v, str(v))
            elif f == 2:
                val = struct.unpack("<d", v)[0]
            elif f == 3:
                val = v
            elif f == 4:
                val = _signed(v)
            elif f in (5, 6):
                val = _text(v)
            elif f == 7:
                val = stat_names.get(v, str(v))
        if name is not None:
            out[name] = val
    return out


def _plane(pb) -> dict:
    """One XPlane: its name, lines as ``(name, timestamp_ns, [raw events])``,
    event metadata as ``{id: (name, [raw stats])}`` and stat names."""
    name, lines, meta, stat_names = "", [], {}, {}
    for f, _wt, v in _fields(pb):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lname, ts, events = "", 0, []
            for lf, _lw, lv in _fields(v):
                if lf == 2:
                    lname = _text(lv)
                elif lf == 3:
                    ts = _signed(lv)
                elif lf == 4:
                    events.append(lv)
            lines.append((lname, ts, events))
        elif f in (4, 5):
            key, val = None, None
            for mf, _mw, mv in _fields(v):
                if mf == 1:
                    key = mv
                elif mf == 2:
                    val = mv
            if key is None or val is None:
                continue
            mname, mstats = "", []
            for ef, _ew, ev in _fields(val):
                if ef == 2:
                    mname = _text(ev)
                elif ef == 5 and f == 4:
                    mstats.append(ev)
            if f == 4:
                meta[key] = (mname, mstats)
            else:
                stat_names[key] = mname
    return {"name": name, "lines": lines, "meta": meta,
            "stat_names": stat_names}


def _event(eb):
    """``(metadata id, offset_ps, duration_ps, [raw stats])`` of an XEvent."""
    mid, off, dur, stats = 0, 0, 0, []
    for f, _wt, v in _fields(eb):
        if f == 1:
            mid = v
        elif f == 2:
            off = _signed(v)
        elif f == 3:
            dur = _signed(v)
        elif f == 4:
            stats.append(v)
    return mid, off, dur, stats


# ---- events -----------------------------------------------------------------

def stage_of(tf_op: str | None) -> str | None:
    """``"scan"`` of ``jit(f)/cylon.groupby/cylon.scan/jit(cumsum)/add:``:
    the innermost ``cylon.<stage>`` component, None where there is none."""
    found = _STAGE.findall(tf_op or "")
    return found[-1] if found else None


#: XLA's ReduceWindowRewriter splits a whole-array scan (cumsum, cummin,
#: cummax) into a tree of small ``reduce-window`` instructions plus the
#: adds/copies that stitch them, and the pieces lose the scan's metadata:
#: the ``reduce-window`` ones have none at all (no ``tf_op``, no ``source``),
#: the stitching ones are named after the scan's reducer alone
#: (``reduce_window_sum:``, no ``jit(...)`` in front).  PR 25's and PR 26's
#: chip traces: 0.45 s a query of the join cell.  Every reduce-window of
#: these programs is such a scan, so these two shapes of an UNSCOPED
#: operation take the stage ``scan`` - said here, in ``PERF.md`` §3, and
#: nowhere hidden.  An operation with a ``jit(...)`` name stack is never
#: touched.
SCAN = "scan"
_REDUCER_NAME = re.compile(r"^reduce_window_(sum|min|max):?$")


def _stage_of_op(label: str, tf_op: str | None) -> str | None:
    if not tf_op:
        return SCAN if label.startswith("reduce-window ") else None
    if _REDUCER_NAME.match(tf_op):
        return SCAN
    return stage_of(tf_op)


def builder_of(module_name: str) -> str:
    """``"join__count_fn"`` of ``jit_join__count_fn(2769891684176257771)``."""
    m = _MODULE.match(module_name)
    return m["builder"] if m else module_name


def read_events(path: str) -> dict:
    """``{"device": {plane: {"modules": [(builder, start_ns, dur_ns)],
    "ops": [(label, stage, start_ns, dur_ns)]}}, "host": [(name, start_ns,
    dur_ns, args)], "spans": [(name, start_ns, dur_ns)]}``: per chip the
    executed programs and instructions, the program's ``cylon.*``
    annotations (prefix kept, arguments as a dict) and the benchmark's
    ``bench.*`` ones (prefix taken off, as ``lib/xplane.read_events`` gives
    them).  Lists are sorted by start."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    device, host, spans = {}, [], []
    for f, _wt, pb in _fields(space):
        if f != 1:
            continue
        plane = _plane(pb)
        meta, stat_names = plane["meta"], plane["stat_names"]
        if xplane.DEVICE_PLANE.match(plane["name"]):
            stage_by_id, label_by_id = {}, {}
            modules, ops = [], []
            for lname, ts, events in plane["lines"]:
                if lname not in (MODULES_LINE, xplane.OPS_LINE):
                    continue
                for eb in events:
                    mid, off, dur, _st = _event(eb)
                    start, dur_ns = ts + off / 1e3, dur / 1e3
                    name, mstats = meta.get(mid, ("", []))
                    if lname == MODULES_LINE:
                        modules.append((builder_of(name), start, dur_ns))
                        continue
                    if mid not in label_by_id:
                        label_by_id[mid] = xplane.label(name)
                        stage_by_id[mid] = _stage_of_op(
                            label_by_id[mid],
                            _stats(mstats, stat_names).get("tf_op"))
                    ops.append((label_by_id[mid], stage_by_id[mid], start,
                                dur_ns))
            if ops:
                device[plane["name"]] = {
                    "modules": sorted(modules, key=lambda m: m[1]),
                    "ops": sorted(ops, key=lambda o: o[2])}
            continue
        for _lname, ts, events in plane["lines"]:
            for eb in events:
                mid, off, dur, st = _event(eb)
                name = meta.get(mid, ("", []))[0]
                start, dur_ns = ts + off / 1e3, dur / 1e3
                if name.startswith(CYLON):
                    host.append((name, start, dur_ns,
                                 _stats(st, stat_names)))
                elif name.startswith(xplane.PREFIX):
                    spans.append((name[len(xplane.PREFIX):], start, dur_ns))
    return {"device": device, "host": sorted(host, key=lambda h: h[1]),
            "spans": sorted(spans, key=lambda s: s[1])}


# ---- the reduction ----------------------------------------------------------

def _innermost(spans: list, t: float) -> str | None:
    """Name of the shortest of ``(name, start, dur, ...)`` open at ``t``."""
    best, best_dur = None, None
    for sp in spans:
        if sp[1] <= t <= sp[1] + sp[2] and (best is None or sp[2] < best_dur):
            best, best_dur = sp[0], sp[2]
    return best


def _module_at(modules: list, t: float) -> str | None:
    for builder, s, d in modules:
        if s <= t <= s + d:
            return builder
    return None


def reduce(events: dict) -> dict | None:
    """Inside the traced queries' window (first ``bench.query`` span's start
    to the last one's end, as ``lib/xplane.reduce`` has it), averaged over
    the chips, in seconds: ``program_s`` by builder (the ``XLA Modules``
    events), ``stage_s`` by stage and ``builder_stage_s`` by (builder,
    stage) (summed ``XLA Ops`` durations; stage None = no ``cylon.`` scope;
    an operation belongs to the program running at its middle),
    ``op_s`` by (builder, stage, label), ``busy_s`` (union of operations)
    and ``ops_s`` (their sum: the denominator of shares by stage).  From
    the host plane: ``host_s`` and ``host_n`` by ``cylon.*`` span name, and
    ``gap_s``: each idle stretch of the window given to the innermost
    ``cylon.*`` span open at its middle, else to the ``bench.*`` one as
    ``lib/xplane`` names it.  None where the trace has no device operation
    or no query span."""
    queries = [(s, s + d) for n, s, d in events["spans"] if n == xplane.QUERY]
    if not events["device"] or not queries:
        return None
    w0, w1 = min(q[0] for q in queries), max(q[1] for q in queries)
    inside = lambda s, d: s + d > w0 and s < w1   # noqa: E731
    n_chips = len(events["device"])
    program, stage, pair, op, gaps = {}, {}, {}, {}, {}
    busy_ns = ops_ns = 0.0

    def add(d, k, v):
        d[k] = d.get(k, 0.0) + v

    for chip in events["device"].values():
        for builder, s, d in chip["modules"]:
            if inside(s, d):
                add(program, builder, d)
        clipped = []
        for lab, stg, s, d in chip["ops"]:
            if not inside(s, d):
                continue
            builder = _module_at(chip["modules"], s + d / 2)
            add(stage, stg, d)
            add(pair, (builder, stg), d)
            add(op, (builder, stg, lab), d)
            ops_ns += d
            clipped.append((max(s, w0), min(s + d, w1)))
        merged = xplane._union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                mid = (g0 + g1) / 2
                where = _innermost(events["host"], mid) \
                    or xplane._host_was_in(events["spans"], mid)
                add(gaps, where, g1 - g0)
    host_s, host_n = {}, {}
    for name, s, d, _args in events["host"]:
        if inside(s, d):
            add(host_s, name, d / 1e9)
            add(host_n, name, 1)
    per_chip = lambda d: {k: v / n_chips / 1e9 for k, v in d.items()}  # noqa: E731
    return {"n_queries": len(queries), "n_chips": n_chips,
            "window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / n_chips / 1e9,
            "ops_s": ops_ns / n_chips / 1e9,
            "program_s": per_chip(program), "stage_s": per_chip(stage),
            "builder_stage_s": per_chip(pair), "op_s": per_chip(op),
            "host_s": host_s, "host_n": host_n, "gap_s": per_chip(gaps)}


# ---- which trace ------------------------------------------------------------

_CACHE: dict = {}


def newest_trace(out_dir: str, since: float = _T_PROCESS) -> str | None:
    """The newest ``<out_dir>/trace.*/**/*.xplane.pb`` modified at or after
    ``since`` (default: when this process started, i.e. a trace of this
    run and not one an earlier run left behind)."""
    found = [(os.path.getmtime(p), p) for p in glob.glob(
        os.path.join(out_dir, "trace.*", "**", "*.xplane.pb"),
        recursive=True)]
    found = [x for x in found if x[0] >= since - 1.0]
    return max(found)[1] if found else None


def reduced(path: str) -> dict | None:
    """:func:`reduce` of :func:`read_events` of ``path``, parsed once per
    (path, mtime)."""
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = reduce(read_events(path))
    return _CACHE[key]


def reduced_of_this_run() -> dict | None:
    """What the readers call: the reduction of this run's trace, or None
    where this process has written none (``--trace 0``, or a CPU rehearsal
    whose trace has no device plane)."""
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out")
    path = newest_trace(out_dir)
    return reduced(path) if path else None
