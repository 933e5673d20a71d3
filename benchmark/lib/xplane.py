"""From the profiler's trace (``*.xplane.pb``) to numbers.

Two steps, so that the arithmetic can be tested without a trace file:
``read_events`` takes the device operations and the benchmark's own spans
out of the file (``jax.profiler.ProfileData``, nothing but JAX), and
``reduce`` turns them into busy seconds, idle share, seconds by operation
and the idle gaps by what the host was doing.

What a TPU v5e trace of this program looks like (read by hand, my chip
run, PR 25): one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA
Modules`` (one event per executed program), ``XLA Ops`` (one event per
executed HLO instruction, never nested, named by the instruction's whole
text: ``%sort = (s32[65011712]{...}, ...) sort(...), dimensions={0}, ...``)
and ``Async XLA Ops`` (copies in flight, which overlap the operations and
are not counted).  Start and duration are nanoseconds on the same clock as
the host plane ``/host:CPU``, where a ``TraceAnnotation`` shows on the line
of the thread that opened it (``python3``).  A Pallas kernel is a
``custom-call`` whose ``custom_call_target`` is ``tpu_custom_call``; the
other custom calls are XLA's own (``X64SplitLow``, ``X64Combine``,
``ConcatBitcast``)."""

from __future__ import annotations

import glob
import os
import re

from .spans import PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
QUERY = "query"

_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = (?P<type>.*?) "
                  r"(?P<opcode>[a-z][a-z0-9-]*)\(")
_ARRAY = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def label(hlo_text: str) -> str:
    """``<opcode> <name> <first result array>[x<arrays in the result>]
    [ target=<custom call's>]`` of an instruction's text, e.g. ``sort sort
    s32[65011712]x6`` or ``custom-call per_shard.1 u32[8,13107200]
    target=tpu_custom_call``.  Text that is no instruction is kept."""
    m = _HLO.match(hlo_text)
    if not m:
        return hlo_text[:120]
    arrays = _ARRAY.findall(m["type"])
    shape = (arrays[0] + (f"x{len(arrays)}" if len(arrays) > 1 else "")
             if arrays else "")
    out = f"{m['opcode']} {m['name']} {shape}".rstrip()
    t = _TARGET.search(hlo_text)
    return f"{out} target={t[1]}" if t else out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def read_events(path: str) -> dict:
    """``{"device": {plane: [(label, start_ns, dur_ns)]}, "spans": [(name,
    start_ns, dur_ns)]}``: every event of each device plane's ``XLA Ops``
    line under its ``label``, and every ``bench.*`` annotation of the host
    planes (prefix taken off)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(label(e.name), float(e.start_ns), float(e.duration_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                device[plane.name] = ops
        else:
            spans += [(e.name[len(PREFIX):], float(e.start_ns),
                       float(e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name.startswith(PREFIX)]
    return {"device": device, "spans": sorted(spans, key=lambda s: s[1])}


def _union(intervals: list) -> list:
    """Sorted, disjoint ``[start, end]`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_was_in(spans: list, t: float) -> str:
    """The innermost benchmark span open at ``t``."""
    best, best_dur = None, None
    for name, s, d in spans:
        if s <= t <= s + d and (best is None or d < best_dur):
            best, best_dur = name, d
    if best is None:
        return "between_queries"
    return "query_other" if best == QUERY else best


def reduce(events: dict) -> dict | None:
    """The traced window is the traced queries: from the first ``query``
    span's start to the last one's end.  Busy is, per chip, the union of the
    intervals in which an operation ran inside that window, averaged over
    the chips; a gap is a stretch of the window in which none ran.  Returns
    None where the trace holds no device operation or no query span."""
    queries = [(s, s + d) for n, s, d in events["spans"] if n == QUERY]
    if not events["device"] or not queries:
        return None
    w0, w1 = min(q[0] for q in queries), max(q[1] for q in queries)
    n_chips = len(events["device"])
    busy_ns, by_op, gaps = 0.0, {}, {}
    for ops in events["device"].values():
        clipped = [(max(s, w0), min(s + d, w1)) for _, s, d in ops
                   if s + d > w0 and s < w1]
        merged = _union(clipped)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, d in ops:
            if s + d > w0 and s < w1:
                by_op[name] = by_op.get(name, 0.0) + d
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 > g0:
                where = _host_was_in(events["spans"], (g0 + g1) / 2)
                gaps[where] = gaps.get(where, 0.0) + (g1 - g0)
    busy_s = busy_ns / n_chips / 1e9
    window_s = (w1 - w0) / 1e9
    rank = lambda d: sorted(((k, v / n_chips / 1e9) for k, v in d.items()),
                            key=lambda kv: -kv[1])
    return {"n_queries": len(queries), "n_chips": n_chips,
            "busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s,
            "op_seconds": rank(by_op), "gap_seconds": rank(gaps)}


def op_seconds_matching(reduced: dict, pattern: str) -> float | None:
    """Seconds (per chip, over the traced window) of the operations whose
    name matches ``pattern``; None where none does."""
    rx = re.compile(pattern)
    hit = [v for k, v in reduced["op_seconds"] if rx.search(k)]
    return sum(hit) if hit else None
