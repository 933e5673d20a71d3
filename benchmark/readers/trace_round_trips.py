"""A round trip, both ends: every ``cylon.launch.<builder>`` span matched to
the start of its program on each chip, every ``cylon.pull.<kind>`` span to
the device's last operation before it returned, and every instant of the
traced queries put down to one of four classes.  ``args["what"]`` picks the
number; milliseconds are per traced query, device numbers the mean over the
chips.

**Classes.**  An instant is *launch* (inside a ``cylon.launch.*`` span),
*pull* (inside a ``cylon.pull.*`` one; a boundary nested in another goes to
the innermost), *turn* (inside an outermost ``cylon.op.*`` span - the
operator call, ``cylon_tpu/obs/plan._NodeCtx`` - and in neither) or
*outside*.  The device's idle gaps (``lib/xplane.reduce``'s: the traced
window less the union of a chip's operations) are cut where the class
changes, so ``idle_in_pull`` + ``idle_in_launch`` + ``idle_in_turn`` +
``idle_outside_ops`` is ``device_idle_share`` x the traced query.  The host's
milliseconds of the outermost operator calls tile the same way:
``launch`` + ``pull`` + ``turn_host`` = the summed ``cylon.op.*`` spans;
``turn_named_share`` is the share of the turns inside a ``cylon.host.*``
span (``cylon_tpu/utils/timing.HOST_STEPS``).

**Matching.**  One client, a closed loop: the k-th ``cylon.launch.<builder>``
span of the trace is the k-th ``XLA Modules`` event ``jit_<builder>`` on
each chip.  Where a builder's counts differ the matched numbers are None
and standard error says which.  For a launch ``[a, b]`` whose program starts
on chip c at ``s_c``, the chip last busy until ``e_c``::

    host    |--launch a..b--|          turn          |--- pull a..b ---|
    chip c  ####| e_c               s_c |################| L_c
                 <---- late_c --------->                  <-- wake -->
                      (from max(a, e_c))
                            <- late_after_return_c ->
                               (from max(b, e_c))

``late_c = max(0, s_c - max(a, e_c))``: the chip was free and the host had
begun the launch.  ``late_after_return_c = max(0, s_c - max(b, e_c))``: the
part after ``program(*args)`` had returned to Python.  ``skew = max_c s_c -
min_c s_c`` (one number a launch).  For a pull ``[a, b]`` at whose return
every chip is idle, ``wake = max(0, b - max(a, max_c L_c))``, ``L_c`` the end
of chip c's last operation before ``b``: how long the host still waited for
an array the device had finished (0 where a chip is busy at ``b``).

**The two clocks.**  The profiler puts host and device on one clock to about
a millisecond, differently in every trace (my chip run, PR 39: a program
0.9 ms BEFORE its launch began in one, 0.4 ms after in the next process).
Where a matched program starts before its launch the reduction says by how
much (``clocks_apart_ms``, and on standard error): what is summed over a
dozen boundaries is good to that, a difference of tens of milliseconds in
one turn is not touched by it, and the host's own milliseconds
(``turn_host``, the ring) not at all.

None where there is no trace, no device operation or no query span in it,
or the program opens no such span (``turn``, ``outside``, ``turn_host``,
``turn_named_share`` need ``cylon.op.*``: a parent from before PR 39)."""

from __future__ import annotations

import bisect
import os
import sys

from lib import xplane, xspace

LAUNCH, PULL, TURN, OUTSIDE = "launch", "pull", "turn", "outside"
_KIND = {xspace.CYLON + "launch.": LAUNCH, xspace.CYLON + "pull.": PULL}
OP = xspace.CYLON + "op."
HOST = xspace.CYLON + "host."
_CACHE: dict = {}


def outermost(spans: list) -> list:
    """Those of ``(name, t0, t1)`` that lie inside no other of them."""
    out = []
    for sp in sorted(spans, key=lambda s: (s[1], -s[2])):
        if not out or sp[2] > out[-1][2]:
            out.append(sp)
    return out


def tile(ops: list, bounds: list, lo: float, hi: float) -> list:
    """``[lo, hi]`` cut into ``(t0, t1, class, index)`` where the class
    changes: ``bounds`` are ``(class, name, t0, t1)`` (launches and pulls),
    ``ops`` the outermost operator calls ``(name, t0, t1)``.  ``index`` is
    the boundary's place in ``bounds`` for a launch or a pull; for a turn
    the place of the boundary it FOLLOWS inside its operator call, or the
    operator call's name where it is the call's first stretch; None
    outside.  One sweep; the innermost of nested boundaries is the one
    that started last."""
    cuts = [(lo, 2, None), (hi, 2, None)]
    for i, (_cls, _name, t0, t1) in enumerate(bounds):
        if t1 > max(t0, lo) and t0 < hi:
            cuts += [(max(t0, lo), 1, i), (min(t1, hi), 0, i)]
    for name, t0, t1 in ops:
        if t1 > max(t0, lo) and t0 < hi:
            cuts += [(max(t0, lo), 1, name), (min(t1, hi), 0, name)]
    cuts.sort(key=lambda c: (c[0], c[1]))      # ends before starts
    out, active, in_ops, follows, prev = [], [], 0, None, lo
    for t, kind, what in cuts:
        if t > prev:
            if active:
                seg = (prev, t, bounds[active[-1]][0], active[-1])
            elif in_ops:
                seg = (prev, t, TURN, follows)
            else:
                seg = (prev, t, OUTSIDE, None)
            if out and out[-1][2:] == seg[2:] and out[-1][1] == prev:
                out[-1] = (out[-1][0], t) + seg[2:]
            else:
                out.append(seg)
            prev = t
        if kind == 2:
            continue
        if isinstance(what, str):              # an operator call
            in_ops += 1 if kind else -1
            if kind and in_ops == 1:
                follows = what
        elif kind:
            active.append(what)
        else:
            active.remove(what)
            follows = what
    return out


def covered(segments: list, spans: list) -> float:
    """Length of the TURN segments inside the union of ``spans``
    (``(name, t0, t1)``)."""
    union = xplane._union([(s[1], s[2]) for s in spans])
    total = 0.0
    for t0, t1, cls, _i in segments:
        if cls == TURN:
            total += sum(min(t1, e) - max(t0, s) for s, e in union
                         if e > t0 and s < t1)
    return total


def _gaps(ops: list, w0: float, w1: float) -> list:
    merged = xplane._union([(max(s, w0), min(s + d, w1))
                            for _lab, _stg, s, d in ops
                            if s + d > w0 and s < w1])
    edges = [w0] + [t for iv in merged for t in iv] + [w1]
    return [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]


def _idle_by_segment(segments: list, gaps: list, idle: list) -> None:
    """Adds each gap's overlap with each segment to ``idle`` (both lists
    sorted and disjoint: two pointers)."""
    i = 0
    for g0, g1 in gaps:
        while i < len(segments) and segments[i][1] <= g0:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < g1:
            idle[j] += min(g1, segments[j][1]) - max(g0, segments[j][0])
            j += 1


def _match(launches: list, device: dict):
    """``{launch's place: {chip: program start}}`` by order, or the
    sentence that says which counts differ."""
    by_builder: dict = {}
    for i, (_cls, name, _t0, _t1) in launches:
        by_builder.setdefault(name.split(".", 2)[2], []).append(i)
    starts: dict = {}
    for plane, chip in device.items():
        mods: dict = {}
        for builder, s, _d in chip["modules"]:
            mods.setdefault(builder, []).append(s)
        for builder, places in by_builder.items():
            got = mods.get(builder, [])
            if len(got) != len(places):
                return None, (f"{len(places)} cylon.launch.{builder} spans "
                              f"but {len(got)} jit_{builder} programs on "
                              f"{plane}")
            for i, s in zip(places, got):
                starts.setdefault(i, {})[plane] = s
    return starts, None


def round_trips(events: dict) -> dict | None:
    """The reduction of :func:`xspace.read_events`' ``events``; see the
    module's text.  ``trips``: per boundary of a query, by its place, the
    means over the traced queries (``host_ms``, ``idle_ms``, and ``late_ms``
    / ``late_after_return_ms`` / ``skew_ms`` or ``wake_ms``), each followed
    by the turn after it (``turn_host_ms``, ``turn_idle_ms``,
    ``turn_named``: the ``cylon.host.*`` spans open in it)."""
    queries = sorted((s, s + d) for n, s, d in events["spans"]
                     if n == xplane.QUERY)
    if not events["device"] or not queries:
        return None
    w0, w1 = queries[0][0], max(q[1] for q in queries)
    nq, nc = len(queries), len(events["device"])
    per_q, per_qc = 1e6 * nq, 1e6 * nq * nc          # ns -> ms a query
    host = [(n, s, s + d) for n, s, d, _a in events["host"]]
    bounds = sorted(((cls, n, s, e) for n, s, e in host
                     for pre, cls in _KIND.items() if n.startswith(pre)),
                    key=lambda b: (b[2], -b[3]))
    ops = outermost([h for h in host if h[0].startswith(OP)])
    named = [h for h in host if h[0].startswith(HOST)]
    segments = tile(ops, bounds, w0, w1)

    # ---- the device's idle gaps, by segment ------------------------------
    idle = [0.0] * len(segments)
    busy = {}
    for plane, chip in events["device"].items():
        _idle_by_segment(segments, _gaps(chip["ops"], w0, w1), idle)
        busy[plane] = xplane._union([(s, s + d)
                                     for _l, _g, s, d in chip["ops"]])
    idle_ms = dict.fromkeys((PULL, LAUNCH, TURN, OUTSIDE), 0.0)
    host_ms = dict.fromkeys((PULL, LAUNCH, TURN, OUTSIDE), 0.0)
    for (t0, t1, cls, _i), g in zip(segments, idle):
        idle_ms[cls] += g / per_qc
        host_ms[cls] += (t1 - t0) / per_q
    in_ops = [s for s in segments if s[2] != OUTSIDE]
    out = {"n_queries": nq, "n_chips": nc,
           "query_ms": [(e - s) / 1e6 for s, e in queries],
           "idle_ms": idle_ms, "host_ms": host_ms,
           "op_ms": sum(min(e, w1) - max(s, w0) for _n, s, e in ops
                        if e > w0 and s < w1) / per_q,
           "has_ops": bool(ops),
           "has": {cls: any(b[0] == cls for b in bounds)
                   for cls in (LAUNCH, PULL)},
           "turn_named_ms": covered(in_ops, named) / per_q}

    # ---- launch <-> program, pull <-> the device's last operation --------
    inside = [(i, b) for i, b in enumerate(bounds)
              if b[2] >= w0 and b[3] <= w1]
    starts, why = _match([(i, b) for i, b in enumerate(bounds)
                          if b[0] == LAUNCH], events["device"])
    out["mismatch"] = why
    if why:
        print(f"trace_round_trips: launches and programs do not match by "
              f"order: {why}", file=sys.stderr)
    busy_from = {p: [iv[0] for iv in ivs] for p, ivs in busy.items()}
    matched: dict = {}
    early = 0.0             # the most a program starts BEFORE its launch
    for i, (cls, _name, a, b) in inside:
        if cls == LAUNCH and starts is not None:
            late = after = 0.0
            for plane, s in starts[i].items():
                early = max(early, a - s)
                k = bisect.bisect_left(busy_from[plane], s) - 1
                e = busy[plane][k][1] if k >= 0 else float("-inf")
                late += max(0.0, s - max(a, e)) / nc
                after += max(0.0, s - max(b, e)) / nc
            sc = starts[i].values()
            matched[i] = {"late_ms": late / 1e6,
                          "late_after_return_ms": after / 1e6,
                          "skew_ms": (max(sc) - min(sc)) / 1e6}
        elif cls == PULL:
            last, busy_at_b = float("-inf"), False
            for plane, ivs in busy.items():
                k = bisect.bisect_left(busy_from[plane], b) - 1
                if k >= 0:
                    busy_at_b |= ivs[k][1] > b
                    last = max(last, ivs[k][1])
            matched[i] = {"wake_ms": 0.0 if busy_at_b
                          else max(0.0, b - max(a, last)) / 1e6}
    for key in ("late_ms", "late_after_return_ms", "skew_ms", "wake_ms"):
        got = [m[key] for m in matched.values() if key in m]
        out[key] = sum(got) / nq if got else None
    out["clocks_apart_ms"] = early / 1e6
    if early:
        print(f"trace_round_trips: a program starts {early / 1e6:.3f} ms "
              "before its launch began: this trace's host and device clocks "
              "are at least that far apart, and late / wake / the cuts "
              "between the classes are good to no less", file=sys.stderr)

    # ---- by name: the round trips of a query, by their place -------------
    def query_of(t):
        return next((k for k, (s, e) in enumerate(queries) if s <= t <= e),
                    None)

    place: dict = {}                       # boundary -> (query, ordinal)
    seen = [0] * nq
    for i, b in inside:
        k = query_of(b[2])
        if k is not None:
            place[i] = (k, seen[k])
            seen[k] += 1
    trips: dict = {}

    def trip(key):
        return trips.setdefault(key, {
            "host_ms": 0.0, "idle_ms": 0.0, "turn_host_ms": 0.0,
            "turn_idle_ms": 0.0, "turn_named": set()})

    def short(name):
        return name[len(xspace.CYLON):]

    for i, (_k, ordinal) in place.items():
        t = trip((ordinal, short(bounds[i][1])))
        for key, v in matched.get(i, {}).items():
            t[key] = t.get(key, 0.0) + v / nq
    for (t0, t1, cls, i), g in zip(segments, idle):
        if cls == OUTSIDE or (cls != TURN and i not in place):
            continue
        if cls != TURN or i in place:
            t = trip((place[i][1], short(bounds[i][1])))
        else:   # an operator call's first stretch (or before the queries)
            t = trip((-1, f"{short(i)} begins" if isinstance(i, str)
                      else "before the traced queries"))
        if cls != TURN:
            t["host_ms"] += (t1 - t0) / per_q
            t["idle_ms"] += g / per_qc
            continue
        t["turn_host_ms"] += (t1 - t0) / per_q
        t["turn_idle_ms"] += g / per_qc
        t["turn_named"] |= {short(n) for n, s, e in named
                            if e > t0 and s < t1}
    out["trips"] = [dict(v, place=k[0], name=k[1],
                         turn_named=sorted(v["turn_named"]))
                    for k, v in sorted(trips.items())]
    return out


_WHAT = {"idle_in_pull": lambda r: r["idle_ms"][PULL] if r["has"][PULL]
         else None,
         "idle_in_launch": lambda r: r["idle_ms"][LAUNCH]
         if r["has"][LAUNCH] else None,
         "idle_in_turn": lambda r: r["idle_ms"][TURN] if r["has_ops"]
         else None,
         "idle_outside_ops": lambda r: r["idle_ms"][OUTSIDE]
         if r["has_ops"] else None,
         "turn_host": lambda r: r["host_ms"][TURN] if r["has_ops"] else None,
         "turn_named_share": lambda r: 100.0 * r["turn_named_ms"]
         / r["host_ms"][TURN] if r["has_ops"] and r["host_ms"][TURN] else None,
         "launch_to_start": lambda r: r["late_ms"],
         "launch_start_after_return": lambda r: r["late_after_return_ms"],
         "launch_chip_skew": lambda r: r["skew_ms"],
         "pull_wake": lambda r: r["wake_ms"]}


def of_trace(path: str) -> dict | None:
    """:func:`round_trips` of the trace at ``path``, parsed once per
    (path, mtime)."""
    key = (os.path.abspath(path), os.path.getmtime(path))
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = round_trips(xspace.read_events(path))
    return _CACHE[key]


def read(ctx: dict, args: dict):
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out")
    path = xspace.newest_trace(out_dir)
    if path is None:
        return None
    reduced = of_trace(path)
    return None if reduced is None else _WHAT[args["what"]](reduced)
