"""An argument of the program's own spans on the trace's host plane, summed
per traced query: ``args["arg"]`` (``bytes``, ``rows``: what
``utils/timing.span`` was given) of every ``cylon.*`` span inside the traced
queries whose name matches ``args["span"]``, times ``args["scale"]``.
``lib/xspace.reduce`` keeps the spans' seconds and counts only, so this
reads the events themselves.  None where the trace holds no such span or
the spans carry no such argument (a parent that opens none)."""

import os
import re

from lib import xplane, xspace


def read(ctx: dict, args: dict):
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "out")
    path = xspace.newest_trace(out_dir)
    if path is None:
        return None
    events = xspace.read_events(path)
    queries = [(s, s + d) for n, s, d in events["spans"]
               if n == xplane.QUERY]
    if not queries:
        return None
    w0, w1 = min(q[0] for q in queries), max(q[1] for q in queries)
    rx = re.compile(args["span"])
    values = [float(a[args["arg"]]) for name, s, d, a in events["host"]
              if rx.search(name) and s + d > w0 and s < w1
              and args["arg"] in a]
    if not values:
        return None
    return float(args.get("scale", 1.0)) * sum(values) / len(queries)
