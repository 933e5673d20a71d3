"""The program's own spans on the trace's host plane (``cylon.*``: every
``utils/timing.region``, ``cylon.launch.<builder>`` around a program's
enqueue, ``cylon.pull.<kind>`` around a host pull) whose name matches
``args["span"]``, per traced query: summed milliseconds (``what`` = ``ms``)
or how many (``count``).  None where the trace holds no such span."""

import re

from lib import xspace


def read(ctx: dict, args: dict):
    tr = xspace.reduced_of_this_run()
    if tr is None:
        return None
    rx = re.compile(args["span"])
    names = [n for n in tr["host_s"] if rx.search(n)]
    if not names:
        return None
    if args["what"] == "count":
        return sum(tr["host_n"][n] for n in names) / tr["n_queries"]
    if args["what"] == "ms":
        return 1e3 * sum(tr["host_s"][n] for n in names) / tr["n_queries"]
    raise ValueError(f"what: {args['what']!r} is neither ms nor count")
