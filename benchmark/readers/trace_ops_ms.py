"""Device milliseconds per traced query of the operations whose HLO name
matches ``args["pattern"]`` (summed durations from the device plane)."""

from lib import xplane


def read(ctx: dict, args: dict):
    tr = ctx["trace"]
    if tr is None:
        return None
    s = xplane.op_seconds_matching(tr, args["pattern"])
    return None if s is None else 1e3 * s / tr["n_queries"]
