"""Share (%) of the device's operation seconds, inside the traced queries,
spent in operations with no ``cylon.<stage>``: what the stage metrics
cannot see (XLA's own copies at a program's boundary).  None where NO
operation carries a stage - a program from before the scopes existed has
nothing to read, which is not "100% unscoped"."""

from lib import xspace


def read(ctx: dict, args: dict):
    tr = xspace.reduced_of_this_run()
    if tr is None or not tr["ops_s"]:
        return None
    if not set(tr["stage_s"]) - {None, xspace.SCAN}:   # SCAN: by opcode too
        return None
    return 100.0 * tr["stage_s"].get(None, 0.0) / tr["ops_s"]
