"""A number the run counted or read from the program's own counters
(``ctx["counters"]``), times ``scale``."""


def read(ctx: dict, args: dict):
    value = ctx["counters"].get(args["key"])
    return None if value is None else value * float(args.get("scale", 1.0))
