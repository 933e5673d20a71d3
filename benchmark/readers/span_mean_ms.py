"""Mean milliseconds of the benchmark's span ``args["span"]`` over the
traced queries (host clock, around the call into the layer)."""


def read(ctx: dict, args: dict):
    d = [t1 - t0 for name, t0, t1 in ctx["spans"] if name == args["span"]]
    return 1e3 * sum(d) / len(d) if d else None
