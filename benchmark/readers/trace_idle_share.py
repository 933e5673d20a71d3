"""Share of the traced window in which no operation ran on the device."""


def read(ctx: dict, args: dict):
    tr = ctx["trace"]
    return None if tr is None else 100.0 * tr["idle_share"]
