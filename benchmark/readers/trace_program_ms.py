"""Device milliseconds per traced query of the programs whose builder name
(``jit_<module>_<builder>`` on the trace's ``XLA Modules`` line, set by
``cylon_tpu/utils/cache.named_for_device``) matches ``args["builder"]``.
None where no program of that name ran: a parent whose programs are all
``per_shard``, a route that does not launch the builder."""

import re

from lib import xspace


def read(ctx: dict, args: dict):
    tr = xspace.reduced_of_this_run()
    if tr is None:
        return None
    rx = re.compile(args["builder"])
    hit = [s for b, s in tr["program_s"].items() if rx.search(b)]
    return 1e3 * sum(hit) / tr["n_queries"] if hit else None
