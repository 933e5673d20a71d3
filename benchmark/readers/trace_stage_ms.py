"""Device milliseconds per traced query of the operations whose stage (the
innermost ``cylon.<stage>`` scope of their ``tf_op``, opened by
``cylon_tpu/utils/stages.stage``; ``lib/xspace.py`` says how it is read)
is ``args["stage"]``, in the programs whose builder matches the optional
``args["builder"]``.  None where no operation carries the stage."""

import re

from lib import xspace


def read(ctx: dict, args: dict):
    tr = xspace.reduced_of_this_run()
    if tr is None:
        return None
    rx = re.compile(args.get("builder", ""))
    hit = [s for (b, stg), s in tr["builder_stage_s"].items()
           if stg == args["stage"] and rx.search(b or "")]
    return 1e3 * sum(hit) / tr["n_queries"] if hit else None
