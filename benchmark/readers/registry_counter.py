"""Counters of the program's own registry (``cylon_tpu/obs/metrics``, as
``snapshot()`` names them: a labelled counter is ``name{label="value"}``):
the sum over the names that match ``args["counter"]`` over the sum over
the names that match ``args["per"]`` - over the whole process, warm-ups
and traced queries included, since the registry keeps no window.  E.g. the
dispatches that settled without the windowed gather over all settled
dispatches; where every query settles one grouped reduce (two programs a
query: the join's count and the fused reduce) that is the number per
query.  None where no name matches ``counter`` - a parent whose program
has no such counter - or ``per`` sums to 0."""

import re


def _total(snapshot: dict, pattern: str):
    rx = re.compile(pattern)
    hit = [v for name, v in snapshot.items()
           if rx.search(name) and isinstance(v, (int, float))]
    return sum(hit) if hit else None


def read(ctx: dict, args: dict):
    from cylon_tpu.obs import metrics
    snapshot = metrics.snapshot()
    value = _total(snapshot, args["counter"])
    per = _total(snapshot, args["per"])
    return value / per if value is not None and per else None
