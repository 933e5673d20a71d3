"""The distributed join -> groupby-sum: the tables partitioned over the
mesh's chips, ``relational.join_tables`` (hash shuffle of both sides, then
each chip's local join) -> ``relational.groupby_aggregate``.

The query, the plain numpy reference (global tables in, global result
out), the float32 control and the comparison are ``queries/join_groupby``'s
own, taken from that file as it stands: the same semantics on the same
data give the same answers whatever the world size.  What this module adds
is ``own_checks``: the rows really crossed chips, as a uniform hash would
send them, and the tables really are spread over the chips.

A tree from before PR 28 dies in XLA:TPU's compiler on a four-chip mesh,
compiling the fused program of its first dispatch (``PERF.md``, PR 28); the
import below, of the blocked 64-bit scan that PR 28's fix is, makes such a
tree stop here, in seconds, with an ImportError.
"""

from __future__ import annotations

import contextlib
import os
import sys

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.dirname(_BENCH_DIR) not in sys.path:
    sys.path.insert(0, os.path.dirname(_BENCH_DIR))

from cylon_tpu.ops.groupby import blocked_cumsum  # noqa: E402,F401

import numpy as np                      # noqa: E402

from lib import files                   # noqa: E402

_LOCAL = files.load_module(_BENCH_DIR, "queries", "join_groupby")

SPANS = _LOCAL.SPANS
make_tables = _LOCAL.make_tables
query = _LOCAL.query
reference = _LOCAL.reference
control = _LOCAL.control
canonical = _LOCAL.canonical
extra_numbers = _LOCAL.extra_numbers


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    """``queries/join_groupby``'s gather check, and ``chip_smoke.py``'s
    exchange checks as numbers with limits: every table spread evenly
    over every chip, rows moved through the exchange, and the share of
    them that changed chip (one more query, after the window, with the
    communication matrix armed) inside the workload's range - a uniform
    hash over w chips moves (w-1)/w of the rows."""
    from cylon_tpu import obs
    from cylon_tpu.obs import comm
    numbers = _LOCAL.own_checks(env, tables, q, n_groups, expect, say)
    w = env.world_size
    uneven = 0
    for name, t in tables.items():
        vc = np.asarray(t.valid_counts, np.int64)
        col = next(iter(t.columns.values())).data
        devs = {sh.device for sh in col.addressable_shards}
        shapes = {sh.data.shape for sh in col.addressable_shards}
        say(f"{name}: valid_counts={vc.tolist()} on {len(devs)} devices, "
            f"shard shapes {sorted(shapes)}")
        uneven += int(vc.shape != (w,) or vc.max() - vc.min() > 1
                      or len(devs) != w or len(shapes) != 1)
    moved = obs.counter("exchange_rows_total")
    count = obs.counter("exchange_count")
    before, n_before = moved.value, count.value
    comm.arm(True)
    comm.reset()
    try:
        query(tables, q, lambda name: contextlib.nullcontext())
        rep = comm.report()
    finally:
        comm.arm(False)
    m = np.asarray(rep["rows"], np.int64) if rep else np.zeros((w, w))
    off = float(m.sum() - np.trace(m)) / max(float(m.sum()), 1.0)
    lo, hi = expect["exchange"]["off_diagonal_share"]
    exchanges = int(count.value - n_before)
    say(f"exchange: {int(moved.value - before)} rows in {exchanges} "
        f"exchange(s) of one query, off-diagonal share {off:.4f} "
        f"(expected inside ({lo}, {hi}))")
    return numbers + [
        ("tables_not_spread_evenly", uneven, 0),
        ("query_moved_no_rows", int(moved.value - before <= 0), 0),
        ("off_diagonal_share_outside_range", int(not lo < off < hi), 0),
        ("exchanges_per_query_off", abs(
            exchanges - int(expect["exchange"]["exchanges_per_query"])), 0),
    ]
