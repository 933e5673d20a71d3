"""The distributed groupby-sum -> sort_values: one table partitioned over
the mesh's chips, ``relational.groupby_aggregate`` (each chip combines its
own rows, a hash shuffle of the partial rows, each chip's final reduce) ->
``relational.sort_table`` (sampled splitters picked on the host, a range
exchange, each chip's local sort).

The query, the plain numpy reference (global table in, global result
out), the float32 control and the comparison are ``queries/groupby_sort``'s
own, taken from that file as it stands: the same semantics on the same
data give the same answers whatever the world size.  The result comes back
in mesh order, so ``sort_inversions`` and the row-by-row comparison of the
sort column hold the chips' results to ONE total order.  What this module
adds is ``own_checks``: the table really is spread over the chips, the
rows really crossed chips in BOTH exchanges, and the program's own count
says the sort took the sample-sort route.

A tree from before PR 44 answers the query exactly and runs this module
as it stands: it has no ``sort_sample_sorts`` counter, so that one check is
left out there (the plan's ``sample_sort`` route is checked either way).
"""

from __future__ import annotations

import os

import numpy as np

from lib import files
from lib import tables as device_tables

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_LOCAL = files.load_module(_BENCH_DIR, "queries", "groupby_sort")

SPANS = _LOCAL.SPANS
make_tables = _LOCAL.make_tables
query = _LOCAL.query
reference = _LOCAL.reference
control = _LOCAL.control
canonical = _LOCAL.canonical
extra_numbers = _LOCAL.extra_numbers

#: the registry's count of sorts that took the ``sample_sort`` route (the
#: program registers it where ``relational/sort`` is imported, since PR 44)
_SAMPLE_SORTS = "sort_sample_sorts"


def _half(comm, obs, call):
    """``call()`` with the communication matrix armed and empty: its
    result, and ``(exchanges, rows moved, off-diagonal share)`` of the
    exchanges it ran."""
    moved = obs.counter("exchange_rows_total")
    count = obs.counter("exchange_count")
    rows0, n0 = moved.value, count.value
    comm.reset()
    out = call()
    device_tables.ready(out)
    rep = comm.report()
    m = np.asarray(rep["rows"], np.int64) if rep else np.zeros((1, 1))
    off = float(m.sum() - np.trace(m)) / max(float(m.sum()), 1.0)
    return out, (int(count.value - n0), int(moved.value - rows0), off)


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    """``queries/dist_join_groupby``'s exchange checks, an exchange at a
    time: the table spread evenly over every chip; one more query after
    the window, its two calls each under an armed and emptied
    communication matrix - exchanges a query as the workload file says,
    rows moved in each call, and the share of them that changed chip
    inside the file's range in each (a uniform hash over w chips moves
    (w-1)/w of the partial rows; so does a range partition of sums that
    do not depend on where a group lives)."""
    from cylon_tpu import obs
    from cylon_tpu.obs import comm
    from cylon_tpu.relational import groupby_aggregate, sort_table
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
    counted = _SAMPLE_SORTS in obs.snapshot()
    sample_sorts = obs.counter(_SAMPLE_SORTS)
    sorts_before = sample_sorts.value
    comm.arm(True)
    try:
        g, of_groupby = _half(comm, obs, lambda: groupby_aggregate(
            tables[q["table"]], q["group_by"],
            [tuple(a) for a in q["aggs"]]))
        s, of_sort = _half(comm, obs, lambda: sort_table(
            g, q["sort_by"], ascending=q["ascending"]))
        vc = np.asarray(s.valid_counts, np.int64)
        del g, s
    finally:
        comm.arm(False)
        comm.reset()
    lo, hi = expect["exchange"]["off_diagonal_share"]
    outside = no_rows = exchanges = 0
    for what, (n, rows, off) in (("groupby", of_groupby), ("sort", of_sort)):
        say(f"exchange, {what}: {rows} rows in {n} exchange(s), "
            f"off-diagonal share {off:.4f} (expected inside ({lo}, {hi}))")
        exchanges += n
        no_rows += int(rows <= 0)
        outside += int(not lo < off < hi)
    say(f"sorted result a chip: {vc.tolist()} rows (fullest "
        f"{vc.max() / max(vc.mean(), 1.0):.4f} of the mean)")
    taken = sample_sorts.value - sorts_before
    say(f"{_SAMPLE_SORTS}: {taken} in that query" if counted else
        f"{_SAMPLE_SORTS}: the program has no such counter")
    return _LOCAL.own_checks(env, tables, q, n_groups, expect, say) + [
        ("tables_not_spread_evenly", uneven, 0),
        ("calls_that_moved_no_rows", no_rows, 0),
        ("off_diagonal_share_outside_range", outside, 0),
        ("exchanges_per_query_off", abs(
            exchanges - int(expect["exchange"]["exchanges_per_query"])), 0),
    ] + ([("sample_sorts_of_one_query_off", abs(taken - 1), 0)]
         if counted else [])
