"""groupby-sum, then sort by the aggregate, on one resident table:
``relational.groupby_aggregate`` -> ``relational.sort_table``."""

from __future__ import annotations

import numpy as np

from lib import tables as device_tables

SPANS = ("groupby_call", "sort_call")


def make_tables(env, host: dict, q: dict) -> dict:
    return device_tables.from_host(env, host)


def query(tables: dict, q: dict, span):
    from cylon_tpu.relational import groupby_aggregate, sort_table
    with span("groupby_call"):
        g = groupby_aggregate(tables[q["table"]], q["group_by"],
                              [tuple(a) for a in q["aggs"]])
        device_tables.ready(g)
    with span("sort_call"):
        s = sort_table(g, q["sort_by"], ascending=q["ascending"])
        device_tables.ready(s)
    return s


def _rows_by_sum_then_key(sums, keys, ascending: bool) -> tuple:
    """``(sums, keys)`` ordered by (sum, key).  Where both fit into one
    int64 side by side they are sorted as one number (numpy's plain sort, a
    third of a lexsort's time at 15M rows)."""
    bits = int(keys.max()).bit_length() if len(keys) else 1
    if ascending and len(sums) and sums.min() >= 0 and keys.min() >= 0 \
            and int(sums.max()).bit_length() + bits <= 62:
        packed = np.sort((sums << bits) | keys)
        return packed >> bits, packed & ((1 << bits) - 1)
    order = np.lexsort((keys, sums if ascending else -sums))
    return sums[order], keys[order]


def canonical(cols: dict, q: dict, seed: int) -> dict:
    """The sort column stays exactly as the program returned it.  Rows
    whose sort values are equal may come in any order (the configuration's
    ``ties``), so the key column is put in (sum, key) order - which, when
    the sort column is in order, moves keys only inside runs of equal sums,
    and cannot make a wrong order look right, since the sort column itself
    is compared as it came (and ``sort_inversions`` counts on it)."""
    by, key = q["sort_by"], q["group_by"]
    s = np.asarray(cols[by])
    _, keys = _rows_by_sum_then_key(s, np.asarray(cols[key]), q["ascending"])
    return {key: keys, by: s}


def reference(host: dict, q: dict, seed: int, acc=np.int64) -> dict:
    """Plain numpy, nothing of the program: sum per key, then rows by
    (sum, key).  Every group is compared: no sample."""
    t = host[q["table"]]
    by = q["group_by"]
    k = t[by]
    if k.min() < 0:
        raise ValueError("reference: keys are non-negative")
    (col, op), = q["aggs"]
    if op != "sum" or q["sort_by"] != f"{col}_{op}":
        raise ValueError("reference: one sum, sorted by it")
    n_keys = int(k.max()) + 1
    sums = np.zeros(n_keys, acc)
    np.add.at(sums, k, t[col].astype(acc))
    keys = np.flatnonzero(np.bincount(k, minlength=n_keys)).astype(np.int64)
    sums, keys = _rows_by_sum_then_key(sums[keys].astype(np.int64), keys,
                                       q["ascending"])
    return {by: keys, q["sort_by"]: sums}


def control(host: dict, q: dict, seed: int) -> dict:
    """The reference with its sums accumulated in float32."""
    return reference(host, q, seed, acc=np.float32)


def extra_numbers(host: dict, cols: dict, q: dict) -> list:
    """Total order on the sort key, said on its own."""
    s = np.asarray(cols[q["sort_by"]])
    bad = s[1:] < s[:-1] if q["ascending"] else s[1:] > s[:-1]
    return [("sort_inversions", int(np.count_nonzero(bad)), 0)]


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    return []
