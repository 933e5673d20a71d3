"""drop_duplicates, then union, then subtract, on two resident tables:
``relational.unique_table(a, subset, keep)`` -> ``relational.set_operation(a,
b, "union")`` -> ``set_operation(a, b, "subtract")`` - what PyCylon's
``DataFrame.drop_duplicates / union / subtract`` call - each result ready on
the device before the next operator starts.

A row is ``(k, v)`` with ``v`` one of ``query.versions`` values, so a whole
row is ONE int64, ``k * versions + v``, on the host.  The reference is numpy
and nothing of the program: ``np.unique`` of the packed rows (with
``return_index`` on the keys for keep-first), ``np.isin`` for the subtract;
every row of every result is compared, none sampled.  No order is promised,
so both sides come in ``(k, v)`` order."""

from __future__ import annotations

import numpy as np

from lib import tables as device_tables

SPANS = ("unique_call", "union_call", "subtract_call")
RESULTS = ("unique", "union", "subtract")

#: how many iterations ``query`` ran in this process: what the registry's
#: dispatch counters are held to (``own_checks``)
_RAN = [0]


def make_tables(env, host: dict, q: dict) -> dict:
    from cylon_tpu.relational import setops
    if not hasattr(setops, "plan_route"):
        raise NotImplementedError(
            "this tree's `unique` and `set_op` plan nodes name no route "
            "(relational/setops.plan_route) and it counts no set-operation "
            "dispatch: the cell cannot hold it to the normal path")
    return device_tables.from_host(env, host)


class Results:
    """The three results of one iteration, as the harness reads a result:
    ``row_count`` and ``host_columns()`` (``name -> (data, validity)``,
    each result's columns under its own prefix; the lengths differ)."""

    def __init__(self, tables: dict):
        self.tables = tables

    @property
    def row_count(self) -> int:
        return sum(t.row_count for t in self.tables.values())

    def host_columns(self) -> dict:
        return {f"{r}.{n}": dv for r, t in self.tables.items()
                for n, dv in t.host_columns().items()}


def query(tables: dict, q: dict, span) -> Results:
    from cylon_tpu.relational import set_operation, unique_table
    a, b = tables[q["left"]], tables[q["right"]]
    out = {}
    with span("unique_call"):
        out["unique"] = unique_table(a, subset=q["unique"]["subset"],
                                     keep=q["unique"]["keep"])
        device_tables.ready(out["unique"])
    for op in q["set_ops"]:
        with span(f"{op}_call"):
            out[op] = set_operation(a, b, op)
            device_tables.ready(out[op])
    _RAN[0] += 1
    return Results(out)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _checked(q: dict) -> tuple:
    if q["unique"] != {"subset": [q["key"]], "keep": "first"} \
            or q["set_ops"] != ["union", "subtract"]:
        raise ValueError("reference: keep-first by the key, then union, "
                         "then subtract")
    return q["left"], q["right"], q["key"], q["version"], int(q["versions"])


def _packed(t: dict, q: dict) -> np.ndarray:
    """Each row of ``t`` as one int64, ordered as ``(k, v)`` is."""
    _, _, key, ver, m = _checked(q)
    k, v = np.asarray(t[key]), np.asarray(t[ver])
    if k.dtype != np.int64 or v.dtype != np.int64:
        raise ValueError("rows are int64")
    if len(k) and (k.min() < 0 or v.min() < 0 or v.max() >= m):
        raise ValueError(f"reference: k >= 0 and 0 <= v < {m}")
    return k * m + v


def _columns(result: str, packed: np.ndarray, q: dict) -> dict:
    _, _, key, ver, m = _checked(q)
    return {f"{result}.{key}": packed // m, f"{result}.{ver}": packed % m}


def reference(host: dict, q: dict, seed: int, by_key_alone=False) -> dict:
    """Every row of the three results, each in ``(k, v)`` order.
    ``by_key_alone``: the control - union and subtract take two rows for
    one when their keys are equal, whatever their versions."""
    left, right, key, _, m = _checked(q)
    pa, pb = _packed(host[left], q), _packed(host[right], q)
    # keep-first: the row at the smallest position of each key
    _, first = np.unique(host[left][key], return_index=True)
    out = _columns("unique", pa[first], q)
    if by_key_alone:
        cat = np.concatenate([pa, pb])
        _, first_cat = np.unique(cat // m, return_index=True)
        union = cat[first_cat]
        kept = pa[first]
        subtract = kept[~np.isin(kept // m, np.unique(pb // m))]
    else:
        ua, ub = np.unique(pa), np.unique(pb)
        union = np.unique(np.concatenate([ua, ub]))
        subtract = ua[~np.isin(ua, ub, assume_unique=True)]
    out.update(_columns("union", union, q))
    out.update(_columns("subtract", subtract, q))
    return out


def control(host: dict, q: dict, seed: int) -> dict:
    """The reference with rows identified by the key alone."""
    return reference(host, q, seed, by_key_alone=True)


def canonical(cols: dict, q: dict, seed: int) -> dict:
    """The pulled columns, each result's rows in ``(k, v)`` order."""
    _, _, key, ver, _ = _checked(q)
    out = {}
    for r in RESULTS:
        packed = _packed({key: cols[f"{r}.{key}"], ver: cols[f"{r}.{ver}"]},
                         q)
        out.update(_columns(r, np.sort(packed), q))
    return out


def _present(packed: np.ndarray, size: int) -> np.ndarray:
    """Which packed rows occur, as a table over the whole domain: no sort,
    so it shares nothing with ``reference`` / ``canonical``."""
    seen = np.zeros(size, bool)
    seen[packed] = True
    return seen


def extra_numbers(host: dict, cols: dict, q: dict) -> list:
    """Membership said on its own, for every row and without a sort: no
    result holds a row twice (the unique: a key twice), the union's rows
    are exactly the rows of either table, the subtract's exactly the rows
    of ``a`` that ``b`` lacks, and the three row counts are the sets'."""
    left, right, key, ver, m = _checked(q)
    pa, pb = _packed(host[left], q), _packed(host[right], q)
    got = {r: _packed({key: cols[f"{r}.{key}"], ver: cols[f"{r}.{ver}"]}, q)
           for r in RESULTS}
    size = int(max(p.max() if len(p) else 0
                   for p in (pa, pb, *got.values()))) + 1
    in_a, in_b = _present(pa, size), _present(pb, size)
    n_a, n_b = int(in_a.sum()), int(in_b.sum())
    n_both = int((in_a & in_b).sum())
    keys = _present(got["unique"] // m, size // m + 1)
    in_union, in_sub = _present(got["union"], size), \
        _present(got["subtract"], size)
    return [
        ("unique_key_twice", len(got["unique"]) - int(keys.sum()), 0),
        ("unique_rows_not_of_a", int((~in_a[got["unique"]]).sum()), 0),
        ("unique_keys_diff", abs(int(keys.sum()) - int(
            _present(host[left][key], size // m + 1).sum())), 0),
        ("union_row_twice", len(got["union"]) - int(in_union.sum()), 0),
        ("union_rows_diff", abs(len(got["union"]) - (n_a + n_b - n_both)),
         0),
        ("union_rows_missing", int(((in_a | in_b) & ~in_union).sum()), 0),
        ("subtract_row_twice", len(got["subtract"]) - int(in_sub.sum()), 0),
        ("subtract_rows_diff", abs(len(got["subtract"]) - (n_a - n_both)),
         0),
        ("subtract_rows_of_b", int((in_sub & in_b).sum()), 0),
        ("subtract_rows_missing", int((in_a & ~in_b & ~in_sub).sum()), 0),
    ]


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    """The registry's dispatch counters, held to the iterations this
    process ran: three dispatches an iteration, one of each kind, none of
    another, and the rows they say they returned are the results'."""
    from cylon_tpu.obs import metrics
    snap = metrics.snapshot()
    ran = _RAN[0]

    def count(name: str, op: str) -> int:
        return int(snap.get(f'{name}{{op="{op}"}}', -1))
    say(f"own checks: {ran} iterations; setop_dispatches "
        + ", ".join(f"{op}={count('setop_dispatches', op)}"
                    for op in RESULTS + ("intersect",)))
    numbers = [(f"setop_dispatches_off.{op}",
                abs(count("setop_dispatches", op) - ran), 0)
               for op in RESULTS]
    numbers.append(("setop_dispatches_off.intersect",
                    abs(count("setop_dispatches", "intersect")), 0))
    numbers.append(("setop_rows_out_off", abs(sum(
        count("setop_rows_out", op) for op in RESULTS) - ran * n_groups), 0))
    return numbers
