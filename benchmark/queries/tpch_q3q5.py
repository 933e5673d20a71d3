"""TPC-H Q3 then Q5 on six resident tables, through ``cylon_tpu.tpch`` and
the ``DataFrame`` API, money as the spec's DECIMAL: one iteration is
``tpch.q3(dfs)`` then ``tpch.q5(dfs)``, both results ready on the device.

The generator (``lib/generate.py``) draws every column on its own;
:func:`derive` adds what depends on another table (a line's dates: its
order's date + the drawn delays) or on the spec (the 25 nations, the 5
regions) - one pure host function that ``make_tables``, ``reference`` and
``control`` all call.  A dictionary string is its int32 code on the host;
the words are ``query.vocabulary``'s, in code order.

The reference is numpy and nothing of the program, and is built otherwise
than the engine: keys are dense, so ``orders[l_orderkey]``,
``customer[o_custkey]``, ``supplier[l_suppkey]`` are direct indexing - no
join, no sort of the inputs -, sums are ``np.add.at`` on exact int64 (a
line's revenue is ``cents * (100 - discount)``: scale 4), the order is
``np.lexsort``."""

from __future__ import annotations

import numpy as np

from lib import tables as device_tables

SPANS = ("q3_call", "q5_call")
DAY_NS = 86_400_000_000_000
Q3_COLUMNS = ("l_orderkey", "revenue", "o_orderdate", "o_shippriority")

#: what ``own_checks`` (which has the device tables) keeps for
#: ``extra_numbers`` (which has the host tables): Q3 without its LIMIT and
#: Q5's joined-row count, of the run in this process
_KEPT: dict = {}


# ---------------------------------------------------------------------------
# the tables
# ---------------------------------------------------------------------------

def derive(host: dict, q: dict) -> dict:
    """``{table: {column: array}}`` as the deployment holds them: the drawn
    columns, the derived ones added, the drawn delays dropped.  Pure; dates
    are ``datetime64[ns]``, money is whole cents, strings are codes."""
    out = {t: dict(cols) for t, cols in host.items()}
    out["region"]["r_name"] = np.arange(len(q["regions"]), dtype=np.int32)
    nat = out["nation"]
    nat["n_name"] = np.arange(len(q["nations"]), dtype=np.int32)
    nat["n_regionkey"] = np.asarray(q["nation_region"], np.int64)[
        nat["n_nationkey"]]
    line = out["lineitem"]
    key = line["l_orderkey"]
    first = np.flatnonzero(np.r_[True, key[1:] != key[:-1]])
    runs = np.diff(np.r_[first, len(key)])
    line["l_linenumber"] = (np.arange(len(key), dtype=np.int64)
                            - np.repeat(first, runs) + 1)
    ship = out["orders"]["o_orderdate"].astype(np.int64)[key] \
        + line.pop("l_shipdelay") * DAY_NS
    line["l_shipdate"] = ship.astype("datetime64[ns]")
    line["l_commitdate"] = (ship + line.pop("l_commitdelay") * DAY_NS
                            ).astype("datetime64[ns]")
    line["l_receiptdate"] = (ship + line.pop("l_receiptdelay") * DAY_NS
                             ).astype("datetime64[ns]")
    return out


def _words(q: dict, column: str):
    return {"n_name": q["nations"], "r_name": q["regions"]}.get(
        column) or q["vocabulary"].get(column)


def make_tables(env, host: dict, q: dict) -> dict:
    """The six tables on the device through the typed ingest: money from
    its cents (``Column.from_scaled_ints``, the spec's precision and
    scale), a string from its codes and words (``Column.from_dictionary``)
    - no Python object a value -, the rest as ``from_pydict`` takes it."""
    import cylon_tpu as ct
    if not hasattr(ct.Column, "from_scaled_ints"):
        raise NotImplementedError(
            "this tree has no typed DECIMAL ingest (Column.from_scaled_ints, "
            "Column.from_dictionary) and no DECIMAL arithmetic: it cannot "
            "hold the deployment's money on the spec's types")
    out = {}
    for name, cols in derive(host, q).items():
        typed = {}
        for c, v in cols.items():
            if c in q["decimal"]:
                p, s = q["decimal"][c]
                typed[c] = ct.Column.from_scaled_ints(v, s, p)
            elif _words(q, c) is not None:
                typed[c] = ct.Column.from_dictionary(v, _words(q, c))
            else:
                typed[c] = v
        out[name] = ct.Table.from_pydict(typed, env)
    return out


# ---------------------------------------------------------------------------
# the query
# ---------------------------------------------------------------------------

class Results:
    """Both results of one iteration, as the harness reads a result:
    ``row_count`` and ``host_columns()`` (``name -> (data, validity)``)."""

    def __init__(self, q3, q5):
        self.q3, self.q5 = q3, q5

    @property
    def row_count(self) -> int:
        return self.q3.row_count + self.q5.row_count

    def host_columns(self) -> dict:
        out = {f"q3.{n}": dv for n, dv in self.q3.host_columns().items()}
        h5 = self.q5.host_columns()
        codes, valid = h5["n_name"]
        words = self.q5.columns["n_name"].dictionary
        out["q5.n_name"] = (np.asarray(words)[codes].astype(str), valid)
        out["q5.revenue"] = h5["revenue"]
        return out


def _frames(tables: dict) -> dict:
    from cylon_tpu import DataFrame
    return {n: DataFrame.from_table(t) for n, t in tables.items()}


def query(tables: dict, q: dict, span) -> Results:
    """One iteration: Q3 then Q5, each ready on the device when its span
    closes."""
    from cylon_tpu import tpch
    dfs = _frames(tables)
    with span("q3_call"):
        r3 = tpch.q3(dfs, **q["q3"]).table
        device_tables.ready(r3)
    with span("q5_call"):
        r5 = tpch.q5(dfs, **q["q5"]).table
        device_tables.ready(r5)
    return Results(r3, r5)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _ns(date: str) -> int:
    return int(np.datetime64(date, "ns").astype(np.int64))


def _line_revenue(line: dict, rows, acc):
    """``l_extendedprice * (1 - l_discount)`` of ``rows`` at scale 4."""
    return (line["l_extendedprice"][rows].astype(acc)
            * (100 - line["l_discount"][rows]).astype(acc))


def q3_groups(t: dict, q: dict, acc=np.int64) -> dict:
    """Every group of Q3 (no LIMIT), in Q3's order with ties by key."""
    p = q["q3"]
    date = _ns(p["date"])
    cust, orders, line = t["customer"], t["orders"], t["lineitem"]
    seg = q["vocabulary"]["c_mktsegment"].index(p["segment"])
    odate = orders["o_orderdate"].astype(np.int64)
    order_ok = (odate < date) & (cust["c_mktsegment"] == seg)[
        orders["o_custkey"]]
    rows = np.flatnonzero((line["l_shipdate"].astype(np.int64) > date)
                          & order_ok[line["l_orderkey"]])
    key = line["l_orderkey"][rows]
    sums = np.zeros(len(odate), acc)
    np.add.at(sums, key, _line_revenue(line, rows, acc))
    keys = np.flatnonzero(np.bincount(key, minlength=len(odate))
                          ).astype(np.int64)
    rev = sums[keys].astype(np.int64)
    order = np.lexsort((keys, odate[keys], -rev))
    keys = keys[order]
    return {"l_orderkey": keys, "revenue": rev[order],
            "o_orderdate": odate[keys],
            "o_shippriority": orders["o_shippriority"][keys]}


def q5_rows(t: dict, q: dict):
    """Q5's joined rows before the groupby: ``(lineitem rows, nation)``."""
    p = q["q5"]
    lo, hi = _ns(p["date_lo"]), _ns(p["date_hi"])
    cust, orders, line = t["customer"], t["orders"], t["lineitem"]
    region = q["regions"].index(p["region"])
    in_region = t["nation"]["n_regionkey"] == region   # by n_nationkey
    odate = orders["o_orderdate"].astype(np.int64)
    cust_nation = np.where((odate >= lo) & (odate < hi),
                           cust["c_nationkey"][orders["o_custkey"]], -1)
    line_nation = cust_nation[line["l_orderkey"]]
    rows = np.flatnonzero(
        (line_nation == t["supplier"]["s_nationkey"][line["l_suppkey"]])
        & in_region[np.maximum(line_nation, 0)])
    return rows, line_nation[rows]


def reference(host: dict, q: dict, seed: int, acc=np.int64) -> dict:
    """Q3's first ``limit`` rows and Q5's five, as int64 columns."""
    t = derive(host, q)
    g = q3_groups(t, q, acc)
    out = {f"q3.{n}": g[n][:q["q3"]["limit"]] for n in Q3_COLUMNS}
    rows, nation = q5_rows(t, q)
    sums = np.zeros(len(q["nations"]), acc)
    np.add.at(sums, nation, _line_revenue(t["lineitem"], rows, acc))
    nations = np.flatnonzero(np.bincount(nation, minlength=len(sums))
                             ).astype(np.int64)
    rev = sums[nations].astype(np.int64)
    order = np.lexsort((nations, -rev))
    out["q5.n_nationkey"] = nations[order]
    out["q5.revenue"] = rev[order]
    return out


def control(host: dict, q: dict, seed: int) -> dict:
    """The reference with revenue computed and summed in float32."""
    return reference(host, q, seed, acc=np.float32)


def _ties_by_key(cols: dict, prefix: str = "") -> dict:
    """Q3's columns with each run of equal (revenue, o_orderdate) ordered by
    l_orderkey: revenue and o_orderdate stay as they came, so a wrong order
    cannot be made to look right (``extra_numbers`` counts inversions on
    them as they came)."""
    rev = np.asarray(cols[prefix + "revenue"])
    date = np.asarray(cols[prefix + "o_orderdate"]).astype(np.int64)
    key = np.asarray(cols[prefix + "l_orderkey"])
    run = np.cumsum(np.r_[False, (rev[1:] != rev[:-1])
                          | (date[1:] != date[:-1])])
    order = np.lexsort((key, run))
    return {"l_orderkey": key[order], "revenue": rev, "o_orderdate": date,
            "o_shippriority": np.asarray(
                cols[prefix + "o_shippriority"])[order]}


def canonical(cols: dict, q: dict, seed: int) -> dict:
    """The pulled columns as the reference's: int64 (scaled revenue,
    nanoseconds, the nation's key for its name), Q3's ties by key."""
    out = {f"q3.{n}": v for n, v in _ties_by_key(cols, "q3.").items()}
    key_of = {w: i for i, w in enumerate(q["nations"])}
    out["q5.n_nationkey"] = np.asarray(
        [key_of[w] for w in cols["q5.n_name"]], np.int64)
    out["q5.revenue"] = np.asarray(cols["q5.revenue"])
    return out


def _inversions(rev, date) -> int:
    """Rows out of (revenue desc, date asc) order against their
    predecessor."""
    return int(np.count_nonzero(
        (rev[1:] > rev[:-1]) | ((rev[1:] == rev[:-1])
                                & (date[1:] < date[:-1]))))


def extra_numbers(host: dict, cols: dict, q: dict) -> list:
    """The order said on its own, and what ``own_checks`` kept of this
    process's run, held to the reference: Q3 without its LIMIT, every
    group; Q5's joined rows before its groupby."""
    rev3 = np.asarray(cols["q3.revenue"])
    rev5 = np.asarray(cols["q5.revenue"])
    numbers = [
        ("q3_sort_inversions", _inversions(
            rev3, np.asarray(cols["q3.o_orderdate"]).astype(np.int64)), 0),
        ("q5_sort_inversions", int(np.count_nonzero(rev5[1:] > rev5[:-1])),
         0)]
    if not _KEPT:
        return numbers
    t = derive(host, q)
    want = q3_groups(t, q)
    got = _ties_by_key(_KEPT["q3_all"])
    numbers += [
        ("q3_all_groups_diff", abs(len(got["revenue"])
                                   - len(want["revenue"])), 0),
        ("q3_all_sort_inversions",
         _inversions(got["revenue"], got["o_orderdate"]), 0)]
    n = min(len(got["revenue"]), len(want["revenue"]))
    numbers += [(f"q3_all_cells_differ.{c}", int(np.count_nonzero(
        got[c][:n] != want[c][:n])), 0) for c in Q3_COLUMNS]
    numbers.append(("q5_joined_rows_diff", abs(
        _KEPT["q5_joined_rows"] - len(q5_rows(t, q)[0])), 0))
    return numbers


def _join_rows_out(plan_dict: dict) -> list:
    """``rows_out`` of every ``join`` node of a plan, pre-order."""
    out = []

    def walk(d):
        if d.get("op") == "join":
            out.append(d.get("rows_out"))
        for c in d.get("children", ()):
            walk(c)
    for root in plan_dict["roots"]:
        walk(root)
    return out


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    """After the window.  Q3 once more without its LIMIT, pulled and kept
    for ``extra_numbers``; Q5 once more under EXPLAIN ANALYZE for its last
    join's ``rows_out`` - a ten-row answer alone would not hold a 30M-row
    plan to its guarantees.  And the types, from the columns themselves:
    money DECIMAL in the tables (the spec's scale), in both results (scale
    4), no float column in either."""
    from cylon_tpu import LogicalType, obs, tpch
    dfs = _frames(tables)
    r3 = tpch.q3(dfs, **dict(q["q3"], limit=None)).table
    _KEPT["q3_all"] = {n: d for n, (d, _v) in r3.host_columns().items()}
    plan = obs.explain_analyze(lambda: tpch.q5(dfs, **q["q5"]),
                               profile_keys=False)
    r5 = plan.result.table
    joins = _join_rows_out(plan.to_dict())
    _KEPT["q5_joined_rows"] = int(joins[-1])
    say(f"own checks: Q3 without LIMIT {r3.row_count} groups; Q5 joins' "
        f"rows_out {joins}")
    not_decimal = sum(
        1 for t in tables.values() for n, c in t.columns.items()
        if n in q["decimal"] and not (
            c.type == LogicalType.DECIMAL
            and [c.dictionary.precision, c.dictionary.scale]
            == list(q["decimal"][n])))
    results = [c for r in (r3, r5) for c in r.columns.values()]
    revenue_wrong = sum(
        1 for r in (r3, r5) if not (
            r.columns["revenue"].type == LogicalType.DECIMAL
            and r.columns["revenue"].dictionary.scale == 4))
    floats = sum(1 for c in results if c.type in (
        LogicalType.FLOAT32, LogicalType.FLOAT64))
    return [("money_columns_not_decimal", not_decimal, 0),
            ("revenue_not_decimal_scale_4", revenue_wrong, 0),
            ("float_columns_in_results", floats, 0)]
