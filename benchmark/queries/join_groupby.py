"""join -> groupby-sum on resident tables: ``relational.join_tables`` ->
``relational.groupby_aggregate`` through ``ct.Table.from_pydict``.

The parameters (tables, key, aggregates) come from the configuration's
``query`` block, so another key distribution or size is a data file."""

from __future__ import annotations

import numpy as np

from lib import tables as device_tables

SPANS = ("join_call", "groupby_call")


def make_tables(env, host: dict, q: dict) -> dict:
    return device_tables.from_host(env, host)


def query(tables: dict, q: dict, span):
    """One query, result ready on the device when it returns."""
    from cylon_tpu.relational import groupby_aggregate, join_tables
    j = q["join"]
    with span("join_call"):
        joined = join_tables(tables[j["left"]], tables[j["right"]],
                             j["on"], j["on"], how=j["how"])
    with span("groupby_call"):
        g = groupby_aggregate(joined, q["group_by"],
                              [tuple(a) for a in q["aggs"]])
        device_tables.ready(g)
    return g


def _sample_residue(q: dict, seed: int) -> tuple:
    """Sums are compared on a sample of the groups drawn from the seed:
    the keys of one residue class modulo ``check.key_sample_mod`` (rows of
    other keys never meet them in a join on the key, so the sample's
    reference needs only the sample's rows).  Membership is compared for
    every group (``extra_numbers``).  The full reference took 18 s at 32M
    rows a side, longer than the window; a quarter of it takes 5."""
    m = int(q.get("check", {}).get("key_sample_mod", 1))
    return m, int(np.random.default_rng([int(seed), 0x5A]).integers(m))


def canonical(cols: dict, q: dict, seed: int) -> dict:
    """The result's rows come in no promised order: the sampled groups,
    by group key."""
    m, r = _sample_residue(q, seed)
    k = np.asarray(cols[q["group_by"]])
    keep = np.flatnonzero(k % m == r)
    order = keep[np.argsort(k[keep], kind="stable")]
    return {name: np.asarray(v)[order] for name, v in cols.items()}


def _group_sums_of_joined(k_own, v_own, k_other, n_keys: int, acc):
    """Sum of ``v_own`` over the joined rows of each key.  An inner join
    pairs every row of this side with every row of the other side that
    has its key, so this side's row appears once per such row: the joined
    rows' (key, value) are written out and summed key by key."""
    times = np.bincount(k_other, minlength=n_keys)[k_own]
    keys = np.repeat(k_own, times)
    vals = np.repeat(v_own, times).astype(acc)
    out = np.zeros(n_keys, acc)
    np.add.at(out, keys, vals)
    return out


def _checked(q: dict) -> tuple:
    j = q["join"]
    if j["how"] != "inner" or q["group_by"] != j["on"]:
        raise ValueError("reference: inner join grouped by its key only")
    return j["left"], j["right"], j["on"]


def reference(host: dict, q: dict, seed: int, acc=np.int64) -> dict:
    """Plain numpy, nothing of the program: inner join on the key, then
    sum per key, rows by key, over the sampled keys.  ``acc``: the
    accumulator (the control's is float32)."""
    lname, rname, on = _checked(q)
    m, r = _sample_residue(q, seed)
    side = {}
    for name in (lname, rname):
        k = host[name][on]
        if k.min() < 0:
            raise ValueError("reference: keys are non-negative")
        rows = np.flatnonzero(k % m == r)
        side[name] = {c: v[rows] for c, v in host[name].items()}
        side[name][on] = side[name][on] // m      # dense again
    lk, rk = side[lname][on], side[rname][on]
    n_keys = int(max(lk.max(), rk.max())) + 1
    sums = {}
    for col, op in q["aggs"]:
        if op != "sum":
            raise ValueError(f"reference: no {op}")
        if col in side[lname]:
            sums[f"{col}_{op}"] = _group_sums_of_joined(
                lk, side[lname][col], rk, n_keys, acc)
        else:
            sums[f"{col}_{op}"] = _group_sums_of_joined(
                rk, side[rname][col], lk, n_keys, acc)
    both = np.flatnonzero((np.bincount(lk, minlength=n_keys) > 0)
                          & (np.bincount(rk, minlength=n_keys) > 0))
    res = {q["group_by"]: both.astype(np.int64) * m + r}
    res.update({name: s[both].astype(np.int64) for name, s in sums.items()})
    return res


def control(host: dict, q: dict, seed: int) -> dict:
    """The reference with its sums accumulated in float32."""
    return reference(host, q, seed, acc=np.float32)


def extra_numbers(host: dict, cols: dict, q: dict) -> list:
    """Every group, not the sample only.  Membership: the result holds each
    key that is on both sides exactly once, and no other.  Sums: a side's
    sum over the joined rows of a key is that side's own sum for the key
    times the other side's row count for it - an identity, which the
    sample's written-out joined rows check in the same run; it is what
    makes every group affordable (3 s, against 18 s written out)."""
    lname, rname, on = _checked(q)
    lk, rk = host[lname][on], host[rname][on]
    n_keys = int(max(lk.max(), rk.max())) + 1
    count = {lname: np.bincount(lk, minlength=n_keys),
             rname: np.bincount(rk, minlength=n_keys)}
    want = (count[lname] > 0) & (count[rname] > 0)
    k = np.asarray(cols[q["group_by"]])
    inside = (k >= 0) & (k < n_keys)
    got = np.bincount(k[inside], minlength=n_keys)
    numbers = [("keys_outside_range", int(np.count_nonzero(~inside)), 0),
               ("keys_wrong_multiplicity",
                int(np.count_nonzero(got != want)), 0)]
    for col, op in q["aggs"]:
        own, other = (lname, rname) if col in host[lname] else (rname, lname)
        per_key = np.zeros(n_keys, np.int64)
        np.add.at(per_key, host[own][on], host[own][col])
        per_key *= count[other]
        res = np.asarray(cols[f"{col}_{op}"])[inside]
        numbers.append((f"all_groups_differ.{col}_{op}", int(
            np.count_nonzero(res != per_key[k[inside]])), 0))
    return numbers


def own_checks(env, tables: dict, q: dict, n_groups: int,
               expect: dict, say) -> list:
    """The gather variant the fused join->groupby callsite settled on
    (``relational/fused._SEG_CACHE``: segment bucket, windowed allowed,
    window), held to ``fused.window_for``'s own rule at the density the
    result shows.  After ``chip_smoke.py``'s ``gather_variants``."""
    from cylon_tpu.relational import fused
    sites = [v for k, v in fused._SEG_CACHE.items()
             if k[0] == env.serial and isinstance(v, tuple)]
    j = q["join"]
    rows = tables[j["left"]].row_count + tables[j["right"]].row_count
    missed = 0
    for seg, allowed, window in sites:
        eligible = fused.window_for(env.mesh, int(seg), n_groups / rows) > 0
        say(f"gather: segment_space={int(seg)} windowed_allowed="
            f"{bool(allowed)} window={int(window)} eligible={eligible}")
        missed += int(eligible and not window)
    numbers = [("fused_callsites_not_1", abs(len(sites) - 1), 0)]
    if expect.get("windowed_gather_if_eligible"):
        numbers.append(("windowed_gather_missed", missed, 0))
    return numbers
