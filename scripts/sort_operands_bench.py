"""The join's key sort by operand count, at the join cells' state sizes:
what one more operand through ``lax.sort`` costs on one chip (ROADMAP S5,
PR 35).  Not part of the suite.

One stable 2-key ``lax.sort`` as ``ops/join.join_sort_state`` writes it -
the row-liveness flag (0 on the live rows, 4 / 5 on the padding), one
int32 key, then the ``idx`` iota and 0 to 3 uint32 payloads, so 3, 4, 5
and 6 operands: 4 is the join cells' sort since PR 35 and 6 what it was;
3 is what folding the liveness flag into a narrow key would leave of the
4; 5 is a wide (hi, lo) key's sort, to the operand.  Keys are drawn on the
device from ``--seed``, uniform in ``[0, 0.45 n)`` over the two sides'
live prefixes like the cells'; every sort's key and idx outputs are
checked against the 3-operand sort's before it is timed.  Times are host
clock around ``block_until_ready``, the median of ``--reps`` calls after
one warm call.

Since ISSUE 50 also the fold itself, at the shapes PR 35 did not time
(``FOLD_SHAPES``): each cell's liveness-led key sort as it was - the flag,
the key column(s), then what rides - beside the same sort with the flag
folded into the leading key (``where(live, key, max - 1 | max)``: one
operand and one sort key fewer, ``ops/pack.key_operands(fold=True)``) - the
join's ``x4 -> x3``, the set operations' ``x4 -> x3`` with two key columns
at 65,011,712 rows, ``unique``'s ``x3 -> x2`` at 32,505,856 and the
groupby's unstable ``x4 -> x3`` at 25,165,824; the live prefix of the
folded sort's keys is checked against the unfolded one's.

    chiprun -- python scripts/sort_operands_bench.py --out chiprun_out/sort_operands_bench.json
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp

#: (cell, rows of the concatenated state a shard, live rows a side)
SHAPES = (
    ("join_groupby_32m", 65_011_712, 32_000_000),
    ("dist_join_groupby_8m_x4", 17_825_792, 8_388_608),
    ("dist_join_groupby_8m_zipf_x4", 20_447_232, 9_700_000),
)


#: (the cell's sort, rows a shard, live rows a table, tables ranked together,
#: further key columns, uint32 payloads, the row index rides, stable)
FOLD_SHAPES = (
    ("join_groupby_32m join__count_fn", 65_011_712, 32_000_000, 2, 0, 1,
     True, True),
    ("setops_dedup_32m setop_count_fn", 65_011_712, 32_000_000, 2, 1, 0,
     True, True),
    ("setops_dedup_32m unique_count_fn", 32_505_856, 32_000_000, 1, 0, 0,
     True, True),
    ("groupby_sort_25m groupby__raw_fn", 25_165_824, 25_000_000, 1, 0, 2,
     False, False),
)


def make_inputs(seed: int, n: int, live_side: int):
    """(liveness flag, key, three payloads) of an ``n``-row concat whose
    two halves hold ``live_side`` live rows each."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    half = n // 2
    pos = jnp.arange(n, dtype=jnp.int32)
    dead = (pos % half) >= live_side
    flag = jnp.where(dead, jnp.where(pos < half, 4, 5), 0).astype(jnp.int32)
    key = jax.random.randint(ks[0], (n,), 0, max(int(0.45 * n), 1),
                             dtype=jnp.int32)
    pays = tuple(jax.random.bits(k, (n,), dtype=jnp.uint32) for k in ks[1:])
    return flag, key, pays


def sort_n(flag, key, pays, n_ops: int):
    """The stable 2-key sort with ``n_ops`` operands; returns all of
    them sorted (a payload that is not an output would be dropped)."""
    idx = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((flag, key, idx) + tuple(pays[:n_ops - 3]),
                        num_keys=2, is_stable=True)


def _timed(f, args, reps: int):
    """(outputs, median ms, best ms, seconds of the first call: compile +
    one run) of ``f(*args)``."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args))
    first_s = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times), min(times), first_s


def time_sort(args, n_ops: int, reps: int):
    return _timed(jax.jit(sort_n, static_argnums=3), (*args, n_ops), reps)


def fold_sort(flag, key, pays, n_keys2: int, n_pays: int, with_idx: bool,
              stable: bool, fold: bool):
    """A cell's liveness-led key sort, or the same sort with the flag folded
    into the leading key: padding at int32's ``max - 1`` (flag 4) / ``max``
    (flag 5), above every live key.  Further key columns are the low bits
    of the first payloads.  Returns (sorted leading key, the other sorted
    operands)."""
    if fold:
        top = jnp.int32(2**31 - 1 - 5) + flag
        lead = (jnp.where(flag == 0, key, top),)
    else:
        lead = (flag, key)
    keys2 = tuple((p & 3).astype(jnp.int32) for p in pays[:n_keys2])
    idx = (jnp.arange(key.shape[0], dtype=jnp.int32),) if with_idx else ()
    out = jax.lax.sort(lead + keys2 + idx + tuple(pays[n_keys2:][:n_pays]),
                       num_keys=len(lead) + n_keys2, is_stable=stable)
    return out[len(lead) - 1], out[len(lead):]


def time_fold(args, statics, reps: int):
    return _timed(jax.jit(fold_sort, static_argnums=(3, 4, 5, 6, 7)),
                  (*args, *statics), reps)


def fold_rows(a) -> list:
    rows = []
    for cell, n, live, tables, n_keys2, n_pays, with_idx, stable in FOLD_SHAPES:
        n, live = (max(int(v * a.scale), 8) for v in (n, live))
        # one table: its live rows lead, the padding behind them
        flag, key, pays = jax.jit(make_inputs, static_argnums=(1, 2))(
            a.seed % (2**31), n * (3 - tables), live)
        args = (flag[:n], key[:n], tuple(p[:n] for p in pays))
        n_live, ref = live * tables, None
        for fold in (False, True):
            (lead, rest), med, best, first_s = time_fold(
                args, (n_keys2, n_pays, with_idx, stable, fold), a.reps)
            operands = 2 - fold + n_keys2 + with_idx + n_pays
            row = {"cell": cell, "rows": n, "operands": operands,
                   "folded": fold, "stable": stable, "ms_median": med,
                   "ms_min": best, "first_call_s": first_s,
                   "ns_per_row_operand": med * 1e6 / n / operands}
            if ref is None:
                ref, base_ms = (lead, rest), med
            else:
                row["ms_saved_by_fold"] = base_ms - med
                row["live_prefix_equal"] = bool(
                    jnp.array_equal(lead[:n_live], ref[0][:n_live]) and all(
                        jnp.array_equal(x[:n_live], y[:n_live])
                        for x, y in zip(rest[:n_keys2 + (with_idx and stable)],
                                        ref[1])))
            print(json.dumps(row), flush=True)
            rows.append(row)
            del lead, rest
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3500000311)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink every shape (CPU rehearsal)")
    ap.add_argument("--operands", default="3,4,5,6")
    ap.add_argument("--shapes", default="all",
                    choices=("all", "operands", "fold"),
                    help="PR 35's operand ladder, ISSUE 50's fold pairs, "
                         "or both")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps({"device": device}), flush=True)
    rows = []
    for cell, n, live_side in SHAPES if a.shapes != "fold" else ():
        n, live_side = (max(int(v * a.scale), 8) for v in (n, live_side))
        args = jax.jit(make_inputs, static_argnums=(1, 2))(
            a.seed % (2**31), n, live_side)
        ref, base_ms = None, None
        for n_ops in (int(x) for x in a.operands.split(",")):
            out, med, best, first_s = time_sort(args, n_ops, a.reps)
            if ref is None:
                ref, base_ms, base_ops = out[1:3], med, n_ops
            row = {"cell": cell, "rows": n, "operands": n_ops,
                   "ms_median": med, "ms_min": best, "first_call_s": first_s,
                   "ns_per_row_operand": med * 1e6 / n / n_ops,
                   "equal_to_first": bool(all(
                       jnp.array_equal(x, y) for x, y in zip(out[1:3], ref)))}
            if n_ops != base_ops:
                row["ms_per_added_operand"] = (med - base_ms) \
                    / (n_ops - base_ops)
            print(json.dumps(row), flush=True)
            rows.append(row)
            del out
    if a.shapes != "operands":
        rows += fold_rows(a)
    if a.out:
        with open(a.out, "w", encoding="utf-8") as f:
            json.dump({"device": device, "seed": a.seed, "reps": a.reps,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
