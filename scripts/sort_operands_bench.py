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


def time_sort(args, n_ops: int, reps: int):
    f = jax.jit(sort_n, static_argnums=3)
    t0 = time.perf_counter()
    out = jax.block_until_ready(f(*args, n_ops))
    first_s = time.perf_counter() - t0          # compile + one call
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args, n_ops))
        times.append((time.perf_counter() - t0) * 1e3)
    return out, statistics.median(times), min(times), first_s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=3500000311)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink every shape (CPU rehearsal)")
    ap.add_argument("--operands", default="3,4,5,6")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps({"device": device}), flush=True)
    rows = []
    for cell, n, live_side in SHAPES:
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
    if a.out:
        with open(a.out, "w", encoding="utf-8") as f:
            json.dump({"device": device, "seed": a.seed, "reps": a.reps,
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
