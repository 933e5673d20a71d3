"""The exchange's target sort by path, at the four-chip cells' source
shards: what getting a lane matrix into destination order costs on one
chip (ROADMAP S10, PR 46).  Not part of the suite.

``parallel/shuffle._prep_fn`` as the exchange runs it, three ways over the
same targets and lanes: ``perm`` - the 2-operand stable ``(target,
position)`` sort and XLA's row gather at its permutation (the only path
until PR 46, and still a wide table's or a float64 side array's);
``ride`` - the lanes as payload operands of ONE sort whose key is
``(target, position)`` in one word, ``1 + L`` operands, not stable;
``ride_stable`` - the same with the target as the key and
``is_stable=True``, what a world too wide for the one-word key gets
(XLA:TPU gives it a tie-break iota of its own: ``2 + L`` operands).  The
three outputs are compared before anything is timed.  Times are host clock
around ``block_until_ready``, the median of ``--reps`` calls after one
warm call; the first call's seconds (compile included) are printed too.

    chiprun -- python scripts/exchange_prep_bench.py --out chiprun_out/exchange_prep_bench.json
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cylon_tpu.ctx.context import ROW_AXIS  # noqa: E402
from cylon_tpu.parallel import shuffle  # noqa: E402

W = 4       # destinations: the four-chip cells' world
#: (what exchanges it, rows a source shard, u32 lanes, live rows)
SHAPES = (
    ("dist_join_groupby_8m_x4: a table's hash shuffle", 8_388_608, 2,
     8_388_608),
    ("groupby_sort_25m_x4: the partials' hash shuffle", 22_020_096, 4,
     21_830_000),
    ("groupby_sort_25m_x4: the sort's range exchange", 15_204_352, 4,
     15_092_000),
)
#: path -> (the builder's ``w``, ``ride``); ``w`` only decides whether the
#: one-word key fits, and 2^32 destinations never do
PATHS = {"perm": (W, False), "ride": (W, True),
         "ride_stable": (1 << 32, True)}


def make_inputs(seed: int, rows: int, lanes: int, live: int):
    kt, km = jax.random.split(jax.random.PRNGKey(seed % (1 << 31)))
    tgt = jax.random.randint(kt, (rows,), 0, W, dtype=jnp.int32)
    tgt = jnp.where(jnp.arange(rows) < live, tgt, W)
    return tgt, jax.random.bits(km, (rows, lanes), dtype=jnp.uint32)


def time_call(fn, args, reps: int):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return out, first, statistics.median(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=4600000007)
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--shrink", type=int, default=1,
                    help="divide every shape's rows (a CPU rehearsal)")
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    dev = jax.devices()[0]
    mesh = Mesh(np.array([dev]), (ROW_AXIS,))
    report = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "seed": a.seed, "reps": a.reps, "shapes": []}
    for what, rows, lanes, live in SHAPES:
        rows, live = rows // a.shrink, live // a.shrink
        tgt, mat = make_inputs(a.seed, rows, lanes, live)
        line = {"what": what, "rows": rows, "lanes": lanes, "live": live}
        outs = {}
        for path, (w, ride) in PATHS.items():
            (outs[path],), first, med = time_call(
                shuffle._prep_fn(mesh, w, ride), (tgt, (mat,)), a.reps)
            line[path] = {"first_call_s": first, "ms": med * 1e3,
                          "ns_a_row": med * 1e9 / rows}
        line["equal"] = bool(jnp.array_equal(outs["perm"], outs["ride"])
                             & jnp.array_equal(outs["perm"],
                                               outs["ride_stable"]))
        print(json.dumps(line), flush=True)
        report["shapes"].append(line)
        del outs
    if a.out:
        with open(a.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
