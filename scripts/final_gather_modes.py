"""The two speeds of ``groupby__final_fn``'s gather, outside the program
(ROADMAP S14a, PR 44).  Not part of the suite.

Phase 2 of the distributed groupby gathers its prefix lanes at the segment
starts with XLA's plain row gather over the WHOLE receive capacity:
``mat u32[N + 1, 3]`` at ``starts s32[N]``, ``N`` = 22,020,096 in cell
``groupby_sort_25m_x4`` - ~15.09M sorted start positions and ~6.93M padding
slots that all hold ``n_live`` (``ops/groupby.grouped_starts``' fill), so
all read the SAME row.  In the cell the instruction ``fusion
u32[22020096,3]`` runs ~77 ms longer on some chips at some seeds.  This
times that gather alone on one chip, over ``n_live`` (the four chips' own
row counts at a seed with no slow chip and at one with two; ``n_live + k``
for small and large ``k``), over the starts' random pattern, and over
their density (how many slots are padding).  Start flags are Bernoulli
draws on the host; times are host clock around ``block_until_ready``, two
calls after one warm call.

    chiprun -- python3 scripts/final_gather_modes.py   # chiprun_out/gather_modes.jsonl
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

N = int(sys.argv[1]) if len(sys.argv) > 1 else 22020096
SCALE = N / 22020096          # a rehearsal on the CPU shrinks every n_live
DENSITY = 0.6913              # ~15.09M groups in ~21.83M rows
NO_SLOW_CHIP = [21829746, 21823819, 21824417, 21834208]      # seed 2
TWO_SLOW_CHIPS = [21820433, 21832568, 21821489, 21837854]    # seed 4400000017


def starts_of(n_live: int, seed: int, p: float):
    flags = np.random.default_rng(seed).random(n_live) < p
    flags[0] = True
    pos = np.flatnonzero(flags).astype(np.int32)
    out = np.full(N, n_live, np.int32)
    out[:len(pos)] = pos
    return out, len(pos)


def timed(fn, *args, reps: int = 2) -> list:
    fn(*args).block_until_ready()
    ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        ms.append(round(1e3 * (time.perf_counter() - t0), 2))
    return ms


def main() -> int:
    t0 = time.time()
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    print("device", jax.devices()[0].device_kind, "N", N, flush=True)
    gather = jax.jit(lambda mat, starts: mat[starts])
    stacked = jax.jit(
        lambda a, b, c, starts: jnp.stack([a, b, c], axis=1)[starts])
    rng = np.random.default_rng(0)
    mat = jax.device_put(rng.integers(0, 2**32, (N + 1, 3), dtype=np.uint32))
    with open(os.path.join(out_dir, "gather_modes.jsonl"), "w") as log:
        def say(rec: dict) -> None:
            print(json.dumps(rec), flush=True)
            log.write(json.dumps(rec) + "\n")
            log.flush()

        def run(tag: str, n_live: int, seed: int = 0, p: float = DENSITY):
            n_live = int(n_live * SCALE)
            starts, groups = starts_of(n_live, seed, p)
            say({"tag": tag, "n_live": n_live, "seed": seed, "p": p,
                 "groups": groups,
                 "ms": timed(gather, mat, jax.device_put(starts))})

        for n_live in NO_SLOW_CHIP:
            run("seed2", n_live)
        for n_live in TWO_SLOW_CHIPS:
            run("seed4400000017", n_live)
        for p in (0.55, 0.62, 0.66, 0.68, 0.70, 0.72, 0.78, 0.9):
            run("density", NO_SLOW_CHIP[0], p=p)
        for seed in range(1, 7):
            run("pattern", NO_SLOW_CHIP[0], seed=seed)
        for k in list(range(1, 9)) + [1000 * k for k in range(1, 5)]:
            run("n_live+k", NO_SLOW_CHIP[0] + k)
        # the program stacks three columns first: another buffer, another base
        cols = [jax.device_put(rng.integers(0, 2**32, N + 1, dtype=np.uint32))
                for _ in range(3)]
        starts, _ = starts_of(int(NO_SLOW_CHIP[0] * SCALE), 0, DENSITY)
        say({"tag": "stack+gather",
             "ms": timed(stacked, *cols, jax.device_put(starts))})
    print("done in %.0f s" % (time.time() - t0), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
