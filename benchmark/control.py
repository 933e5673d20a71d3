"""The control of a cell's comparison: it has to come out NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

The control is the plain reference, put in the program's place, with the
one guarantee broken that a later PR would be tempted to break: sums
accumulated in float32 where the configuration states exact int64 (each
query module's ``control``).  It is compared as a run's result is, against
the reference proper, at the cell's own size, from the same seeds' tables.
numpy only: it needs no chip and holds none.  The benchmark's own runs do
not run it.  Exit 0 when every seed's control was caught.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from lib import compare, files, generate  # noqa: E402


def control_numbers(bench_dir: str, workload: str, seed: int) -> list:
    cell = files.load_json(bench_dir, "workloads", workload)
    cfg = files.load_json(bench_dir, "configs", cell["config"])
    qm = files.load_module(bench_dir, "queries", cell["query"])
    host = generate.host_tables(bench_dir, cfg, seed)
    q = cfg["query"]
    return compare.columns(qm.control(host, q, seed),
                           qm.reference(host, q, seed))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    caught = 0
    for seed in args.seeds:
        t0 = time.perf_counter()
        numbers = control_numbers(BENCH_DIR, args.workload, seed)
        correct = compare.verdict(numbers)
        caught += not correct
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_correct": correct,
                          "seconds": round(time.perf_counter() - t0, 2),
                          "compared": compare.as_dict(numbers)}), flush=True)
    print(f"control caught on {caught} of {len(args.seeds)} seeds")
    return 0 if caught == len(args.seeds) else 1


if __name__ == "__main__":
    sys.exit(main())
