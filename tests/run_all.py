"""Suite driver: one pytest process per test file, segfault-resilient.

The reference runs its python suite exactly this way — test_all.py shells
out a pytest invocation per file (python/pycylon/test/test_all.py:23-29) —
and here it is load-bearing robustness, not just parity: the XLA:CPU
compiler segfaults nondeterministically in long-lived processes (~1 in
1000 compiles, observed live as faulthandler dumps inside
``backend_compile_and_load`` at random tests on full-suite runs; single
files never accumulate enough compiles to hit it).  Per-file processes
bound the blast radius and a crashed file retries once — a repeated crash
in the SAME file is a real failure and reports as one.

Usage: python tests/run_all.py [pytest args...]
Exit code 0 iff every file passed.
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

#: seconds to back off before re-running a signal-killed file: the
#: crash class this driver exists for (XLA:CPU compiler segfaults)
#: correlates with transient memory pressure, and an immediate rerun
#: inherits it more often than a briefly delayed one
RETRY_BACKOFF_S = 2.0


def run_file(path: str, extra: list[str]) -> int:
    cmd = [sys.executable, "-m", "pytest", path, "-q", *extra]
    # each file's process prints a greppable `# COMPILE_COUNT file=...
    # n=...` line at exit (tests/conftest.py): the per-file compile
    # budget audit that motivated this driver (XLA:CPU segfaults track
    # compile accumulation) becomes a number in the tee'd log
    env = dict(os.environ, CYLON_TPU_COMPILE_COUNT="1")
    for attempt in (1, 2):
        r = subprocess.run(cmd, cwd=os.path.dirname(HERE), env=env)
        if r.returncode in (0, 5):     # 5 = no tests collected
            return 0
        # negative = killed by signal (SIGSEGV -11); retry once
        if r.returncode >= 0 or attempt == 2:
            return r.returncode
        # one-line retry marker: a retried file's dots appear TWICE in
        # the tee'd log, so the tier-1 DOTS accounting needs a greppable
        # record of every retry that fired (and of the crashed first
        # pass's partial dot line) to stay auditable
        print(f"# DOTS_RETRY file={os.path.basename(path)} "
              f"signal={-r.returncode} backoff={RETRY_BACKOFF_S:g}s "
              "(first pass's partial dots above are superseded by the "
              "rerun)", flush=True)
        time.sleep(RETRY_BACKOFF_S)
    return 1


def main() -> int:
    extra = sys.argv[1:]
    files = sorted(glob.glob(os.path.join(HERE, "**", "test_*.py"),
                             recursive=True))
    failed = []
    for f in files:
        print(f"== {os.path.basename(f)}", flush=True)
        if run_file(f, extra) != 0:
            failed.append(os.path.basename(f))
    if failed:
        print(f"FAILED files: {failed}", flush=True)
        return 1
    print("ALL FILES PASSED", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
