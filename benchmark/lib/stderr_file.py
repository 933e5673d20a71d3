"""Keep a run's standard error in a file in the checkout as well.

Exit 139 is a known class here (XLA:TPU's compiler segfaults in-process,
PERF.md section 6) and PR 24's death left no stack anywhere.  File
descriptor 2 is pointed at a ``tee`` child that copies it to the real
standard error and to the file, so whatever writes there - Python,
``faulthandler``, XLA's own C++ failure handler - lands in both, also when
the process dies: ``tee`` then reads end-of-file and ends by itself."""

from __future__ import annotations

import faulthandler
import os
import shutil
import subprocess
import sys


class StderrFile:
    def __init__(self, path: str):
        self.path = path
        self._tee = None
        self._saved = None
        self._py_stderr = None

    def __enter__(self):
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        tee = shutil.which("tee")
        if tee is not None:
            sys.stderr.flush()
            self._saved = os.dup(2)
            with open(self.path, "wb"):
                pass
            self._tee = subprocess.Popen(
                [tee, "-a", self.path], stdin=subprocess.PIPE,
                stdout=self._saved, stderr=subprocess.DEVNULL,
                close_fds=True)
            os.dup2(self._tee.stdin.fileno(), 2)
            # Python's own writes go through descriptor 2 as well, also
            # where a caller (a test's capture) had replaced sys.stderr
            self._py_stderr = sys.stderr
            sys.stderr = open(2, "w", buffering=1, closefd=False)
        faulthandler.enable(file=sys.stderr, all_threads=True)
        return self

    def __exit__(self, *exc):
        sys.stderr.flush()
        if self._tee is not None:
            faulthandler.disable()
            sys.stderr = self._py_stderr
            os.dup2(self._saved, 2)
            self._tee.stdin.close()
            self._tee.wait(timeout=30)
            os.close(self._saved)
            faulthandler.enable(file=sys.stderr, all_threads=True)
        return False
