"""The benchmark's own spans, around the calls into each layer.

Every span is timed on the host clock (``time.perf_counter``); while the
profiler runs it is also written into the profiler's trace as
``bench.<name>`` (``jax.profiler.TraceAnnotation``), which puts it on the
device events' clock, so that an idle gap can be given to what the host
was doing in it."""

from __future__ import annotations

import contextlib
import time

PREFIX = "bench."


class Spans:
    def __init__(self):
        self.records = []          # (name, start_s, end_s), perf_counter
        self.annotate = False      # True while the profiler is on

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str) -> list:
        return [t1 - t0 for n, t0, t1 in self.records if n == name]
