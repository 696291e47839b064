"""The benchmark's own spans around its calls into the program.

Kept in memory; when the profiler is tracing, each span is also a
``jax.profiler.TraceAnnotation`` so that it lands in the trace on the
device events' clock and an idle gap can be given to what the host did.
"""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self):
        self.records = []      # (name, start_s, end_s) on time.perf_counter
        self.annotate = False  # set while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def total(self, name: str, since: float = 0.0, until: float = float("inf")) -> float:
        return sum(e - s for n, s, e in self.records if n == name and s >= since and e <= until)
