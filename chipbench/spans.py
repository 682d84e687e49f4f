"""chipbench's own host spans, around the calls into each layer.  A span is
kept in memory as (name, start, end) on time.perf_counter and, while the
profiler runs, also written into its trace (jax.profiler.TraceAnnotation),
so that idle gaps of the device can be named by what the host was doing."""

import contextlib
import time

import jax

NAMES = ("dispatch", "block", "window_op", "input")


class Spans:
    def __init__(self):
        self.records = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        if self.annotate:
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.records.append((name, t0, time.perf_counter()))

    def durations(self, name):
        return [e - s for n, s, e in self.records if n == name]

    def clear(self):
        self.records = []
