"""The benchmark's own spans and counters, round the calls into each layer.

Spans are kept in memory on the host's monotonic clock and, while the
profiler runs, also written into its trace (``TraceAnnotation``) under the
prefix ``bench:``, so that the reduction can lay them over the device's
timeline on the profiler's own clock."""

from __future__ import annotations

import time
from collections import defaultdict
from contextlib import contextmanager

PREFIX = "bench:"


class Recorder:
    def __init__(self):
        self.spans = defaultdict(list)     # name -> [(start_s, end_s)]
        self.counters = defaultdict(float)
        self.series = defaultdict(list)    # name -> [value]
        self.annotate = False              # profiler running

    @contextmanager
    def span(self, name: str):
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(PREFIX + name):
                t0 = time.monotonic()
                try:
                    yield
                finally:
                    self.spans[name].append((t0, time.monotonic()))
        else:
            t0 = time.monotonic()
            try:
                yield
            finally:
                self.spans[name].append((t0, time.monotonic()))

    def wrap(self, obj, method: str, name: str, before=None, after=None):
        """Replace ``obj.method`` (on the instance) by one that records a
        span round the original; ``before(*args)`` and
        ``after(state, result, *args)`` may note counts."""
        inner = getattr(obj, method)

        def wrapped(*args, **kw):
            state = before(*args) if before else None
            with self.span(name):
                result = inner(*args, **kw)
            if after:
                after(state, result, *args)
            return result

        setattr(obj, method, wrapped)

    @contextmanager
    def muted(self):
        """What runs inside leaves no span, counter or series behind."""
        kept = self.spans, self.counters, self.series
        self.spans, self.counters, self.series = (
            defaultdict(list), defaultdict(float), defaultdict(list))
        try:
            yield
        finally:
            self.spans, self.counters, self.series = kept

    def clear(self):
        self.spans.clear()
        self.counters.clear()
        self.series.clear()
