"""Host spans of the benchmark's own, around its calls into each layer.

Kept in memory on ``time.perf_counter``; in a traced run each span is
also a ``jax.profiler.TraceAnnotation``, so that it lies on the
profiler's clock beside the device's operations and an idle gap can be
labelled by the span that covers it.
"""
import contextlib
import time


class SpanRecorder:

    def __init__(self, annotate=False):
        self.annotate = annotate
        self.spans = []          # (name, start_s, end_s) on perf_counter

    @contextlib.contextmanager
    def span(self, name):
        annotation = contextlib.nullcontext()
        if self.annotate:
            import jax
            annotation = jax.profiler.TraceAnnotation(name)
        with annotation:
            start = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, start, time.perf_counter()))

    def durations(self, name, since=None, until=None):
        """Seconds of each span called ``name`` that ended inside
        [since, until]."""
        return [e - s for n, s, e in self.spans
                if n == name and (since is None or e >= since)
                and (until is None or e <= until)]

    def names(self):
        return sorted({n for n, _, _ in self.spans})
