"""Host spans recorded from the benchmark's own files, around the calls
into each layer. With tracing on, each span is also written into the
profiler's trace (`jax.profiler.TraceAnnotation`), so the reduction
can attribute a device idle gap to what the host was doing on the
trace's own clock."""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Tuple

PREFIX = "bench:"   # how the reduction finds these spans in a trace


class SpanLog:
    """name -> [(start, end)] on time.perf_counter. Kept in memory,
    read by the metric readers when the run ends."""

    def __init__(self, annotate: bool = False):
        self.by_name: Dict[str, List[Tuple[float, float]]] = {}
        self._annotate = annotate

    @contextmanager
    def span(self, name: str):
        ann = None
        if self._annotate:
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
            self.by_name.setdefault(name, []).append((t0, t1))

    def wrap(self, name: str, fn):
        """fn with every call inside a span: for the program's own
        entry points, wrapped from here and not edited there."""
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> float:
        """Seconds of `name` inside [t0, t1], spans clipped to it."""
        return sum(max(0.0, min(b, t1) - max(a, t0))
                   for a, b in self.by_name.get(name, ()))

    def count(self, name: str) -> int:
        return len(self.by_name.get(name, ()))
