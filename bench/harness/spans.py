"""Host spans and answer times, recorded by the benchmark around its calls
into the program's layers.

A span is ``(start, end)`` on ``time.perf_counter``.  With ``annotate`` the
span is also written into the profiler's trace (``bench.<name>``), so that
the trace reduction can say what the host was doing in each idle gap of the
device.
"""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "bench."


class Spans:
    def __init__(self, *, annotate: bool = False):
        self.annotate = annotate
        self.by_name: dict[str, list[tuple[float, float]]] = defaultdict(list)

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.by_name[name].append((t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def durations(self, name: str) -> list[float]:
        return [b - a for a, b in self.by_name.get(name, ())]

    def total(self, name: str) -> float:
        return sum(self.durations(name))


class Reservoir:
    """A uniform sample of at most ``size`` items from a stream, drawn with
    a seeded generator: the same seed and the same stream keep the same
    items.  Holding an item is a reference, not a copy."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list = []
        self.seen = 0
        self._rng = np.random.default_rng(seed)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self._rng.integers(self.seen))
        if j < self.size:
            self.items[j] = item


class TimedResults(list):
    """``VideoServer.results`` that stamps each ``FrameResult`` with the
    time it was appended: the frame's answer time."""

    def __init__(self):
        super().__init__()
        self.answered_at: list[float] = []

    def append(self, item) -> None:
        self.answered_at.append(time.perf_counter())
        super().append(item)
