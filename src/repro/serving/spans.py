"""Spans and a compile counter for the serving loop.

A span is one named interval of work at a layer boundary of the serving
path (a planner round, a host-to-device copy, a forward's dispatch, the
wait for its result).  :class:`SpanRecorder` keeps, per span:

* its name;
* start and end on ``time.perf_counter_ns``;
* its parent: the innermost span open on the same thread when it began;
* a request id: the frame it serves, or the head frame of the round.

While a profiler records, each span also enters
``jax.profiler.TraceAnnotation("fastva." + name)``, so a profiler capture
shows the program's spans on the host timeline that the device trace is
aligned to.

Records go into a bounded ring: the oldest are dropped, and counted, once
it is full.  ``enabled = False`` makes every span a no-op.

Compiles are recorded too, as zero-length ``compile`` records whose parent
is the span open when XLA compiled: a compile inside the serving loop names
the step that recompiled.  One ``jax.monitoring`` listener, registered once
per process, feeds every recorder; a compile is what
:class:`repro.core.compile_cache.CompileCounter` counts as one.

``RECORDER`` is the process-wide recorder the serving objects use;
``RECORDER.summary()`` is what an operator reads.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
import weakref

import jax
import jax.monitoring
import numpy as np

from ..core.compile_cache import CompileCounter

PREFIX = "fastva."
_Annotation = jax.profiler.TraceAnnotation
_profiling = _Annotation.is_enabled
COMPILE = "compile"
# one 40 s replay window of every span fits with room to spare
CAPACITY = 1 << 18


@dataclasses.dataclass(frozen=True)
class SpanRecord:
    id: int
    parent: int | None  # the id of the span open around it, if any
    name: str
    start_ns: int
    end_ns: int
    request_id: int | None

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


class _Thread(threading.local):
    def __init__(self):
        self.stack: list[Span] = []
        self.request: int | None = None


class Span:
    """One span; made by :meth:`SpanRecorder.span` and used with ``with``."""

    __slots__ = ("_rec", "_name", "_rid", "_ann", "_stack", "_id", "_parent", "_start")

    def __init__(self, rec: "SpanRecorder", name: str, request_id: int | None):
        self._rec, self._name, self._rid = rec, name, request_id

    def __enter__(self) -> "Span":
        rec = self._rec
        # An annotation made while no profiler records is dropped by the
        # profiler itself; skip making it then.
        if _profiling():
            self._ann = ann = _Annotation(PREFIX + self._name)
            ann.__enter__()
        else:
            self._ann = None
        thread = rec._thread
        self._stack = stack = thread.stack
        if stack:
            outer = stack[-1]
            self._parent = outer._id
            if self._rid is None:
                self._rid = outer._rid if thread.request is None else thread.request
        else:
            self._parent = None
            if self._rid is None:
                self._rid = thread.request
        self._id = next(rec._ids)
        stack.append(self)
        self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        rec = self._rec
        self._stack.pop()
        rec._ring.append((self._id, self._parent, self._name, self._start, end, self._rid))
        rec._pushed += 1
        if self._ann is not None:
            self._ann.__exit__(None, None, None)


class _Off:
    """What :meth:`SpanRecorder.span` returns while the recorder is off."""

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass


_OFF = _Off()


class SpanRecorder:
    """Spans and compiles of this process, newest last, in a bounded ring."""

    def __init__(self, capacity: int = CAPACITY):
        self.enabled = True
        self.capacity = capacity
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._pushed = 0
        self._ids = itertools.count()
        self._thread = _Thread()
        self._compiles = CompileCounter()
        _RECORDERS.add(self)

    def span(self, name: str, request_id: int | None = None) -> "Span | _Off":
        """A span named ``name``.  Without ``request_id`` it takes the
        request that :meth:`set_request` set on this thread, or else that of
        the span it opens inside."""
        return Span(self, name, request_id) if self.enabled else _OFF

    def set_request(self, request_id: int | None) -> None:
        """The request of the spans this thread opens next without one of
        their own; ``None`` returns them to their parent's."""
        self._thread.request = request_id

    @property
    def dropped(self) -> int:
        """Records pushed out of the ring since the last :meth:`clear`."""
        return max(0, self._pushed - self.capacity)

    def clear(self) -> None:
        self._ring.clear()
        self._pushed = 0

    def records(self) -> list[SpanRecord]:
        """The records in the ring, oldest (by end) first."""
        return [SpanRecord(*r) for r in tuple(self._ring)]

    def summary(self) -> dict:
        """Per span name its ``count``, ``total_ms``, ``p50_ms`` and
        ``p95_ms``; compiles by the name of their parent span (``None`` for
        a compile outside every span); and ``dropped``."""
        recs = self.records()
        names = {r.id: r.name for r in recs}
        ms: dict[str, list[float]] = collections.defaultdict(list)
        compiles: collections.Counter = collections.Counter()
        for r in recs:
            if r.name == COMPILE:
                compiles[names.get(r.parent)] += 1
            else:
                ms[r.name].append(r.duration_ns / 1e6)
        spans = {}
        for name, d in ms.items():
            p50, p95 = np.percentile(d, [50, 95])
            spans[name] = {"count": len(d), "total_ms": float(sum(d)),
                           "p50_ms": float(p50), "p95_ms": float(p95)}
        return {"spans": spans, "compiles": dict(compiles), "dropped": self.dropped}

    def _on_event(self, event: str) -> None:
        if not self._compiles.see(event) or not self.enabled:
            return
        stack = self._thread.stack
        outer = stack[-1] if stack else None
        now = time.perf_counter_ns()
        self._ring.append((next(self._ids), outer._id if outer is not None else None, COMPILE,
                           now, now, outer._rid if outer is not None else None))
        self._pushed += 1


_RECORDERS: "weakref.WeakSet[SpanRecorder]" = weakref.WeakSet()


def _on_event(event: str, **kwargs) -> None:
    for rec in tuple(_RECORDERS):
        rec._on_event(event)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    _on_event(event)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_duration)

RECORDER = SpanRecorder()
