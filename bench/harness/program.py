"""The program's own spans and compile records over a run's measured window.

The serving path records them in ``repro.serving.spans.RECORDER``, on
``time.perf_counter``'s clock, the clock of the benchmark's own spans.  The
measured window is the envelope of those spans (``run.spans``, the
untraced window's): records from the warm-up before it, and from the
traced window and the correctness check after it, fall outside.

Every function returns ``None`` where there is nothing to read: a program
that records no spans, or a ring that dropped records inside the window.
"""
from __future__ import annotations

from .stats import percentile


def window(run) -> list | None:
    """The records that overlap the measured window, oldest first."""
    try:
        from repro.serving.spans import RECORDER
    except ImportError:
        return None
    marks = [t for spans in run.spans.by_name.values() for ab in spans for t in ab]
    if not marks:
        return None
    lo, hi = min(marks) * 1e9, max(marks) * 1e9
    records = RECORDER.records()
    # the ring drops the records that ended first: one that ended inside the
    # window may be gone unless the oldest one kept ended before it
    if RECORDER.dropped and (not records or records[0].end_ns >= lo):
        return None
    return [r for r in records if r.start_ns <= hi and r.end_ns >= lo]


def durations_ms(run, name: str) -> list | None:
    recs = window(run)
    if recs is None:
        return None
    return [r.duration_ns / 1e6 for r in recs if r.name == name]


def p50_ms(run, name: str) -> float | None:
    d = durations_ms(run, name)
    return percentile(d, 50) if d else None


def self_ms(run, name: str) -> float | None:
    """Summed self time of the spans named ``name``: each one's duration less
    that of the spans opened directly inside it."""
    recs = window(run)
    if recs is None:
        return None
    own = {r.id: r.duration_ns for r in recs if r.name == name}
    if not own:
        return None
    for r in recs:
        if r.parent in own:
            own[r.parent] -= r.duration_ns
    return sum(own.values()) / 1e6


def count(run, name: str) -> int | None:
    recs = window(run)
    if recs is None:
        return None
    return sum(r.name == name for r in recs)
