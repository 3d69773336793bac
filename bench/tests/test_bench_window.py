"""Percentiles and rates over a synthetic live window: a stall in the
server must move ``frame_p95_ms`` and ``frames_per_s``, and a frame never
answered must land in the tail."""
import math
import time
from types import SimpleNamespace

import numpy as np
import pytest
import tinycells  # noqa: F401

from harness import serve, spec, stats, stream
from harness.spans import Spans, TimedResults

FPS = 200.0
N = 60


def _window(stall_at=None, stall_s=0.0, drop=()):
    """Serve N frames of a live source as a server would, answering each
    frame as soon as it is read; optionally stall once, or drop frames."""
    src = stream.FrameSource(np.zeros((N, 1, 1, 3), np.float32), fps=FPS)
    server = SimpleNamespace(results=TimedResults())
    t0 = src.start()
    for i in range(N):
        src[i]
        if i == stall_at:
            time.sleep(stall_s)
        if i not in drop:
            server.results.append(SimpleNamespace(frame=i))
    t1 = time.perf_counter()
    answered, scheduled, lat, misses = serve.tally([(server, list(range(N)), src)], live=True)
    run = SimpleNamespace(latencies_s=lat, answered=answered, window_s=t1 - t0, spans=Spans())
    return run, misses


def _read(name, run):
    return spec.reader(name).read(run)


def test_percentile_matches_numpy():
    xs = list(np.random.default_rng(0).exponential(size=101))
    for q in (0, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)))
    assert stats.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert stats.percentile([1.0, 2.0, math.inf], 100) == math.inf
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate():
    assert stats.rate(300, 10.0) == 30.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)


def test_stall_moves_the_tail_and_the_rate():
    calm, misses = _window()
    assert misses == 0
    # a stall of 0.1 s six frames from the end: the frames behind it (a
    # tenth of the window's) wait for it, and the window ends later
    stalled, _ = _window(stall_at=N - 6, stall_s=0.1)
    assert _read("frame_p95_ms", stalled) > _read("frame_p95_ms", calm) + 50.0
    # the same frames over a longer window
    assert _read("frames_per_s", stalled) < 0.8 * _read("frames_per_s", calm)
    # the median frame comes before the stall
    assert _read("frame_p50_ms", stalled) < 20.0


def test_unanswered_frame_counts_at_the_top():
    run, misses = _window(drop=(3, 4, 5, 6))
    assert misses == 4
    assert sorted(run.latencies_s)[-4:] == [math.inf] * 4
    assert _read("frame_p95_ms", run) == math.inf
    assert math.isfinite(_read("frame_p50_ms", run))
