"""The frame source blocks a live read until the frame is due, and refuses
to become one array."""
import time

import numpy as np
import pytest
import tinycells  # noqa: F401

from harness import stream


def test_live_source_blocks_until_due():
    frames = np.arange(10 * 2 * 2 * 3, dtype=np.float32).reshape(10, 2, 2, 3)
    src = stream.FrameSource(frames, fps=50.0)
    t0 = src.start()
    np.testing.assert_array_equal(src[0], frames[0])
    np.testing.assert_array_equal(src[4], frames[4])
    assert time.perf_counter() - t0 >= 4 / 50.0
    src[2:8]  # a slice waits for its last frame
    assert time.perf_counter() - t0 >= 7 / 50.0
    src[-1]
    assert time.perf_counter() - t0 >= 9 / 50.0
    assert len(src) == 10


def test_live_source_counts_waits():
    waits = []

    class Span:
        def __enter__(self):
            waits.append(time.perf_counter())

        def __exit__(self, *exc):
            pass

    src = stream.FrameSource(np.zeros((3, 1, 1, 3), np.float32), fps=100.0, on_wait=Span)
    src.start()
    src[2]
    assert len(waits) == 1


def test_source_refuses_the_whole_video():
    src = stream.FrameSource(np.zeros((4, 2, 2, 3), np.float32), fps=30.0)
    src.start()
    with pytest.raises(TypeError):
        np.asarray(src)
    with pytest.raises(TypeError):
        list(src)
    replay = stream.FrameSource(np.zeros((4, 2, 2, 3), np.float32))
    with pytest.raises(TypeError):
        np.asarray(replay)


def test_live_read_before_start_raises():
    with pytest.raises(RuntimeError):
        stream.FrameSource(np.zeros((2, 1, 1, 3), np.float32), fps=30.0)[0]


def test_replay_source_does_not_wait():
    src = stream.FrameSource(np.zeros((300, 1, 1, 3), np.float32))
    src[299]  # no start(), no due times: a recording hands frames over at once
    assert src.t0 is None


def test_synthetic_video_is_seeded():
    a, la = stream.synthetic_video(20, res=8, seed=2**31 + 5)
    b, lb = stream.synthetic_video(20, res=8, seed=2**31 + 5)
    c, _ = stream.synthetic_video(20, res=8, seed=3)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(la, lb)
    assert not np.array_equal(a, c)
    assert a.shape == (20, 8, 8, 3) and a.dtype == np.float32


def test_square_wave():
    assert stream.square_wave([8, 2], 5.0, 12.0) == [(0.0, 8.0), (5.0, 2.0), (10.0, 8.0), (15.0, 2.0)]
