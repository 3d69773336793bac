"""Traffic: seeded synthetic video, the frame source handed to the server,
and the uplink's bandwidth trace, all built from a traffic file's data."""
from __future__ import annotations

import time

import numpy as np


def synthetic_video(n_frames: int, *, res: int, seed: int, n_classes: int = 10,
                    drift: float = 0.05, proto_seed: int = 1234) -> tuple[np.ndarray, np.ndarray]:
    """Labelled frames: class prototypes plus noise, with slow scene drift.

    The same scheme as the program's ``serving.make_synthetic_video``
    (prototypes fixed by ``proto_seed``, the trajectory by ``seed``), drawn
    in one vectorised pass in float32 so that a 224x224 stream costs well
    under a second of set-up."""
    protos = np.random.default_rng(proto_seed).standard_normal(
        (n_classes, res, res, 3), dtype=np.float32)
    rng = np.random.default_rng(seed)
    switch = rng.uniform(size=n_frames) < drift
    fresh = rng.integers(n_classes, size=n_frames + 1)
    labels = np.empty(n_frames, np.int32)
    label = int(fresh[-1])
    for i in range(n_frames):
        if switch[i]:
            label = int(fresh[i])
        labels[i] = label
    frames = rng.standard_normal((n_frames, res, res, 3), dtype=np.float32)
    frames *= np.float32(0.9)
    frames += protos[labels]
    return frames, labels


class FrameSource:
    """A stream's frames as ``VideoServer.run`` reads them: ``len`` and
    indexing.

    With ``fps`` set the source is live: ``source[i]`` blocks until frame
    ``i`` is due (``t0 + i / fps``), as a camera would deliver it, and a
    slice blocks until its last frame is due.  Without it the source is a
    recording that hands frames over at once.  Turning the whole video into
    one array, or iterating over it, raises: a server that read ahead would
    otherwise be measured on frames it could not yet have.
    """

    def __init__(self, frames: np.ndarray, *, fps: float | None = None, on_wait=None):
        self._frames = frames
        self.fps = fps
        self.t0: float | None = None
        self._on_wait = on_wait  # context-manager factory wrapped round each wait

    def start(self) -> float:
        self.t0 = time.perf_counter()
        return self.t0

    def due(self, i: int) -> float:
        return self.t0 + i / self.fps

    def __len__(self) -> int:
        return len(self._frames)

    def __getitem__(self, key):
        if self.fps is not None:
            if isinstance(key, slice):
                idx = range(len(self._frames))[key]
                if len(idx):
                    self._wait(max(idx))
            else:
                self._wait(range(len(self._frames))[key])
        return self._frames[key]

    def _wait(self, i: int) -> None:
        if self.t0 is None:
            raise RuntimeError("a live source is read before start()")
        ahead = self.due(i) - time.perf_counter()
        if ahead <= 0:
            return
        if self._on_wait is None:
            time.sleep(ahead)
        else:
            with self._on_wait():
                time.sleep(ahead)

    def __array__(self, *args, **kwargs):
        raise TypeError("the frame source hands out frames one at a time; "
                        "the whole video is not an array")

    def __iter__(self):
        raise TypeError("the frame source is indexed, not iterated")


def square_wave(mbps: list[float], period_s: float, horizon_s: float) -> list[tuple[float, float]]:
    """``(t_start, mbps)`` points cycling through ``mbps`` every ``period_s``
    seconds up to ``horizon_s``."""
    n = max(1, int(np.ceil(horizon_s / period_s)) + 1)
    return [(k * period_s, float(mbps[k % len(mbps)])) for k in range(n)]
