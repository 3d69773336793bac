"""A whole run at a CPU size, past the harness's look for a chip, with the
timed path broken underneath: ``correct`` has to come out false for each
fault a serving cell can have, and true with nothing broken."""
import time

import numpy as np
import pytest
from tinycells import config, traffic

from harness import serve


@pytest.fixture(autouse=True)
def _deploy_once(monkeypatch):
    """One deployment per configuration and seed for the whole file: the
    faults are planted in the program's classes, which every deployment
    calls, and compiling the CPU-size models once keeps the file short."""
    from repro.serving import BatchStats

    def cached(cfg, ref, seed, traffic, sample):
        key = (cfg["name"], seed, tuple(traffic["paths"]))
        if key not in _DEPLOYED:
            _DEPLOYED[key] = _deploy(cfg, ref, seed, traffic, sample)
        dep = _DEPLOYED[key]
        dep.edge.stats = BatchStats()
        return dep

    monkeypatch.setattr(serve, "deploy", cached)


_DEPLOYED: dict = {}
_deploy = serve.deploy


def _run(cell_config="resnet50", mix="live_max_accuracy", seconds=1.0, controls=False, **over):
    cfg, ref = config(cell_config)
    tr = traffic(mix, **over)
    return serve.run_cell(cfg, ref, tr, seed=2**31 + 11, seconds=seconds, trace=False,
                          t_process=time.perf_counter(), log=lambda *a: None, controls=controls)


def test_sound_run_is_correct():
    run, numbers, ok = _run()
    assert ok, numbers
    assert run.npu_frames > 0 and run.edge_frames > 0
    assert set(numbers) == {"npu_rel_l2", "edge_rel_l2", "answered_once_misses"}
    assert run.compiles == 0
    assert run.answered == run.scheduled > 0


def test_npu_answer_altered_where_produced(monkeypatch):
    from repro.serving.engine import ModelEndpoint

    call = ModelEndpoint.__call__
    monkeypatch.setattr(ModelEndpoint, "__call__", lambda self, x: call(self, x)[:, ::-1].copy())
    _, numbers, ok = _run()
    assert not ok
    assert numbers["npu_rel_l2"][0] > numbers["npu_rel_l2"][1]


def test_edge_answer_altered_where_produced(monkeypatch):
    from repro.serving.engine import BatchedEndpoint

    call = BatchedEndpoint.__call__
    monkeypatch.setattr(BatchedEndpoint, "__call__", lambda self, x: 1.05 * call(self, x))
    _, numbers, ok = _run()
    assert not ok
    assert numbers["edge_rel_l2"][0] > numbers["edge_rel_l2"][1]


def test_half_the_batch_left_out(monkeypatch):
    """The edge forward runs on the first half of each batch; the rest get
    the mean of the answers that were computed."""
    from repro.serving.engine import BatchedEndpoint

    call = BatchedEndpoint.__call__

    def half(self, images):
        keep = len(images) // 2
        out = call(self, images[:keep]) if keep else np.zeros((0, self.n_out), np.float32)
        mean = out.mean(0, keepdims=True) if keep else np.zeros((1, self.n_out), np.float32)
        return np.concatenate([out, np.repeat(mean, len(images) - keep, 0)])

    monkeypatch.setattr(BatchedEndpoint, "n_out", 10, raising=False)
    monkeypatch.setattr(BatchedEndpoint, "__call__", half)
    _, numbers, ok = _run()
    assert not ok
    assert numbers["edge_rel_l2"][0] > numbers["edge_rel_l2"][1]


def test_replay_cell_sound_and_npu_fault(monkeypatch):
    """The replay mix through the same run: correct as it stands, and not
    with the NPU's answers altered."""
    _, numbers, ok = _run("squeezenet", "replay_max_accuracy", seconds=0.5, clip_s=1.0, clips=1)
    assert ok, numbers
    from repro.serving.engine import ModelEndpoint

    call = ModelEndpoint.__call__
    monkeypatch.setattr(ModelEndpoint, "__call__", lambda self, x: 0.5 * call(self, x))
    _, numbers, ok = _run("squeezenet", "replay_max_accuracy", seconds=0.5, clip_s=1.0, clips=1)
    assert not ok
    assert numbers["npu_rel_l2"][0] > numbers["npu_rel_l2"][1]


def test_frames_left_unanswered(monkeypatch):
    """A flush that answers only half of what was queued: the server cannot
    finish its round, and the run is not correct."""
    from repro.serving.engine import EdgeBatchServer

    flush = EdgeBatchServer.flush

    def half(self):
        self.queue = self.queue[: len(self.queue) // 2]
        return flush(self)

    monkeypatch.setattr(EdgeBatchServer, "flush", half)
    _, numbers, ok = _run()
    assert not ok
    assert numbers["answered_once_misses"][0] > 0


def test_controls_fail_where_the_program_passes():
    run, numbers, ok = _run(controls=True)
    assert ok, numbers
    program, control = run.readings
    assert set(program) == set(control) == {"npu_rel_l2", "edge_rel_l2"}
    for name, value in program.items():
        if name in numbers:
            assert value == numbers[name][0]
    limits = config("resnet50")[0]["limits"]
    assert any(control[k] > lim for k, lim in limits.items()), (control, limits)
    for name in program:
        assert control[name] > 2 * program[name], (name, control[name], program[name])
