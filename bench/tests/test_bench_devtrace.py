"""The trace reduction: device busy time, idle gaps by host span, kernel
time; on hand-made events and on a small trace recorded on a TPU v5e."""
from pathlib import Path

import pytest
import tinycells  # noqa: F401

from harness import devtrace
from harness.devtrace import Event

DEV = "/device:TPU:0"
OPS = devtrace.OPS_LINE
MS = 1e6  # ns


def _ev(name, start_ms, dur_ms, plane=DEV, **stats):
    return Event(plane, OPS if plane == DEV else "python", name, start_ms * MS, dur_ms * MS,
                 tuple(stats.items()))


def _events():
    host = "/host:CPU"
    return [
        _ev("bench.window", 10, 100, plane=host),
        _ev("bench.npu_call", 20, 30, plane=host),
        _ev("bench.frame_wait", 60, 40, plane=host),
        _ev("fusion.1", 5, 10),  # half outside the window
        _ev("%int8_matmul.7 = f32[64,128] custom-call(s8[64,64] %p.1)", 25, 10),
        _ev("%fusion.2 = f32[64,8] fusion(f32[64,128] %int8_matmul.7)", 28, 5),  # nested: once in busy
        _ev("%int8_matmul.8 = f32[64,128] custom-call(s8[64,64] %p.2)", 45, 5),
        _ev("copy.3", 200, 10),  # after the window
    ]


def test_busy_and_window():
    r = devtrace.reduce(_events())
    assert r.window_s == pytest.approx(0.100)
    # [10,15) + [25,35) + [45,50) inside the window
    assert r.busy_s == pytest.approx(0.020)
    assert r.devices == 1


def test_idle_gaps_by_host_span():
    r = devtrace.reduce(_events())
    # idle: [15,25) [35,45) [50,110); npu_call covers [20,50), frame_wait [60,100)
    assert r.gap_s["npu_call"] == pytest.approx(0.015)
    assert r.gap_s["frame_wait"] == pytest.approx(0.040)
    assert r.gap_s["host_other"] == pytest.approx(0.025)
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s)
    gaps = r.breakdown()["idle_gaps"]
    assert gaps[0][0] == "frame_wait"


def test_kernel_time_by_name_or_stat():
    r = devtrace.reduce(_events())
    # by instruction name only: the fusion that reads the kernel's output is not it
    assert r.seconds_matching(KERNEL) == pytest.approx(0.015)
    assert r.count_matching(KERNEL) == 2
    assert r.seconds_matching("no_such_kernel") == 0.0
    ops = dict(r.breakdown()["device_ops"])
    # self time: the fusion nested in the first kernel call is taken out of it
    assert ops["int8_matmul"] == pytest.approx(0.010)
    assert ops["fusion"] == pytest.approx(0.005 + 0.005)
    assert "copy" not in ops


def test_window_is_required():
    with pytest.raises(ValueError):
        devtrace.reduce([e for e in _events() if e.name != "bench.window"])


def test_no_device_work_is_an_error():
    with pytest.raises(ValueError):
        devtrace.reduce([e for e in _events() if e.plane != DEV])


def test_clock_offset_from_run_ids():
    host = "/host:CPU"
    events = [
        Event(DEV, devtrace.MODULES_LINE, "jit_fwd(1)", 10 * MS, 2 * MS, (("run_id", 7),)),
        Event(host, "main", devtrace.ENQUEUE, 11 * MS, 1 * MS, (("run_id", 7),)),
        Event(host, "tasks", devtrace.COMPLETE, 15 * MS, 0.1 * MS, (("run_id", 7),)),
    ]
    # the program ran after its enqueue ended (12) and before completion (15):
    # offsets between 2 and 3 ms; the midpoint
    assert devtrace.clock_offset_ns(events) == pytest.approx(2.5 * MS)
    assert devtrace.clock_offset_ns(events[1:]) == 0.0


RECORDED = Path(__file__).parent / "data" / "npu_edge_v5e.xplane.pb"


def test_recorded_tpu_trace():
    """Three int8 and three bf16 forwards of the SMOKE ResNet at 32x32 on one
    TPU v5e, between host spans (see data/README.md)."""
    events = devtrace.load(str(RECORDED))
    r = devtrace.reduce(events)
    assert r.devices == 1
    # the device's clock runs about 1.7 ms behind the host's in this trace
    assert 1e-3 < r.clock_offset_s < 3e-3
    assert 0 < r.busy_s < r.window_s
    assert set(r.gap_s) >= {"npu_call", "edge_flush", "host_other"}
    assert sum(r.gap_s.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    # ten GEMMs a frame, three frames, every one on the kernel, all inside the window
    assert r.count_matching(KERNEL) == 30
    assert 0 < r.seconds_matching(KERNEL) < r.busy_s
    assert dict(r.breakdown()["device_ops"])["int8_matmul"] == pytest.approx(
        r.seconds_matching(KERNEL))


KERNEL = r"^int8_matmul(\.\d+)?$"
