"""The readers of the program's own spans (``harness/program.py``): whole
CPU-size runs of each cell, the measured window's edges, a ring that lost
records, and a program that records no spans."""
import math
import sys
import time
from types import SimpleNamespace

import pytest
from tinycells import config, traffic

from harness import program, serve, spec
from harness.spans import Spans

BENCH = spec.benchmark()
READERS = [m for m in BENCH["per_layer"]
           if m["source"] in ("program_span", "program_counter") and m["name"] != "edge_pad_frac"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}


@pytest.fixture(scope="module")
def runs():
    """One CPU-size run of each cell: the run, its readings, and the seconds
    of ``npu.dispatch`` and ``npu.sync`` in its window (read before the next
    run clears the recorder)."""
    from repro.serving.spans import RECORDER

    out = {}
    for name, cell in CELLS.items():
        cfg, ref = config(cell["config"])
        tr = traffic(cell["traffic"])
        if tr["mode"] == "replay":
            tr.update(clip_s=1.0, clips=1)
        RECORDER.clear()
        run, _, ok = serve.run_cell(cfg, ref, tr, seed=2**31 + 23, seconds=1.0, trace=False,
                                    t_process=time.perf_counter(), log=lambda *a: None)
        assert ok
        npu_s = sum(sum(program.durations_ms(run, n)) for n in ("npu.dispatch", "npu.sync")) / 1e3
        out[name] = (run, {m["name"]: spec.reader(m["name"]).read(run) for m in READERS}, npu_s)
    return out


def test_the_six_program_metrics():
    assert {m["name"] for m in READERS} == {
        "npu_dispatch_ms_p50", "npu_sync_ms_p50", "round_self_ms_per_frame",
        "degrade_ms_per_frame", "edge_sync_ms_p50", "window_compiles"}


@pytest.mark.parametrize("metric", READERS, ids=lambda m: m["name"])
def test_reader_finite_in_its_cells(runs, metric):
    for cell in metric["workloads"]:
        value = runs[cell][1][metric["name"]]
        assert value is not None and math.isfinite(value), (cell, value)
        assert value >= 0
    if metric["name"] == "window_compiles":
        assert all(runs[c][1]["window_compiles"] == runs[c][0].compiles == 0
                   for c in metric["workloads"])


@pytest.mark.parametrize("cell", list(CELLS))
def test_npu_spans_inside_the_benchmarks_npu_calls(runs, cell):
    run, _, inside = runs[cell]
    outside = run.spans.total("npu_call")
    assert 0.8 * outside <= inside <= outside


def _envelope_run():
    """The program's spans before, inside and after a window of the
    benchmark's own spans."""
    from repro.serving.spans import RECORDER

    spans = Spans()
    with RECORDER.span("npu.dispatch", 1):
        pass
    with spans.span("npu_call"):
        with RECORDER.span("npu.dispatch", 2):
            time.sleep(0.002)
    with spans.span("edge_flush"):
        pass
    with RECORDER.span("npu.dispatch", 3):
        pass
    return SimpleNamespace(spans=spans, answered=1)


def test_spans_outside_the_window_are_not_counted():
    from repro.serving.spans import RECORDER

    RECORDER.clear()
    run = _envelope_run()
    assert [r.request_id for r in program.window(run)] == [2]
    assert len(program.durations_ms(run, "npu.dispatch")) == 1
    assert program.p50_ms(run, "npu.dispatch") >= 2.0
    assert program.count(run, "compile") == 0


def test_ring_that_dropped_inside_the_window_reads_none(monkeypatch):
    from repro.serving import spans as program_spans

    small = program_spans.SpanRecorder(capacity=2)
    monkeypatch.setattr(program_spans, "RECORDER", small)
    for i in range(3):  # dropped before the window: the window is whole
        with small.span("npu.sync", i):
            pass
    run = _envelope_run()
    assert small.dropped == 4
    assert program.window(run) is None
    assert spec.reader("npu_dispatch_ms_p50").read(run) is None

    small = program_spans.SpanRecorder(capacity=3)
    monkeypatch.setattr(program_spans, "RECORDER", small)
    for i in range(4):
        with small.span("npu.sync", i):
            pass
    spans = Spans()
    with spans.span("plan"):
        with small.span("npu.dispatch", 5):
            pass
    with small.span("npu.dispatch", 6):
        pass
    run = SimpleNamespace(spans=spans, answered=1)
    assert small.dropped == 3
    assert [r.request_id for r in program.window(run)] == [5]


def test_program_without_spans_reads_none(monkeypatch):
    """On a program that records no spans every reader says so, without
    raising."""
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    run = SimpleNamespace(spans=Spans(), answered=1)
    with run.spans.span("plan"):
        pass
    for m in READERS:
        assert spec.reader(m["name"]).read(run) is None, m["name"]
