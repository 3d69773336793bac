"""The serving loop's spans and compile counter (``serving/spans``): the
recorder's rules, the spans a ``VideoServer.run`` of a SMOKE classifier
leaves, and the same spans in a ``jax.profiler`` capture."""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compile_cache import CompileCounter
from repro.serving import spans
from repro.serving.spans import SpanRecorder


@pytest.fixture
def no_disk_cache():
    """A compile the persistent cache serves is not a compile: keep the
    disk cache out of tests that count them."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_nesting_records_parent_and_request():
    rec = SpanRecorder()
    with rec.span("round", 10):
        with rec.span("plan"):
            pass
        rec.set_request(12)
        with rec.span("npu.dispatch"):
            with rec.span("inner"):
                pass
        rec.set_request(None)
        with rec.span("edge.flush"):
            pass
    with rec.span("alone"):
        pass
    by = {r.name: r for r in rec.records()}
    assert by["round"].parent is None and by["round"].request_id == 10
    assert by["plan"].parent == by["round"].id and by["plan"].request_id == 10
    assert by["npu.dispatch"].parent == by["round"].id and by["npu.dispatch"].request_id == 12
    assert by["inner"].parent == by["npu.dispatch"].id and by["inner"].request_id == 12
    assert by["edge.flush"].request_id == 10
    assert by["alone"].parent is None and by["alone"].request_id is None
    for name in ("plan", "npu.dispatch", "edge.flush"):
        assert by["round"].start_ns <= by[name].start_ns <= by[name].end_ns <= by["round"].end_ns


def test_ring_drops_the_oldest_and_counts():
    rec = SpanRecorder(capacity=4)
    for i in range(7):
        with rec.span("s", i):
            pass
    assert [r.request_id for r in rec.records()] == [3, 4, 5, 6]
    assert rec.dropped == 3
    rec.clear()
    assert rec.records() == [] and rec.dropped == 0


def test_disabled_records_nothing(no_disk_cache):
    rec = SpanRecorder()
    rec.enabled = False
    with rec.span("round", 1):
        jax.jit(lambda x: x * 5.0 - 2.0)(jnp.ones(11)).block_until_ready()
    assert rec.records() == []
    rec.enabled = True
    with rec.span("round", 2):
        pass
    assert [r.request_id for r in rec.records()] == [2]


def test_compile_is_counted_under_its_span(no_disk_cache):
    rec = SpanRecorder()
    x = jnp.ones(13)
    with rec.span("step", 4):
        jax.jit(lambda v: v * 3.0 + 1.0)(x).block_until_ready()
    with rec.span("warm"):
        f = jax.jit(lambda v: v - 7.0)
        f(x).block_until_ready()
    with rec.span("again"):
        f(x).block_until_ready()
    compiles = [r for r in rec.records() if r.name == spans.COMPILE]
    step = next(r for r in rec.records() if r.name == "step")
    assert compiles and all(r.duration_ns == 0 for r in compiles)
    assert any(r.parent == step.id and r.request_id == 4 for r in compiles)
    by_parent = rec.summary()["compiles"]
    assert by_parent.get("step", 0) >= 1 and by_parent.get("warm", 0) >= 1
    assert "again" not in by_parent


@pytest.mark.parametrize("events,compiles", [
    (["/jax/core/compile/backend_compile_duration"], 1),
    (["/jax/compilation_cache/compile_requests_use_cache", "/jax/compilation_cache/cache_misses",
      "/jax/core/compile/backend_compile_duration"], 1),
    (["/jax/compilation_cache/compile_requests_use_cache", "/jax/compilation_cache/cache_hits",
      "/jax/core/compile/backend_compile_duration"], 0),
    (["/jax/compilation_cache/cache_hits", "/jax/core/compile/backend_compile_duration",
      "/jax/core/compile/backend_compile_duration", "/jax/other"], 1),
])
def test_compile_counter_rule(events, compiles):
    """A backend build is a compile unless the persistent cache served it;
    the recorder and ``CompileCounter`` share the rule."""
    counter = CompileCounter()
    marked = [counter.see(e) for e in events]
    assert counter.compiles == sum(marked) == compiles


def test_summary_agrees_with_records():
    rec = SpanRecorder()
    for i in range(9):
        with rec.span("a", i):
            sum(range(1000 * (i + 1)))
    with rec.span("b"):
        pass
    got = rec.summary()
    d = np.array([r.duration_ns for r in rec.records() if r.name == "a"]) / 1e6
    a = got["spans"]["a"]
    assert a["count"] == 9 and got["spans"]["b"]["count"] == 1
    assert a["total_ms"] == pytest.approx(d.sum())
    assert a["p50_ms"] == pytest.approx(np.percentile(d, 50))
    assert a["p95_ms"] == pytest.approx(np.percentile(d, 95))
    assert got["dropped"] == 0 and got["compiles"] == {}


# ---------------------------------------------------------------------------
# One VideoServer.run of a SMOKE classifier
# ---------------------------------------------------------------------------

def _smoke_server():
    """VideoServer over a SMOKE SqueezeNet: int8 NPU endpoint, bf16 edge
    behind an EdgeBatchServer, max_accuracy on a constant 8 Mbps link.
    Returns ``(server, endpoint, frames, labels, rounds)``."""
    from repro import configs, quant
    from repro.arch import abstract_params, classifier_forward
    from repro.core import BandwidthEstimator, OnlineController, PolicySpec, StreamSpec
    from repro.core.profiles import SQUEEZENET, NetworkState
    from repro.models.common import init_tree
    from repro.serving import (
        BatchedEndpoint,
        EdgeBatchServer,
        ModelEndpoint,
        VideoServer,
        make_synthetic_video,
    )

    arch = configs.get("squeezenet", smoke=True)
    specs, state_specs = abstract_params(arch)
    params = init_tree(jax.random.key(0), specs)
    state = init_tree(jax.random.key(1), state_specs)
    qparams = quant.fake_quant_tree(params)

    def forward(p, x):
        return classifier_forward(arch, p, state, x, train=False)[0]

    npu_fwd = quant.npu_forward(forward)
    npu = ModelEndpoint("npu", lambda x: npu_fwd(qparams, x), profile_latency_s=SQUEEZENET.t_npu)
    edge = BatchedEndpoint("edge", lambda x: forward(params, x), max_batch=16)
    frames, labels = make_synthetic_video(12, n_classes=10, res=32, seed=5)
    npu.warmup(jnp.asarray(frames[:1]))
    edge.warmup(frames[0])
    stream = StreamSpec(fps=30.0)
    net = NetworkState(bandwidth_bps=8e6, rtt=0.05)
    controller = OnlineController(models=(SQUEEZENET,), stream=stream,
                                  policy=PolicySpec("max_accuracy"),
                                  estimator=BandwidthEstimator(init_bps=net.bandwidth_bps))
    controller.estimator.observe_rtt(net.rtt)
    rounds = []
    next_plan = controller.next_plan
    controller.next_plan = lambda head: rounds.append(head) or next_plan(head)
    server = VideoServer(controller=controller, npu_endpoints={0: npu}, stream=stream,
                         trace=net, edge_server=EdgeBatchServer({0: edge}))
    return server, edge, frames, labels, rounds


@pytest.fixture(scope="module")
def served():
    server, edge, frames, labels, rounds = _smoke_server()
    spans.RECORDER.clear()
    summary = server.run(frames, labels)
    return summary, edge, rounds, spans.RECORDER.records(), (server, frames, labels)


def test_run_counts(served):
    summary, edge, rounds, recs, _ = served
    count = {n: sum(r.name == n for r in recs) for n in
             ("round", "npu.dispatch", "npu.sync", "npu.put", "edge.flush", "edge.dispatch")}
    assert summary["npu_frames"] > 0 and summary["edge_frames"] > 0
    assert count["npu.dispatch"] == count["npu.sync"] == count["npu.put"] == summary["npu_frames"]
    assert count["edge.dispatch"] == edge.stats.flushes
    rounds_with_offloads = {r.parent for r in recs if r.name == "offload.degrade"}
    assert count["edge.flush"] == len(rounds_with_offloads) == summary["batch"]["flushes"] > 0
    assert count["round"] == len(rounds)
    assert sorted(r.request_id for r in recs if r.name == "round") == rounds


def test_round_parents_its_spans(served):
    _, _, _, recs, _ = served
    rounds = {r.id: r for r in recs if r.name == "round"}
    by_id = {r.id: r for r in recs}
    npu_frames = set()
    for r in recs:
        if r.name.startswith("npu.") or r.name in ("plan", "offload.degrade", "edge.flush"):
            outer = rounds[r.parent]
            assert outer.start_ns <= r.start_ns <= r.end_ns <= outer.end_ns
            if r.name in ("plan", "edge.flush"):
                assert r.request_id == outer.request_id
            else:
                assert r.request_id >= outer.request_id
            if r.name.startswith("npu."):
                npu_frames.add(r.request_id)
        if r.name.startswith("edge.") and r.name != "edge.flush":
            flush = by_id[r.parent]
            assert flush.name == "edge.flush" and r.request_id == flush.request_id
    assert len(npu_frames) == sum(r.name == "npu.dispatch" for r in recs)


def test_profiler_capture_holds_the_spans(served, tmp_path):
    from jax.profiler import ProfileData

    server, frames, labels = served[-1]
    jax.profiler.start_trace(str(tmp_path))
    try:
        summary = server.run(frames, labels)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans.PREFIX):
                    found.setdefault(e.name, set()).add(plane.name)
    assert summary["npu_frames"] > 0
    assert any(p.startswith("/host:") for p in found.get("fastva.npu.dispatch", ())), found
    assert "fastva.round" in found and "fastva.npu.sync" in found
