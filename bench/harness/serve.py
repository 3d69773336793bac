"""One run of one cell: build, warm, drive ``VideoServer.run``, compare.

The window drives the program's own objects, built as a deployment builds
them: an ``OnlineController`` over the configuration's paper profile, a
``ModelEndpoint`` for the int8 NPU path, a ``BatchedEndpoint`` behind an
``EdgeBatchServer`` for the edge path, and ``VideoServer``.  The benchmark
gives them generated inputs and wraps its own spans around the calls into
each layer; ``VideoServer.run`` itself runs unchanged.

Two things the benchmark sets on those objects, and why:

* Each endpoint's ``forward`` takes the weights as an argument.  Built the
  program's way (a closure over the weights), the weights are constants of
  the compiled program, so every new seed compiles every program anew and
  the compile cache never serves a second seed.
* ``server.results`` is a list that stamps each ``FrameResult`` with the
  time it was appended: a frame's answer time, taken where the program
  records the answer.
"""
from __future__ import annotations

import dataclasses
import functools
import shutil
import tempfile
import time
import traceback
from collections import Counter

import numpy as np

from . import check, devtrace, stream
from .spans import Reservoir, Spans, TimedResults


@dataclasses.dataclass
class Run:
    """What one window leaves for the metric readers."""

    cfg: dict
    traffic: dict
    gemms: list  # (M, K, N) of one frame
    peaks: dict | None
    setup_seconds: float
    window_s: float
    spans: Spans
    answered: int
    scheduled: int
    latencies_s: list | None  # live traffic only: answer time minus due time
    npu_frames: int
    edge_frames: int
    edge_padded: int
    edge_flushes: int
    compiles: int
    device_trace: devtrace.Reduction | None = None
    traced_npu_frames: int = 0  # NPU frames inside the traced window
    memory_peak_bytes: int = 0
    readings: tuple | None = None  # (program, control) readings, with ``controls``


def jax_seed(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    return int(np.random.default_rng(seed).integers(2 ** 31))


@dataclasses.dataclass
class Deployment:
    prof: object  # the planner's ModelProfile
    params: object
    state: object
    qparams: object
    npu: object  # ModelEndpoint
    edge: object  # BatchedEndpoint


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _program_shapes(arch):
    import jax

    from repro.arch import abstract_params
    from repro.models.common import ParamSpec

    return [[s.shape for s in jax.tree.leaves(t, is_leaf=lambda x: isinstance(x, ParamSpec))]
            for t in abstract_params(arch)]


def deploy(cfg: dict, ref, seed: int, traffic: dict, sample: np.ndarray) -> Deployment:
    """Weights from the seed, the program's endpoints over them, and every
    shape the traffic's paths reach warmed: the NPU at batch 1; with
    ``edge`` the edge buckets and every offload resolution below the
    largest."""
    import jax
    import jax.numpy as jnp

    from repro import configs, quant
    from repro.arch import classifier_forward
    from repro.core.profiles import PAPER_MODELS
    from repro.serving import BatchedEndpoint, ModelEndpoint
    from repro.serving.engine import degrade_frame

    arch = configs.get(cfg["program_arch"], smoke=cfg.get("program_smoke", False))
    ours = [[tuple(s) for s in jax.tree.leaves(t, is_leaf=_is_shape)]
            for t in ref.param_shapes(cfg)]
    if ours != _program_shapes(arch):
        raise ValueError(f"{cfg['name']}: the program's {arch.name} does not have the "
                         "parameter shapes of the configuration file")
    prof = next(p for p in PAPER_MODELS if p.name == cfg["profile"])

    params, state = jax.jit(lambda k: ref.make_weights(k, cfg))(jax.random.key(jax_seed(seed)))
    qparams = jax.jit(quant.fake_quant_tree)(params)

    def forward(p, s, x):
        return classifier_forward(arch, p, s, x, train=False)[0]

    edge_fwd = jax.jit(forward)
    npu_fwd = jax.jit(quant.npu_forward(forward, interpret=None))
    npu = ModelEndpoint(f"{arch.name}-npu", forward, profile_latency_s=prof.t_npu)
    npu.forward = functools.partial(npu_fwd, qparams, state)
    edge = BatchedEndpoint(f"{arch.name}-edge", forward, profile_latency_s=prof.t_server,
                           max_batch=cfg["max_batch"])
    edge.forward = functools.partial(edge_fwd, params, state)

    if "npu" in traffic["paths"]:
        npu.warmup(jnp.asarray(sample[None]))
    if "edge" in traffic["paths"]:
        edge.warmup(sample)
        r_max = max(traffic["resolutions"])
        for r in traffic["resolutions"]:
            degrade_frame(sample, r, r_ref=r_max)
    return Deployment(prof, params, state, qparams, npu, edge)


class NpuCalls:
    """The NPU endpoint as ``VideoServer`` calls it, with a span round each
    call and a seeded sample of what it was given and what it answered."""

    def __init__(self, endpoint, spans: Spans, sample: Reservoir):
        self.endpoint, self.spans, self.sample = endpoint, spans, sample
        self.frames = 0

    def __call__(self, images):
        with self.spans.span("npu_call"):
            out = self.endpoint(images)
        self.frames += len(out)
        self.sample.offer((images, out))
        return out


def _wrap_flush(edge_server, spans: Spans, sample: Reservoir):
    flush = edge_server.flush

    def spanned_flush():
        reqs = list(edge_server.queue)
        with spans.span("edge_flush"):
            out = flush()
        for r in reqs:
            sample.offer((r.image, out.get((r.client_id, r.frame_id))))
        return out

    edge_server.flush = spanned_flush


def _wrap_planner(controller, spans: Spans, scheduled: list, n: int):
    next_plan = controller.next_plan

    def spanned_next_plan(head):
        with spans.span("plan"):
            plan = next_plan(head)
        scheduled.extend(head + d.frame for d in plan.decisions
                         if d.is_processed() and head + d.frame < n)
        return plan

    controller.next_plan = spanned_next_plan


def tally(runs, live: bool):
    """``(answered, scheduled, latencies, misses)`` over the window's
    ``(server, scheduled frames, source)`` runs.  A miss is a scheduled frame
    not answered exactly once, or an answer to a frame never scheduled.
    Live latencies run from each scheduled frame's due time to its answer;
    a frame never answered is infinitely late."""
    answered = scheduled_n = misses = 0
    latencies = [] if live else None
    for server, scheduled, source in runs:
        results = server.results
        got = Counter(r.frame for r in results)
        want = Counter(scheduled)
        misses += sum(((want - got) + (got - want)).values())
        answered += len(results)
        scheduled_n += len(scheduled)
        if live:
            at = dict(zip((r.frame for r in results), results.answered_at))
            latencies += [at[f] - source.due(f) if f in at else float("inf") for f in want]
    return answered, scheduled_n, latencies, misses


TRACE_SECONDS = 5.0


def _traced_window(drive, secs: float):
    """A second, shorter window under the profiler, after the measured one:
    tracing slows the host loop (per-op device events, annotations), so the
    host spans of the measured window stay untraced.  Returns ``(runs,
    reduction, error, npu frames)``."""
    import jax

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        # no Python tracer: it would time every host function call and slow
        # the loop further; the host runtime's own events stay (level 2),
        # since they tie the device's clock to the host's
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level, opts.host_tracer_level, opts.enable_hlo_proto = 0, 2, False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW):
                runs, npu_frames, _, _, error = drive(secs, Spans(annotate=True))
        finally:
            jax.profiler.stop_trace()
        reduction = devtrace.reduce(devtrace.load(devtrace.find_xplane(trace_dir)))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    return runs, reduction, error, npu_frames


def run_cell(cfg: dict, ref, traffic: dict, *, seed: int, seconds: float, trace: bool,
             t_process: float, log=print, controls: bool = False) -> tuple[Run, dict, bool]:
    """Set up, run the window, compare.  Returns the window's ``Run``, the
    compared numbers (``{name: (value, limit)}``) and whether they pass.
    With ``controls`` the lower-precision control's readings are also
    taken on the same sample (``Run.readings``: program, control); the
    benchmark's own runs never do."""
    import jax

    from repro.core import BandwidthEstimator, OnlineController, PolicySpec, StreamSpec
    from repro.core.compile_cache import CompileCounter
    from repro.core.simulator import Trace
    from repro.serving import EdgeBatchServer, VideoServer

    fps = float(traffic["fps"])
    live = traffic["mode"] == "live"
    if live:
        n_frames, clips = int(round(fps * seconds)), 1
    else:
        n_frames, clips = int(round(fps * traffic["clip_s"])), int(traffic["clips"])
    res = cfg["input_res"]
    videos = [stream.synthetic_video(n_frames, res=res, seed=seed * 7919 + c) for c in range(clips)]
    dep = deploy(cfg, ref, seed, traffic, videos[0][0][0])
    sstream = StreamSpec(fps=fps, deadline=traffic["deadline_ms"] / 1e3,
                         resolutions=tuple(traffic["resolutions"]))
    horizon = n_frames / fps + traffic["bandwidth_period_s"]
    net = Trace.piecewise(stream.square_wave(traffic["bandwidth_mbps"], traffic["bandwidth_period_s"],
                                             horizon), rtt_ms=traffic["rtt_ms"])
    samples = {"npu": Reservoir(traffic["check_sample"], seed + 1),
               "edge": Reservoir(traffic["check_sample"], seed + 2)}

    def make_controller():
        net0 = net.at(0.0)
        controller = OnlineController(models=(dep.prof,), stream=sstream,
                                      policy=PolicySpec(traffic["policy"]),
                                      estimator=BandwidthEstimator(init_bps=net0.bandwidth_bps))
        controller.estimator.observe_rtt(net0.rtt)
        return controller

    def drive(secs: float, spans: Spans):
        """One window of this traffic: ``(runs, npu frames, t_start, t_end,
        error)``, each run ``(server, scheduled frames, source)``."""
        npu_calls = NpuCalls(dep.npu, spans, samples["npu"])

        def serve_one(source, labels):
            scheduled: list = []
            controller = make_controller()
            _wrap_planner(controller, spans, scheduled, len(source))
            edge_server = EdgeBatchServer({0: dep.edge})
            _wrap_flush(edge_server, spans, samples["edge"])
            server = VideoServer(controller=controller, npu_endpoints={0: npu_calls},
                                 stream=sstream, trace=net, edge_server=edge_server)
            server.results = TimedResults()
            runs.append((server, scheduled, source))
            if live:
                source.start()
            server.run(source, labels)

        runs, error = [], None
        t_start = time.perf_counter()
        try:
            if live:
                frames, labels = videos[0]
                n = int(round(fps * secs))
                serve_one(stream.FrameSource(frames[:n], fps=fps,
                                             on_wait=lambda: spans.span("frame_wait")), labels[:n])
                t_start = runs[0][2].t0
            else:
                while not runs or time.perf_counter() - t_start < secs:
                    frames, labels = videos[len(runs) % clips]
                    serve_one(stream.FrameSource(frames), labels)
        except Exception:  # a broken program is an incorrect run, not a crash
            error = traceback.format_exc()
        return runs, npu_calls.frames, t_start, time.perf_counter(), error

    # one planner round, so that nothing the planner touches first runs in the window
    make_controller().next_plan(0)

    spans = Spans()
    with CompileCounter() as counter:
        runs, npu_frames, t_start, t_end, error = drive(seconds, spans)
        edge_stats = dataclasses.replace(dep.edge.stats)  # the measured window's alone
        if trace and not error:
            traced = _traced_window(drive, min(seconds, TRACE_SECONDS))
    log(f"window: {len(runs)} run(s) of VideoServer.run, {t_end - t_start} s; "
        f"compiles in window: {counter.compiles}")
    if error:
        log(f"window ended by {error}")

    answered, scheduled_n, latencies, misses = tally(runs, live)
    if trace and not error:
        misses += tally(traced[0], live)[3]
        error = traced[2]
    if error:
        misses = max(misses, 1)

    run = Run(cfg=cfg, traffic=traffic, gemms=ref.gemms(cfg), peaks=None,
              setup_seconds=t_start - t_process, window_s=t_end - t_start, spans=spans,
              answered=answered, scheduled=scheduled_n, latencies_s=latencies,
              npu_frames=npu_frames, edge_frames=edge_stats.frames,
              edge_padded=edge_stats.padded, edge_flushes=edge_stats.flushes,
              compiles=counter.compiles)
    if trace and not error:
        run.device_trace, run.traced_npu_frames = traced[1], traced[3]

    memory = jax.devices()[0].memory_stats() or {}
    run.memory_peak_bytes = int(memory.get("peak_bytes_in_use", 0))

    items = {p: r.items for p, r in samples.items()}
    # the program's state goes before the reference runs
    params, state = dep.params, dep.state
    del dep
    values = check.readings(cfg, ref, params, state, items)
    if controls:
        run.readings = (values, check.readings(cfg, ref, params, state, items, control=True))
    numbers = check.compare(cfg, values)
    numbers["answered_once_misses"] = (misses, 0)
    return run, numbers, check.passes(numbers)
