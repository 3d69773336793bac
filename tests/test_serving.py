"""Serving-loop regressions: the estimator-echo fix (a wrong bandwidth belief
must converge to the TRUE link during ``VideoServer.run``), frame degradation,
the matmul-backend hook that routes convolutions through ``kernels/npu_matmul``,
and the measured-profile calibration pipeline."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import BandwidthEstimator, OnlineController, PolicySpec, profile_ms
from repro.core.profiles import NetworkState, StreamSpec


# ---------------------------------------------------------------------------
# Toy serving stack: real VideoServer/controller, trivial models
# ---------------------------------------------------------------------------

def _toy_stack(*, policy="offload", true_mbps=4.0, init_bps=None, fps=10.0,
               use_edge_server=False):
    import jax.numpy as jnp

    from repro.serving import (
        BatchedEndpoint,
        EdgeBatchServer,
        ModelEndpoint,
        VideoServer,
        make_synthetic_video,
    )

    res = 8
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.standard_normal((res * res * 3, 10)).astype(np.float32))

    def forward(x):
        return jnp.tanh(x).reshape(x.shape[0], -1) @ W

    prof = profile_ms(
        "toy",
        t_npu_ms=5.0,
        t_server_ms=5.0,
        acc_server={45: 0.30, 134: 0.55, 224: 0.80},
        acc_npu={224: 0.60},
    )
    stream = StreamSpec(fps=fps)
    true_net = NetworkState(bandwidth_bps=true_mbps * 1e6, rtt=0.02)
    controller = OnlineController(
        models=[prof],
        stream=stream,
        policy=PolicySpec.coerce(policy),
        estimator=BandwidthEstimator(
            init_bps=init_bps if init_bps is not None else true_net.bandwidth_bps
        ),
    )
    npu = ModelEndpoint("toy-npu", forward, profile_latency_s=prof.t_npu)
    kwargs = {}
    if use_edge_server:
        ep = BatchedEndpoint("toy-edge", forward, max_batch=8)
        ep.warmup(np.zeros((res, res, 3), np.float32))
        kwargs["edge_server"] = EdgeBatchServer({0: ep})
    else:
        kwargs["edge_endpoints"] = {0: ModelEndpoint("toy-edge", forward, profile_latency_s=prof.t_server)}
    server = VideoServer(
        controller=controller, npu_endpoints={0: npu}, stream=stream,
        trace=true_net, **kwargs,
    )
    frames, labels = make_synthetic_video(60, n_classes=10, res=res, seed=3)
    return server, controller, frames, labels, true_net


# ---------------------------------------------------------------------------
# Estimator echo fix: wrong beliefs converge during run()
# ---------------------------------------------------------------------------

def test_estimator_converges_from_optimistic_prior():
    """Belief starts 10x HIGH; the loop must report measured transfer times
    (not its own predictions) so the EWMA converges down to the true link.
    With the echo bug, each observation reproduced the belief and the wrong
    prior persisted forever."""
    server, controller, frames, labels, true_net = _toy_stack(
        policy="offload", true_mbps=4.0, init_bps=40e6
    )
    server.run(frames, labels)
    est = controller.estimator
    assert est.samples >= 20  # offload ships (and measures) nearly every frame
    rel_err = abs(est._bps - true_net.bandwidth_bps) / true_net.bandwidth_bps
    assert rel_err < 0.1, f"estimator stuck at {est._bps:.3g} (true {true_net.bandwidth_bps:.3g})"


def test_estimator_converges_from_pessimistic_prior():
    """Belief starts 4x LOW with a generous frame gap (so the Offload policy
    still believes shipping is sustainable and keeps probing): it converges up."""
    server, controller, frames, labels, true_net = _toy_stack(
        policy="offload", true_mbps=4.0, init_bps=1e6, fps=4.0
    )
    server.run(frames, labels)
    est = controller.estimator
    assert est.samples >= 20
    rel_err = abs(est._bps - true_net.bandwidth_bps) / true_net.bandwidth_bps
    assert rel_err < 0.1, f"estimator stuck at {est._bps:.3g} (true {true_net.bandwidth_bps:.3g})"


def test_dead_link_misses_frames_without_poisoning_the_clock():
    """True link dead while the belief says fine: offloaded frames miss (no
    inference result), the estimator decays, and the virtual uplink clock
    stays finite so a later recovery could still transmit."""
    server, controller, frames, labels, _ = _toy_stack(
        policy="offload", true_mbps=4.0, init_bps=4e6
    )
    server._net_at = lambda t: NetworkState(bandwidth_bps=0.0, rtt=0.02)
    summary = server.run(frames, labels)
    dead = [r for r in server.results if r.where == "server"]
    assert dead and all(not r.deadline_met and not r.correct for r in dead)
    assert np.isfinite(server._net_free_abs)
    assert summary["deadline_met_frac"] < 1.0
    # inf-time observations drive the belief toward zero, not to NaN.
    assert 0.0 <= controller.estimator._bps < 4e6


def test_videoserver_measured_latency_includes_uplink_queueing():
    """Two offloads in one round share the serial uplink: the second frame's
    measured finish must queue behind the first's transfer."""
    server, controller, frames, labels, true_net = _toy_stack(
        policy="offload", true_mbps=4.0
    )
    server.run(frames[:10], labels[:10])
    lats = [r.latency_s for r in server.results if r.where == "server"]
    t_up_224 = true_net.upload_time(server.stream.frame_bytes(224))
    # every measured latency >= one true transfer + rtt + service
    assert all(lat >= min(t_up_224, true_net.upload_time(server.stream.frame_bytes(45))) for lat in lats)
    assert summary_finite(server.summary())


def summary_finite(s: dict) -> bool:
    return np.isfinite(s["fps_sustained"]) and np.isfinite(s["mean_latency_s"])


def test_videoserver_edge_server_batches_and_matches_endpoints():
    """With an EdgeBatchServer attached, predictions are identical to the
    per-frame endpoint path and batch stats land in the summary."""
    s1, _, frames, labels, _ = _toy_stack(policy="offload", use_edge_server=False)
    s2, _, _, _, _ = _toy_stack(policy="offload", use_edge_server=True)
    sum1 = s1.run(frames, labels)
    sum2 = s2.run(frames, labels)
    assert sum1["accuracy"] == sum2["accuracy"]
    assert sum1["edge_frames"] == sum2["edge_frames"] > 0
    assert sum2["batch"]["flushes"] > 0
    assert sum2["batch"]["mean_batch"] >= 1.0


# ---------------------------------------------------------------------------
# degrade_frame
# ---------------------------------------------------------------------------

def test_degrade_frame_identity_at_full_resolution():
    from repro.serving import degrade_frame

    f = np.random.default_rng(1).standard_normal((16, 16, 3)).astype(np.float32)
    assert degrade_frame(f, 224, r_ref=224) is f
    assert degrade_frame(f, 500, r_ref=224) is f


def test_degrade_frame_loses_information_monotonically():
    from repro.serving import degrade_frame

    f = np.random.default_rng(2).standard_normal((32, 32, 3)).astype(np.float32)
    errs = []
    for r in (179, 90, 45):
        g = degrade_frame(f, r, r_ref=224)
        assert g.shape == f.shape and g.dtype == f.dtype
        errs.append(float(np.linalg.norm(g - f)))
    assert errs[0] > 0
    assert errs == sorted(errs)  # smaller resolution -> more loss


# ---------------------------------------------------------------------------
# matmul backend hook + im2col conv lowering
# ---------------------------------------------------------------------------

def test_matmul_backend_conv_equivalence():
    """conv() through the backend hook (im2col + GEMM) == lax.conv, including
    the strided-1x1 projection case."""
    import jax.numpy as jnp

    from repro.models import convnets
    from repro.models.common import matmul_backend

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 9, 9, 4)).astype(np.float32))
    cases = [
        (jnp.asarray(rng.standard_normal((3, 3, 4, 8)).astype(np.float32)), 1),
        (jnp.asarray(rng.standard_normal((3, 3, 4, 8)).astype(np.float32)), 2),
        (jnp.asarray(rng.standard_normal((1, 1, 4, 8)).astype(np.float32)), 1),
        (jnp.asarray(rng.standard_normal((1, 1, 4, 8)).astype(np.float32)), 2),  # strided proj
    ]
    for p, stride in cases:
        direct = convnets.conv(p, x, stride=stride)
        with matmul_backend(lambda a, b: a @ b):
            routed = convnets.conv(p, x, stride=stride)
        np.testing.assert_allclose(
            np.asarray(routed), np.asarray(direct), rtol=1e-5, atol=1e-5
        )


def test_matmul_backend_counts_and_restores():
    """The hook is a stack: active inside the context (every matmul counted),
    inert outside (plain @)."""
    import jax.numpy as jnp

    from repro.models.common import current_matmul, matmul, matmul_backend

    calls = []

    def counting(a, b):
        calls.append((a.shape, b.shape))
        return a @ b

    x = jnp.ones((3, 4, 5))
    w = jnp.ones((5, 6))
    base = matmul(x, w)
    assert not calls and current_matmul() is None
    with matmul_backend(counting):
        out = matmul(x, w)
    assert len(calls) == 1 and calls[0] == ((12, 5), (5, 6))  # leading dims flattened
    assert current_matmul() is None
    np.testing.assert_allclose(np.asarray(out), np.asarray(base))


def test_npu_forward_routes_model_matmuls_through_kernel():
    """A squeezenet-smoke forward under the NPU execution context runs its
    convs/head as int8 kernel GEMMs: close to (quantization error), but not
    bit-identical to, the full-precision forward."""
    import jax
    import jax.numpy as jnp

    from repro import configs, quant
    from repro.arch import abstract_params as arch_params
    from repro.arch import classifier_forward
    from repro.models.common import init_tree

    arch = configs.get("squeezenet", smoke=True)
    specs, state_specs = arch_params(arch)
    params = init_tree(jax.random.key(0), specs)
    state = init_tree(jax.random.key(1), state_specs)

    def forward(p, x):
        return classifier_forward(arch, p, state, x, train=False)[0]

    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 16, 16, 3)).astype(np.float32))
    fp = np.asarray(forward(params, x), np.float32)
    routed = np.asarray(quant.npu_forward(forward, interpret=True)(params, x), np.float32)
    assert fp.shape == routed.shape
    assert np.any(fp != routed), "kernel routing was a no-op (backend never engaged)"
    assert np.all(np.isfinite(routed))
    # Untrained logits are tiny (relu kills most), so judge the int8 error
    # relative to the logit scale, not the near-zero vector norm.
    denom = max(float(np.max(np.abs(fp))), 1e-6)
    assert float(np.max(np.abs(fp - routed))) / denom < 0.25  # round-off, not garbage


def _rel_l2(a, ref):
    return float(np.linalg.norm(np.asarray(a, np.float64) - ref) / np.linalg.norm(ref))


@pytest.mark.parametrize("family", ["resnet", "squeezenet"])
def test_reference_forward_is_float32_and_matches_served_forward(family):
    """models/reference computes in float32 (it agrees with its own float64
    run), while the served convnets forward computes in bfloat16: the two
    differ, by bf16 round-off and no more.  ResNet gets stacked blocks and
    BatchNorm state away from the identity so every branch is exercised."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.arch import abstract_params as arch_params
    from repro.arch import classifier_forward
    from repro.models import convnets
    from repro.models.common import init_tree
    from repro.models.reference import reference_logits

    if family == "resnet":
        arch = dataclasses.replace(
            configs.get("resnet-50", smoke=True),
            cfg=convnets.ResNetConfig(name="resnet-stacked", depths=(2, 2), width=8, n_classes=10),
        )
    else:
        arch = configs.get("squeezenet", smoke=True)
    specs, state_specs = arch_params(arch)
    rng = np.random.default_rng(5)
    shift = lambda t, lo, hi: jax.tree.map(  # noqa: E731
        lambda a: a + jnp.asarray(rng.uniform(lo, hi, a.shape), a.dtype), t)
    # one compiled init per tree: leaf by leaf, every shape is its own compile
    params = shift(jax.jit(lambda k: init_tree(k, specs))(jax.random.key(0)), -0.1, 0.1)
    state = shift(jax.jit(lambda k: init_tree(k, state_specs))(jax.random.key(1)), 0.0, 0.5)
    x = rng.standard_normal((4, 32, 32, 3)).astype(np.float32)

    served = jax.jit(lambda p, s, x: classifier_forward(arch, p, s, x, train=False)[0])(
        params, state, x)
    ref32 = np.asarray(jax.jit(lambda p, s, x: reference_logits(arch, p, s, x))(params, state, x))
    with jax.enable_x64(True):
        ref64 = np.asarray(jax.jit(
            lambda p, s, x: reference_logits(arch, p, s, x, dtype=jnp.float64))(params, state, x))
    assert ref32.dtype == np.float32 and ref32.shape == served.shape == (4, 10)
    assert _rel_l2(ref32, ref64) < 1e-5
    err = _rel_l2(served, ref64)
    assert 0.0 < err < 5e-2, err


# ---------------------------------------------------------------------------
# Calibration pipeline (heavy: trains + compiles both variants)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_calibration_artifact_roundtrips_into_scenariospec(tmp_path):
    import dataclasses

    from repro.serving import CalibrationConfig, calibrate, load_calibration, save_calibration
    from repro.session import ScenarioSpec

    cfg = dataclasses.replace(
        CalibrationConfig.smoke(),
        model_names=("squeezenet",),
        train_steps={"squeezenet": 10},
        holdout_frames=32,
        batch_sizes=(1,),
        repeats=1,
    )
    cal = calibrate(cfg)
    path = save_calibration(cal.artifact, tmp_path / "calibration.json")
    art = load_calibration(path)

    (m,) = art["models"]
    assert m["name"] == "squeezenet"
    assert m["t_npu_ms"] >= 1.0 and m["t_server_ms"] >= 1.0  # measured, floored
    assert set(m["acc_server"]) == {"45", "90", "134", "179", "224"}
    assert m["provenance"]["source"] == "measured"
    assert m["provenance"]["kernel"].startswith("kernels/npu_matmul")
    assert 0.0 <= m["provenance"]["fp32_int8_agreement"] <= 1.0

    spec = ScenarioSpec(policy="max_accuracy", models=art["models"], n_frames=4)
    prof = spec.models[0]
    assert prof.t_npu == pytest.approx(m["t_npu_ms"] / 1e3)
    assert prof.acc_server[45] == m["acc_server"]["45"]
    assert prof.accuracy(100, where="server") >= 0.0  # interpolation works

    # The endpoints returned alongside the artifact are live and agree with
    # the payload's provenance (same variants that were measured).
    logits = cal.models[0].npu_endpoint(np.zeros((1, cfg.res, cfg.res, 3), np.float32))
    assert logits.shape == (1, cfg.n_classes)


def test_load_calibration_rejects_foreign_json(tmp_path):
    import json

    from repro.serving import load_calibration

    p = tmp_path / "other.json"
    p.write_text(json.dumps({"schema": "something-else", "models": []}))
    with pytest.raises(ValueError):
        load_calibration(p)
