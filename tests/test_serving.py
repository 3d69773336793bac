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

def _lax_patch_conv(p, x, stride, padding):
    """The lowering ``convnets.im2col`` replaced, kept as the reference:
    patches from ``lax.conv_general_dilated_patches`` in (cin, kh, kw) order
    and the weight transposed to match."""
    import jax
    import jax.numpy as jnp

    from repro.models.common import matmul

    kh, kw, cin, cout = p.shape
    w = p.astype(x.dtype)
    if (kh, kw) == (1, 1) and stride == 1:
        return matmul(x, w.reshape(cin, cout))
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return matmul(patches, jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout))


# Every conv class of the published configs, and a narrow 7x7 input on
# each side of the patch-builder rule (49 views over 3 and over 4 lane
# tiles of the patch row): (kernel, stride, padding, input height and width,
# input channels, patch builder).
CONV_CASES = {
    "3x3_s1_same": (3, 1, "SAME", 9, 16, "slices"),
    "3x3_s2_same_odd": (3, 2, "SAME", 9, 16, "slices"),
    "3x3_s2_same_even": (3, 2, "SAME", 8, 16, "slices"),
    "3x3_s2_cin3": (3, 2, "SAME", 16, 3, "slices"),
    "7x7_s2_cin3": (7, 2, "SAME", 16, 3, "patch_op"),
    "7x7_s2_cin7": (7, 2, "SAME", 16, 7, "patch_op"),
    "7x7_s2_cin8": (7, 2, "SAME", 16, 8, "slices"),
    "1x1_s1": (1, 1, "SAME", 9, 16, "pointwise"),
    "1x1_s2": (1, 2, "SAME", 9, 16, "strided_pointwise"),
    "4x4_s4_valid_cin3": (4, 4, "VALID", 16, 3, "nonoverlap"),
    "4x4_s4_valid_wide": (4, 4, "VALID", 18, 16, "nonoverlap"),
}


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_matmul_backend_conv_equivalence(case):
    """conv() through the backend hook (im2col + GEMM) == lax.conv, by the
    patch builder its shape selects; and through the int8 kernel it is bit
    for bit the (cin, kh, kw) lowering it replaced: the kernel quantizes each
    row and column by its absolute maximum and sums K in int32, so the same
    permutation of K on both operands changes no output bit."""
    import jax
    import jax.numpy as jnp

    from repro import quant
    from repro.models import convnets
    from repro.models.common import matmul_backend

    k, stride, padding, hw, cin, path = CONV_CASES[case]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, hw, hw, cin)).astype(np.float32))
    p = jnp.asarray(rng.standard_normal((k, k, cin, 8)).astype(np.float32))

    direct = convnets.conv(p, x, stride=stride, padding=padding)
    convnets.CONV_PATHS.clear()
    with matmul_backend(lambda a, b: a @ b):
        routed = convnets.conv(p, x, stride=stride, padding=padding)
    assert convnets.CONV_PATHS == {path: 1}
    np.testing.assert_allclose(np.asarray(routed), np.asarray(direct), rtol=1e-5, atol=1e-5)

    xb = x.astype(jnp.bfloat16)
    with quant.npu_execution(interpret=True):
        ours = jax.jit(lambda p, x: convnets.conv(p, x, stride=stride, padding=padding))(p, xb)
        ref = jax.jit(lambda p, x: _lax_patch_conv(p, x, stride, padding))(p, xb)
    assert np.array_equal(np.asarray(ours), np.asarray(ref))


@pytest.mark.parametrize("arch_name", ["resnet-50", "squeezenet"])
def test_int8_forward_bitwise_unchanged_by_patch_order(arch_name, monkeypatch):
    """The SMOKE int8 forward gives bit for bit the logits of the (cin, kh,
    kw) lax-patch lowering."""
    import jax
    import jax.numpy as jnp

    from repro import configs, quant
    from repro.arch import abstract_params as arch_params
    from repro.arch import classifier_forward
    from repro.models import convnets
    from repro.models.common import init_tree

    arch = configs.get(arch_name, smoke=True)
    specs, state_specs = arch_params(arch)
    params = jax.jit(lambda k: init_tree(k, specs))(jax.random.key(0))
    state = jax.jit(lambda k: init_tree(k, state_specs))(jax.random.key(1))
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 32, 32, 3)).astype(np.float32))

    def logits():
        fwd = quant.npu_forward(
            lambda p, s, x: classifier_forward(arch, p, s, x, train=False)[0], interpret=True)
        return np.asarray(jax.jit(fwd)(params, state, x))

    ours = logits()
    monkeypatch.setattr(convnets, "_conv_via_matmul", _lax_patch_conv)
    ref = logits()
    assert np.all(np.isfinite(ref))
    assert np.array_equal(ours, ref)


# Conv call sites per patch builder at 224 x 224, a scan body once.
PUBLISHED_CONV_PATHS = {
    "resnet-50": {"patch_op": 1, "slices": 8, "pointwise": 17, "strided_pointwise": 3},
    "squeezenet": {"slices": 9, "pointwise": 17},
    "swin-b": {"nonoverlap": 1},
}


@pytest.mark.parametrize("arch_name", list(PUBLISHED_CONV_PATHS))
def test_conv_path_tally_at_published_width(arch_name):
    """Which patch builder each conv of the int8 forward takes at published
    width, traced (not run) at one 224 x 224 frame."""
    import jax
    import jax.numpy as jnp

    from repro import configs, quant
    from repro.arch import abstract_params as arch_params
    from repro.arch import classifier_forward
    from repro.models import convnets
    from repro.models.common import abstract_tree

    arch = configs.get(arch_name)
    specs, state_specs = arch_params(arch)
    fwd = quant.npu_forward(lambda p, s, x: classifier_forward(arch, p, s, x, train=False)[0])
    convnets.CONV_PATHS.clear()
    jax.eval_shape(fwd, abstract_tree(specs), abstract_tree(state_specs),
                   jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32))
    assert convnets.CONV_PATHS == PUBLISHED_CONV_PATHS[arch_name]


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
