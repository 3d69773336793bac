"""End-to-end behaviour tests for the full FastVA system: the serving stack
(real models + controller + deadlines), the planner's latency budget, and
the NPU path's agreement with full precision."""
from __future__ import annotations

import jax
import pytest


@pytest.mark.slow
def test_serving_end_to_end_deadlines():
    """Serve a synthetic video through the full stack; all executed frames
    must have met their planned deadline and accuracy must beat chance."""
    from repro.launch import serve as S

    summary = S.main(
        ["--policy", "max_accuracy", "--frames", "80", "--bandwidth", "2.0", "--fps", "30"]
    )
    assert summary["frames"] >= 60
    assert summary["deadline_met_frac"] == 1.0
    assert summary["accuracy"] > 0.2  # > chance (10 classes)
    assert summary["npu_frames"] + summary["edge_frames"] == summary["frames"]


def test_serving_controller_adapts_bandwidth():
    from repro.core import BandwidthEstimator

    est = BandwidthEstimator(init_bps=8e6, beta=0.5, pessimism=1.0)
    for _ in range(12):
        est.observe_upload(125_000, 1.0)  # 1 Mbps observed
    assert est.state().bandwidth_bps == pytest.approx(1e6, rel=0.05)


def test_scheduler_latency_budget():
    """Paper: scheduling runs in < 1 ms on a phone.  Our Python planner must
    stay well under the 200 ms frame deadline; the jitted DP under 20 ms."""
    import time

    from repro.core import PAPER_MODELS, PAPER_STREAM, network_mbps
    from repro.core.jax_sched import local_accuracy_dp_jax
    from repro.core.max_accuracy import plan_round

    models = list(PAPER_MODELS)
    net = network_mbps(2.0)
    plan_round(models, PAPER_STREAM, net)  # warm caches
    t0 = time.perf_counter()
    for _ in range(20):
        plan_round(models, PAPER_STREAM, net)
    py_ms = (time.perf_counter() - t0) / 20 * 1e3
    assert py_ms < 50, f"python planner too slow: {py_ms:.1f} ms"

    kw = dict(n_frames=6, gamma=1 / 30, deadline=0.2, npu_free=0.0, first_arrival=1 / 30)
    local_accuracy_dp_jax(models, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(20):
        local_accuracy_dp_jax(models, **kw)
    jit_ms = (time.perf_counter() - t0) / 20 * 1e3
    assert jit_ms < 20, f"jitted DP too slow: {jit_ms:.1f} ms"


def test_npu_edge_paths_disagree_predictably():
    """System-level NPU characterization (paper §III.A): the quantized path
    agrees with full precision on most inputs but not all."""
    from repro import configs, quant
    from repro.arch import abstract_params, classifier_forward
    from repro.models.common import init_tree

    rng_in = jax.random.normal(jax.random.key(5), (64, 32, 32, 3))
    agreements = {}
    for name in ("squeezenet", "resnet-50"):
        a = configs.get(name, smoke=True)
        specs, st_specs = abstract_params(a)
        params = init_tree(jax.random.key(0), specs)
        state = init_tree(jax.random.key(1), st_specs)
        qparams, _ = quant.npu_variant(params)
        fwd = lambda p, x, a=a, s=state: classifier_forward(a, p, s, x, train=False)[0]
        agreements[name] = quant.agreement(fwd, params, qparams, rng_in)
    assert all(0.3 <= v <= 1.0 for v in agreements.values()), agreements
