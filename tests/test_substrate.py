"""Substrate tests: the optimizer and the fault-tolerance units (heartbeats,
stragglers, elastic remesh planning).  The optimizer driving a served
classifier's training is in ``test_serving.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.train import optim


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_converges_quadratic():
    c = optim.AdamWConfig(lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0, grad_clip=0)
    params = {"w": jnp.asarray([5.0, -3.0])}
    opt = optim.init_opt_state(params)
    for _ in range(200):
        g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = optim.adamw_update(c, params, g, opt)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.05


def test_grad_clip_bounds_update():
    c = optim.AdamWConfig(lr=1.0, grad_clip=1.0, warmup_steps=0, total_steps=10, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    opt = optim.init_opt_state(params)
    g = {"w": jnp.full(4, 1e6)}
    p2, _, metrics = optim.adamw_update(c, params, g, opt)
    assert float(metrics["grad_norm"]) == pytest.approx(2e6, rel=1e-3)
    assert bool(jnp.all(jnp.isfinite(p2["w"])))


def test_lr_schedule_shape():
    c = optim.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(optim.lr_at(c, jnp.asarray(s))) for s in [0, 5, 10, 50, 100]]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0, rel=0.2)
    assert lrs[4] == pytest.approx(0.1, rel=1e-3)
    assert lrs[3] < lrs[2]


# ---------------------------------------------------------------------------
# fault tolerance / elasticity
# ---------------------------------------------------------------------------


def test_heartbeat_detection():
    from repro.runtime import HeartbeatMonitor, WorkerState

    t = [0.0]
    mon = HeartbeatMonitor(interval_s=1.0, suspect_after=2.0, dead_after=5.0, clock=lambda: t[0])
    for w in ("a", "b"):
        mon.register(w)
    t[0] = 1.5
    mon.beat("a")
    t[0] = 3.0  # b silent for 3s -> suspect
    changed = mon.sweep()
    assert changed == {"b": WorkerState.SUSPECT}
    t[0] = 7.0  # b silent for 7s -> dead; a silent 5.5 -> dead too
    changed = mon.sweep()
    assert changed["b"] is WorkerState.DEAD
    assert "b" in mon.dead()


def test_straggler_detection_and_mitigation():
    from repro.runtime import StragglerMitigator

    m = StragglerMitigator(threshold=1.5, min_samples=3)
    for step in range(5):
        for w in range(8):
            m.observe(f"w{w}", 1.0)
        m.observe("slow", 2.5)
    assert m.stragglers() == ["slow"]
    assert m.mitigation("slow") == "rebalance_input"
    for _ in range(10):
        m.observe("slow", 10.0)
    assert m.mitigation("slow") == "replace"


def test_elastic_remesh_plans():
    from repro.runtime import plan_elastic_remesh

    # full 2 pods
    p = plan_elastic_remesh(512)
    assert p.mesh_shape == (2, 16, 16)
    # lost part of a pod: model axis preserved, data axis takes the survivors
    p = plan_elastic_remesh(300)
    assert p.mesh_shape == (18, 16) and p.dropped_chips == 300 - 288
    # deeper loss: shrink data axis further, keep model axis
    p = plan_elastic_remesh(200)
    assert p.mesh_shape == (12, 16)
    with pytest.raises(ValueError):
        plan_elastic_remesh(8)
