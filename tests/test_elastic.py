"""Elastic remesh planning, worker membership and straggler decisions, and
the planners' reaction to a dead edge pool."""
from __future__ import annotations

import pytest

from repro.runtime import plan_elastic_remesh


def test_remesh_scale_measured_against_one_pod_prior():
    """The scale must be measured against the mesh the cluster actually ran,
    not a hardwired two-pod history: a one-pod cluster losing half its chips
    halves DP, it does not quarter it."""
    plan = plan_elastic_remesh(128, prior_chips=256)
    assert plan.mesh_shape == (8, 16)
    assert plan.data_parallel_scale == pytest.approx(8 / 16)


def test_remesh_default_prior_is_the_two_pod_cluster():
    plan = plan_elastic_remesh(300)
    assert plan.mesh_shape == (18, 16)
    assert plan.data_parallel_scale == pytest.approx(18 / 32)


def test_remesh_scale_against_four_pod_prior():
    plan = plan_elastic_remesh(512, prior_chips=1024)
    assert plan.mesh_shape == (2, 16, 16)
    assert plan.data_parallel_scale == pytest.approx(32 / 64)


def test_remesh_rejects_invalid_prior():
    with pytest.raises(ValueError, match="prior cluster invalid"):
        plan_elastic_remesh(32, prior_chips=8)


def test_mitigation_for_unknown_worker_is_observe():
    """Asking about a worker with no timing data must not KeyError — the
    decision is to gather samples first."""
    from repro.runtime.fault_tolerance import StragglerMitigator

    mit = StragglerMitigator()
    assert mit.mitigation("ghost") == "observe"
    mit.observe("w0", 1.0)
    assert mit.mitigation("ghost") == "observe"  # still unknown
    assert mit.mitigation("w0") in ("rebalance_input", "replace")


def test_register_does_not_resurrect_dead_workers():
    """Re-registering a DEAD worker is a membership no-op: only a real
    heartbeat proves liveness again."""
    from repro.runtime.fault_tolerance import HeartbeatMonitor, WorkerState

    now = 0.0
    mon = HeartbeatMonitor(
        interval_s=1.0, suspect_after=2.0, dead_after=4.0, clock=lambda: now
    )
    mon.register("w")
    now = 10.0
    assert mon.sweep() == {"w": WorkerState.DEAD}
    mon.register("w")  # a restarted host re-announcing itself
    assert mon.workers["w"].state is WorkerState.DEAD
    assert mon.dead() == ["w"]
    mon.beat("w")  # the one legitimate resurrection path
    assert mon.workers["w"].state is WorkerState.HEALTHY
    assert mon.dead() == []


def test_controller_reacts_to_edge_pool_failure():
    """FastVA tie-in: when the edge pool dies (t_server -> inf), the policies
    route everything to the NPU path and keep meeting deadlines."""
    from repro.core import PAPER_MODELS, PAPER_STREAM, Trace, make_policy, simulate
    from repro.core.profiles import ModelProfile

    dead_edge = [
        ModelProfile(m.name, m.t_npu, float("inf"), m.acc_server, m.acc_npu)
        for m in PAPER_MODELS
    ]
    st = simulate(make_policy("max_accuracy"), dead_edge, PAPER_STREAM, Trace.constant(3.0), 60)
    assert st.frames_processed == 60
    assert st.frames_missed_deadline == 0
    # all-local accuracy == the Local baseline's
    st_local = simulate(make_policy("local"), dead_edge, PAPER_STREAM, Trace.constant(3.0), 60)
    assert st.mean_accuracy == pytest.approx(st_local.mean_accuracy, abs=1e-9)
