"""Golden equivalence for the batched online engine (core/sim_online_batch).

The contract under test is the belief-vs-truth split of ``Session.run_online``:
planning sees only the EWMA estimator's belief (seeded from the trace at t=0,
fed back from the uploads the plans actually perform), while execution audits
offload completions against the *true* trace.  The batched engine carries the
estimator state through a jitted while-loop, vmapped over whole grids; these
goldens pin it to the reference loop — integer stats and round counts exactly,
accuracy sums within AUDIT_TOL, and the final believed bandwidth bit-for-bit
(the EWMA chain is guarded against XLA fma/reassociation rewrites).

Also here: the regression tests for the estimator-belief bugfix this engine
was certified against — ``observe_rtt`` must *seed* from the first real RTT
sample instead of blending it into the 0.1 s stub prior.
"""
from __future__ import annotations

import logging

import pytest

from repro.core import PolicySpec
from repro.core.audit import AUDIT_TOL
from repro.core.controller import BandwidthEstimator
from repro.core.registry import available_policies, get_policy
from repro.core.sim_online_batch import (
    OnlineScenario,
    batched_online_policies,
    simulate_online_batch,
)
from repro.scenariogen import edge_failure
from repro.session import FleetSpec, ScenarioSpec, Session, SweepGrid, TraceSpec

# Every batched_online policy with (base params, the param axis swept in the
# golden lattice).  test_registry_flag below fails if a policy registers
# batched_online=True without joining this table.
ONLINE_PARAMS: dict[str, tuple[dict, dict]] = {
    "max_accuracy": ({"grid": 0.01}, {"grid": (0.01, 0.02)}),
    "max_utility": ({"alpha": 200.0}, {"alpha": (50.0, 200.0)}),
}

INT_FIELDS = (
    "frames_processed",
    "frames_missed_deadline",
    "frames_offloaded",
    "frames_total",
    "schedule_calls",
)

# Walking in/out of coverage: 3.5 Mbps for the first second, 0.8 after — the
# estimator starts believing 3.5 and has to learn the collapse from its own
# uploads.
SQUARE = TraceSpec(
    kind="piecewise", points=((0.0, 3.5), (1.0, 0.8)), rtt_ms=100.0
)


def _spec(name: str, params: dict, trace: TraceSpec, n_frames: int) -> ScenarioSpec:
    return ScenarioSpec(
        policy=PolicySpec(name, params), n_frames=n_frames, trace=trace
    )


def _assert_online_equal(ref, bat):
    """ints + rounds exact, accuracy within AUDIT_TOL, belief bit-exact."""
    assert len(ref.points) == len(bat.points)
    for pr, pb in zip(ref.points, bat.points):
        assert pr.overrides == pb.overrides
        (sr,), (sb,) = pr.streams, pb.streams
        for f in INT_FIELDS:
            assert getattr(sr, f) == getattr(sb, f), (pr.overrides, f)
        assert abs(sr.accuracy_sum - sb.accuracy_sum) <= AUDIT_TOL, pr.overrides
        assert pr.meta["rounds"] == pb.meta["rounds"], pr.overrides
        assert pr.meta["estimated_bps"] == pb.meta["estimated_bps"], pr.overrides


def test_registry_flag_matches_online_backend_table():
    flagged = {n for n in available_policies() if get_policy(n).batched_online}
    assert set(batched_online_policies()) == flagged
    assert set(ONLINE_PARAMS) == flagged


@pytest.mark.parametrize("name", sorted(ONLINE_PARAMS))
def test_online_equivalence_square_wave(name):
    """Fast golden: one shape bucket, square-wave trace, both rtts."""
    base, _ = ONLINE_PARAMS[name]
    spec = _spec(name, base, SQUARE, n_frames=45)
    grid = SweepGrid(rtt_ms=(60.0, 100.0))
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    bat = Session(spec).run_sweep(grid, backend="batched", mode="online")
    assert ref.backend == "reference" and bat.backend == "batched"
    assert bat.meta["engine"] == "sim_online_batch"
    assert ref.meta["mode"] == bat.meta["mode"] == "online"
    _assert_online_equal(ref, bat)


@pytest.mark.parametrize("name", sorted(ONLINE_PARAMS))
def test_online_equivalence_fault_injection(name):
    """Golden with an injected mid-run edge failure: the monitor-detected
    outage window collapses the trace to 0.05 Mbps; the controller has to
    discover both the outage and the recovery from its own uploads."""
    base, _ = ONLINE_PARAMS[name]
    outage = edge_failure(
        fail_at_s=2.0, recover_at_s=5.0, duration_s=8.0, base_mbps=3.5
    )
    assert outage.detected_at_s > outage.fail_at_s  # detection lags the crash
    spec = _spec(name, base, outage.trace, n_frames=180)  # 6 s: spans the outage
    grid = SweepGrid(rtt_ms=(60.0, 100.0))
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    bat = Session(spec).run_sweep(grid, backend="batched", mode="online")
    _assert_online_equal(ref, bat)


def test_online_equivalence_dead_link_from_start():
    """A link dead from t=0 seeds the belief at 0 bps: planning must go
    all-local on both engines (no offloads, no misses) and stay equivalent."""
    dead = TraceSpec(kind="constant", mbps=0.0, rtt_ms=100.0)
    spec = _spec("max_accuracy", {"grid": 0.01}, dead, n_frames=45)
    grid = SweepGrid()
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    bat = Session(spec).run_sweep(grid, backend="batched", mode="online")
    _assert_online_equal(ref, bat)
    assert bat.points[0].stats.frames_offloaded == 0
    assert bat.points[0].stats.frames_missed_deadline == 0


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(ONLINE_PARAMS))
def test_online_golden_lattice(name):
    """The certification lattice: deadlines x rtts x a param axis over the
    square-wave trace — multiple shape buckets, 12 points per policy."""
    base, axis = ONLINE_PARAMS[name]
    spec = _spec(name, base, SQUARE, n_frames=90)
    grid = SweepGrid(deadline_ms=(150.0, 200.0, 250.0), rtt_ms=(60.0, 100.0), params=axis)
    assert len(grid) == 12
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    bat = Session(spec).run_sweep(grid, backend="batched", mode="online")
    _assert_online_equal(ref, bat)


def test_online_estimator_converges_on_square_wave():
    """Belief-vs-truth: after the 1 s collapse from 3.5 to 0.8 Mbps, the
    EWMA belief must leave the initial 3.5e6 seed and settle inside the
    trace's band (pessimism keeps the reported state below the raw EWMA)."""
    spec = _spec("max_accuracy", {"grid": 0.01}, SQUARE, n_frames=240)
    rep = Session(spec).run_online()
    est = rep.meta["estimated_bps"]
    assert est < 3.5e6 * 0.9  # moved off the optimistic seed
    assert est > 0.8e6 * 0.5  # did not collapse below the true floor band
    assert rep.meta["rounds"] == rep.streams[0].schedule_calls


def test_optimistic_initial_estimate_surfaces_as_audited_misses():
    """The estimator seeds from the trace at t=0; when the link collapses one
    frame later, the stale optimistic belief keeps planning offloads the true
    link cannot complete — the audit must charge those as deadline misses.
    An honest belief (constant low trace) plans local and misses nothing."""
    collapse = TraceSpec(
        kind="piecewise", points=((0.0, 3.5), (0.01, 0.05)), rtt_ms=100.0
    )
    honest = TraceSpec(kind="constant", mbps=0.05, rtt_ms=100.0)
    opt = Session(_spec("max_accuracy", {"grid": 0.01}, collapse, 60)).run_online()
    hon = Session(_spec("max_accuracy", {"grid": 0.01}, honest, 60)).run_online()
    assert opt.streams[0].frames_missed_deadline > 0
    assert hon.streams[0].frames_missed_deadline == 0
    assert opt.meta["estimated_bps"] < 3.5e6 * 0.9  # the misses taught it


def test_online_engine_init_bps_override_models_stale_belief():
    """OnlineScenario.init_bps decouples the seed from the trace: an
    optimistic stale belief over a slow link must cost misses that an honest
    seed avoids."""
    scen = dict(
        stream=ScenarioSpec(policy=PolicySpec("max_accuracy", {"grid": 0.01})).stream,
        n_frames=60,
        params={"grid": 0.01},
        rtt=0.1,
        bw_segments=((0.0, 0.05e6),),
    )
    models = list(ScenarioSpec(policy=PolicySpec("max_accuracy")).models)
    (st_opt, _), (st_hon, _) = simulate_online_batch(
        "max_accuracy",
        models,
        [
            OnlineScenario(**scen, init_bps=3.5e6),
            OnlineScenario(**scen),  # seeds from the trace: honest
        ],
    )
    assert st_opt.frames_missed_deadline > st_hon.frames_missed_deadline
    assert st_hon.frames_missed_deadline == 0


def test_online_sweep_falls_back_without_batched_online_backend(caplog):
    """jax_accuracy is batched for oracle sweeps but has no online backend:
    forcing backend='batched' warns, records the fallback, and still returns
    reference-loop results."""
    spec = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=30, trace=SQUARE)
    grid = SweepGrid(rtt_ms=(60.0, 100.0))
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep = Session(spec).run_sweep(grid, backend="batched", mode="online")
    assert rep.backend == "reference"
    assert "no batched online backend" in rep.meta["fallback"]
    assert any("falling back" in r.message for r in caplog.records)
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    _assert_online_equal(ref, rep)
    # auto routing makes the same decision silently
    auto = Session(spec).run_sweep(grid, mode="online")
    assert auto.backend == "reference"
    assert "fallback" not in auto.meta


def test_online_sweep_rejects_fleet_grids():
    spec = ScenarioSpec(
        policy=PolicySpec("max_accuracy"), n_frames=30, fleet=FleetSpec(n_clients=2)
    )
    with pytest.raises(ValueError, match="single-stream"):
        Session(spec).run_sweep(SweepGrid(), mode="online")


def test_online_sweep_rejects_track_workload():
    spec = ScenarioSpec(
        policy=PolicySpec("track_fixed", {"k": 3}),
        n_frames=30,
        workload="track",
    )
    with pytest.raises(ValueError, match="tracking workload"):
        Session(spec).run_sweep(SweepGrid(), mode="online")


def test_simulate_online_batch_rejects_unregistered_policy():
    models = list(ScenarioSpec(policy=PolicySpec("max_accuracy")).models)
    with pytest.raises(ValueError, match="batched online"):
        simulate_online_batch("jax_accuracy", models, [])
    assert simulate_online_batch("max_accuracy", models, []) == []


# ---------------------------------------------------------------------------
# Estimator-belief regressions (the bugfix this engine was certified against)
# ---------------------------------------------------------------------------


def test_first_rtt_sample_seeds_the_belief():
    """The 0.1 s default is a stub prior, not a measurement: the first real
    RTT observation must *replace* it, not blend into it."""
    est = BandwidthEstimator()
    assert est.state().rtt == 0.1  # stub prior before any observation
    assert est.rtt_samples == 0
    est.observe_rtt(0.27)
    assert est.state().rtt == 0.27  # seeded exactly, no trace of the prior
    assert est.rtt_samples == 1


def test_later_rtt_samples_blend_by_ewma():
    est = BandwidthEstimator(beta=0.3)
    est.observe_rtt(0.2)
    est.observe_rtt(0.1)
    assert est.state().rtt == pytest.approx(0.7 * 0.2 + 0.3 * 0.1)
    assert est.rtt_samples == 2


@pytest.mark.parametrize("name", sorted(ONLINE_PARAMS))
def test_online_smoke_grid_mobility_square(name):
    """The adaptivity benchmark's smoke cells: 60 frames of the scenario
    generator's mobility square wave, a 10-point deadline x rtt grid inside
    one shape bucket at 30 fps."""
    from repro.scenariogen import make_trace

    base, _ = ONLINE_PARAMS[name]
    spec = _spec(name, base, make_trace("mobility_square"), n_frames=60)
    grid = SweepGrid(deadline_ms=(200.0, 208.0, 216.0, 224.0, 232.0), rtt_ms=(50.0, 80.0))
    ref = Session(spec).run_sweep(grid, backend="reference", mode="online")
    bat = Session(spec).run_sweep(grid, backend="batched", mode="online")
    assert bat.backend == "batched" and bat.meta["engine"] == "sim_online_batch"
    _assert_online_equal(ref, bat)
