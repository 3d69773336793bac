"""Tracking workload class: golden equivalence + contract tests.

The detect+track workload (``core/tracking.py``) couples frames temporally:
a detection's accuracy carries to tracked frames decayed by staleness.  The
contract under test mirrors the classification suite:

  * golden three-path equivalence — the reference ``simulate`` /
    ``simulate_multi`` loops, the batched single-stream engine, and the
    batched fleet engine produce identical audited stats (ints exact,
    accuracy sums within ``AUDIT_TOL`` / ``MULTI_TOL``) for both tracking
    planners, on constant and piecewise traces;
  * the ``run_sweep`` front door routes tracking grids to the batched
    engines and round-trips ``WorkloadSpec`` through JSON;
  * workload/policy gates: classification planners refuse ``kind="track"``
    scenarios and vice versa — at spec time and at the engine boundary;
  * registry error paths for the tracking params (unknown kwarg, ``k < 1``,
    decay outside [0, 1]);
  * hypothesis property: tracked accuracy is monotone non-increasing in
    detector staleness (the table the planners' k-reduction relies on).
"""
from __future__ import annotations

import json

import pytest

from repro.core import PolicySpec
from repro.core.audit import AUDIT_TOL, TrackState, apply_track_round
from repro.core.edge_server import EdgeServerScheduler, make_fleet
from repro.core.profiles import PAPER_MODELS, StreamSpec
from repro.core.sim_batch import BatchScenario, simulate_batch
from repro.core.sim_multi_batch import MULTI_TOL, FleetScenario, simulate_multi_batch
from repro.core.simulator import Trace, simulate, simulate_multi
from repro.core.tracking import WorkloadSpec
from repro.session import FleetSpec, ScenarioSpec, Session, SweepGrid, TraceSpec

INT_FIELDS = (
    "frames_processed",
    "frames_missed_deadline",
    "frames_offloaded",
    "frames_total",
    "schedule_calls",
)

GOLD_FRAMES = 24
MODELS = list(PAPER_MODELS)

# (policy, params) pairs covering both planners; k=3 keeps track_fixed's
# coast-on-stale-state path live at low bandwidth.
PLANNERS = (
    ("track_accuracy", {}),
    ("track_accuracy", {"decay": 0.35, "density": 2.0, "k_max": 4}),
    ("track_fixed", {"k": 3}),
)

# Truth specs decoupled from planner belief (decay 0.0 = lossless tracker,
# 1.0 = tracked frames score zero — both edge rows of the decay table).
WORKLOADS = (
    WorkloadSpec("track"),
    WorkloadSpec("track", decay=0.4, density=2.0),
    WorkloadSpec("track", decay=0.0),
    WorkloadSpec("track", decay=1.0),
)

PIECEWISE = ((0.0, 4.0), (0.25, 0.4), (0.8, 8.0))


def _assert_stats_equal(ref, bat, tol):
    for f in INT_FIELDS:
        assert getattr(ref, f) == getattr(bat, f), f
    assert abs(ref.accuracy_sum - bat.accuracy_sum) <= tol


# ---------------------------------------------------------------------------
# Golden equivalence: reference loop == batched single-stream engine
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy,params", PLANNERS)
def test_batched_matches_reference_single_stream(policy, params):
    spec = PolicySpec(policy, params)
    for wl in WORKLOADS:
        for segs in (((0.0, 6.0),), ((0.0, 0.0),), PIECEWISE):
            trace = Trace.piecewise(list(segs), rtt_ms=60.0)
            ref = simulate(
                spec.build(), MODELS, StreamSpec(), trace, GOLD_FRAMES, workload=wl
            )
            (bat,) = simulate_batch(
                policy,
                MODELS,
                [
                    BatchScenario(
                        n_frames=GOLD_FRAMES,
                        params=spec.resolved,
                        rtt=0.060,
                        bw_segments=tuple((t, v * 1e6) for t, v in segs),
                        workload=wl,
                    )
                ],
            )
            _assert_stats_equal(ref, bat, AUDIT_TOL)
    # non-vacuous: some configuration actually tracks frames
    assert bat.frames_total == GOLD_FRAMES


# ---------------------------------------------------------------------------
# Golden equivalence: reference fleet loop == batched fleet engine
# ---------------------------------------------------------------------------


def test_fleet_batched_matches_simulate_multi_quick():
    """One fleet scenario per planner in the fast lane; the full
    allocation × planner lattice below is slow-marked (CI --runslow)."""
    _assert_fleet_golden("track_accuracy", {}, [("weighted_fair", 2, 2.0)])


@pytest.mark.slow
@pytest.mark.parametrize("policy,params", PLANNERS)
def test_fleet_batched_matches_simulate_multi(policy, params):
    _assert_fleet_golden(
        policy,
        params,
        [
            ("weighted_fair", 3, 2.0),
            ("fifo", 2, 6.0),
            ("priority", 2, 0.8),
        ],
    )


def _assert_fleet_golden(policy, params, cases):
    spec = PolicySpec(policy, params)
    wl = WorkloadSpec("track", decay=0.25)
    for alloc, n_clients, mbps in cases:
        fleet = make_fleet(
            n_clients,
            policy=spec,
            priorities=tuple(range(n_clients)) if alloc == "priority" else None,
        )
        sched = EdgeServerScheduler(fleet, policy=alloc, capacity=2)
        ms_ref = simulate_multi(
            sched, Trace.constant(mbps, rtt_ms=100.0), GOLD_FRAMES, workload=wl
        )
        ((ms_bat, meta),) = simulate_multi_batch(
            policy,
            MODELS,
            [
                FleetScenario(
                    n_frames=GOLD_FRAMES,
                    bandwidth_bps=mbps * 1e6,
                    n_clients=n_clients,
                    allocation=alloc,
                    capacity=2,
                    priorities=(
                        tuple(range(n_clients)) if alloc == "priority" else None
                    ),
                    params=spec.resolved,
                    workload=wl,
                )
            ],
        )
        assert len(ms_bat.per_client) == len(ms_ref.per_client)
        for sr, sb in zip(ms_ref.per_client, ms_bat.per_client):
            _assert_stats_equal(sr, sb, MULTI_TOL)
        assert ms_bat.server_jobs == ms_ref.server_jobs
        assert abs(ms_bat.server_busy_s - ms_ref.server_busy_s) <= MULTI_TOL
        assert meta["grants"] == sched.audit.grants
        assert meta["denials"] == sched.audit.denials


def test_fleet_detections_contend_tracker_frames_do_not():
    """Tracking's fleet economics: only detections touch the shared uplink
    (at most one per k-frame interval), the tracker carries every other
    frame locally — so offloads stay bounded by the detection count while
    the whole stream is still processed."""
    k = 4
    ((ms, meta),) = simulate_multi_batch(
        "track_fixed",
        MODELS,
        [
            FleetScenario(
                n_frames=GOLD_FRAMES,
                bandwidth_bps=20.0e6,
                n_clients=2,
                params={"k": k},
                workload=WorkloadSpec("track"),
            )
        ],
    )
    for s in ms.per_client:
        assert s.frames_offloaded <= -(-GOLD_FRAMES // k)  # detections only
        assert s.frames_processed + s.frames_missed_deadline == GOLD_FRAMES
    # the shared link saw exactly the offloaded detections, nothing else
    assert ms.server_jobs == sum(s.frames_offloaded for s in ms.per_client)
    assert ms.server_jobs > 0  # non-vacuous: the link is actually used


# ---------------------------------------------------------------------------
# Front door: run_sweep routing + JSON round-trip
# ---------------------------------------------------------------------------


def _track_spec(fleet=None):
    return ScenarioSpec(
        policy=PolicySpec("track_accuracy", {"k_max": 5}),
        n_frames=GOLD_FRAMES,
        trace=TraceSpec(mbps=2.5, rtt_ms=80.0),
        workload=WorkloadSpec("track", decay=0.2, density=1.5),
        fleet=fleet,
    )


def test_run_sweep_tracking_batched_matches_reference():
    grid = SweepGrid(bandwidth_mbps=(0.5, 3.0, 9.0), deadline_ms=(100.0, 200.0))
    for fleet, engine in (
        (None, "sim_batch"),
        (FleetSpec(n_clients=2, capacity=2), "sim_multi_batch"),
    ):
        session = Session(_track_spec(fleet))
        ref = session.run_sweep(grid, backend="reference")
        bat = session.run_sweep(grid, backend="batched")
        assert bat.backend == "batched" and bat.meta["engine"] == engine
        assert len(ref.points) == len(bat.points) == 6
        for pr, pb in zip(ref.points, bat.points):
            assert pr.overrides == pb.overrides
            for sr, sb in zip(pr.streams, pb.streams):
                _assert_stats_equal(sr, sb, MULTI_TOL)
        assert any(s.frames_processed > 0 for p in bat.points for s in p.streams)


def test_scenario_spec_workload_json_round_trip():
    spec = _track_spec(fleet=FleetSpec(n_clients=2))
    rt = ScenarioSpec.from_json(json.dumps(spec.to_json()))
    assert rt == spec
    assert rt.workload == WorkloadSpec("track", decay=0.2, density=1.5)
    # a classify spec omits the default workload from its payload
    classify = ScenarioSpec(policy=PolicySpec("local"))
    assert "workload" not in classify.to_json()
    assert ScenarioSpec.from_json(json.dumps(classify.to_json())) == classify


def test_workload_spec_round_trip_and_coercion():
    wl = WorkloadSpec("track", decay=0.3, density=2.0)
    assert WorkloadSpec.from_json(wl.to_json()) == wl
    # ScenarioSpec coerces strings and mappings into WorkloadSpec
    s = ScenarioSpec(policy=PolicySpec("track_accuracy"), workload="track")
    assert s.workload == WorkloadSpec("track")
    s = ScenarioSpec(
        policy=PolicySpec("track_accuracy"), workload={"kind": "track", "decay": 0.5}
    )
    assert s.workload.decay == 0.5


# ---------------------------------------------------------------------------
# Gates: workload kind vs. policy capability
# ---------------------------------------------------------------------------


def test_workload_policy_gate_at_spec_time():
    with pytest.raises(ValueError, match="plans classify workloads, not 'track'"):
        ScenarioSpec(policy=PolicySpec("max_accuracy"), workload="track")
    with pytest.raises(ValueError, match="plans track workloads, not 'classify'"):
        ScenarioSpec(policy=PolicySpec("track_accuracy"))


def test_workload_policy_gate_at_engine_boundary():
    with pytest.raises(ValueError, match="plans classify workloads, not 'track'"):
        simulate_batch(
            "max_accuracy", MODELS, [BatchScenario(workload=WorkloadSpec("track"))]
        )
    with pytest.raises(ValueError, match="plans track workloads, not 'classify'"):
        simulate_multi_batch("track_accuracy", MODELS, [FleetScenario()])


def test_online_and_serving_reject_tracking():
    spec = _track_spec()
    with pytest.raises(ValueError, match="tracking workload"):
        Session(spec).run_online()
    with pytest.raises(ValueError, match="tracking workload"):
        Session(spec).run_serving()


# ---------------------------------------------------------------------------
# Validation: WorkloadSpec fields + registry param schemas
# ---------------------------------------------------------------------------


def test_workload_spec_validation_errors():
    with pytest.raises(ValueError, match="unknown workload kind"):
        WorkloadSpec("segment")
    with pytest.raises(ValueError, match="decay must be a number"):
        WorkloadSpec("track", decay=-0.1)
    with pytest.raises(ValueError, match="decay must be a number"):
        WorkloadSpec("track", decay=1.5)
    with pytest.raises(ValueError, match="density must be a number"):
        WorkloadSpec("track", density=-1.0)
    with pytest.raises(ValueError, match="not a WorkloadSpec payload"):
        WorkloadSpec.from_json({"decay": 0.2})


def test_registry_rejects_bad_tracking_params():
    with pytest.raises(ValueError, match="accepts no parameter"):
        PolicySpec("track_accuracy", {"interval": 3})
    with pytest.raises(ValueError, match="requires parameter 'k'"):
        PolicySpec("track_fixed")
    with pytest.raises(ValueError, match="must be in \\[1, \\+inf\\]"):
        PolicySpec("track_fixed", {"k": 0})
    with pytest.raises(ValueError, match="must be in \\[1, \\+inf\\]"):
        PolicySpec("track_accuracy", {"k_max": 0})
    with pytest.raises(ValueError, match="must be in \\[0.0, 1.0\\]"):
        PolicySpec("track_accuracy", {"decay": 1.5})
    with pytest.raises(ValueError, match="must be in \\[0.0, 1.0\\]"):
        PolicySpec("track_accuracy", {"decay": -0.1})
    with pytest.raises(ValueError, match="must be in \\[0.0, \\+inf\\]"):
        PolicySpec("track_accuracy", {"density": -2.0})
    with pytest.raises(ValueError, match="expects int"):
        PolicySpec("track_fixed", {"k": 2.5})


def test_track_state_carries_across_rounds():
    """The audit contract's tracking extension: ``apply_track_round`` hands
    back the state a later round needs to score stale frames — a SKIP round
    coasts on the previous detection, decayed per frame of staleness."""
    from repro.core.schedule import Decision, RoundPlan, StreamStats, Where

    wl = WorkloadSpec("track", decay=0.2)
    stream = StreamSpec()
    plan = PolicySpec("track_fixed", {"k": 3}).build()(
        MODELS, stream, Trace.constant(6.0).at(0.0)
    )
    stats = StreamStats()
    state = apply_track_round(
        stats, plan, models=MODELS, stream=stream, state=TrackState(),
        head=0, n_frames=12, horizon=plan.horizon, bad_frames=set(),
        retention=wl.retention,
    )
    assert state.det_frame == 0 and state.det_acc > 0
    assert stats.frames_processed == 3  # detection + 2 tracker-carried frames
    # coast one frame on a SKIP round: score = the detection decayed by age 3
    skip = RoundPlan(decisions=[Decision(0, Where.SKIP)], horizon=1)
    stats2 = StreamStats()
    state2 = apply_track_round(
        stats2, skip, models=MODELS, stream=stream, state=state,
        head=3, n_frames=12, horizon=1, bad_frames=set(),
        retention=wl.retention,
    )
    assert state2 == state
    assert stats2.accuracy_sum == pytest.approx(
        state.det_acc * wl.retention**3, abs=0
    )


# ---------------------------------------------------------------------------
# The tracking benchmark's smoke cells: a 10-point deadline x rtt grid over a
# piecewise trace at 120 frames, both planners single-stream and
# track_accuracy on a 3-client fleet (2 server slots); ints exact, accuracy
# within MULTI_TOL.
# ---------------------------------------------------------------------------

SMOKE_WORKLOAD = WorkloadSpec("track", decay=0.2, density=1.0)
SMOKE_TRACE = TraceSpec(kind="piecewise", points=((0.0, 3.0), (0.3, 0.8), (0.9, 6.0)),
                        rtt_ms=60.0)
SMOKE_CELLS = {
    "track_accuracy": ("track_accuracy", {"decay": 0.2, "k_max": 6}, None),
    "track_fixed": ("track_fixed", {"k": 3}, None),
    "track_accuracy_fleet": ("track_accuracy", {"decay": 0.2, "k_max": 6},
                             FleetSpec(n_clients=3, capacity=2)),
}


@pytest.mark.parametrize("cell", list(SMOKE_CELLS))
def test_smoke_grid_batched_matches_reference(cell):
    policy, params, fleet = SMOKE_CELLS[cell]
    grid = SweepGrid(deadline_ms=(150.0, 200.0, 250.0, 300.0, 350.0), rtt_ms=(50.0, 100.0))
    session = Session(
        ScenarioSpec(policy=PolicySpec(policy, params), n_frames=120, trace=SMOKE_TRACE,
                     workload=SMOKE_WORKLOAD, fleet=fleet)
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert bat.backend == "batched"
    assert len(ref.points) == len(bat.points) == 10
    for pr, pb in zip(ref.points, bat.points):
        assert pr.overrides == pb.overrides
        assert len(pr.streams) == len(pb.streams) == (3 if fleet else 1)
        for sr, sb in zip(pr.streams, pb.streams):
            _assert_stats_equal(sr, sb, MULTI_TOL)
