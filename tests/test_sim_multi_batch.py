"""Golden equivalence for the vectorized multi-stream fleet backend.

The contract under test (docs/simulation.md, "Multi-stream fleet grids"):
for every policy with a fleet planner in ``core/sim_multi_batch``,
``Session.run_sweep`` on a fleet grid reproduces the reference
``simulate_multi`` event loop's audited stats — integer stats (frames
processed / offloaded / missed, server jobs, scheduler grants/denials)
**exactly**, float stats within the certified ``MULTI_TOL``.  Plus:
registry-flag <-> planner-table sync, the logged fallback for Python-only
policies and non-constant traces, and the structured ``PlanError`` audit
path of ``simulate_multi`` itself.
"""
from __future__ import annotations

import logging

import pytest

from repro.core import (
    EdgeServerScheduler,
    PolicySpec,
    Trace,
    make_fleet,
    simulate_multi,
)
from repro.core.registry import available_policies, get_policy
from repro.core.schedule import Decision, RoundPlan, Where, validate_plan
from repro.core.sim_multi_batch import (
    EQUIV_INT_FIELDS,
    MULTI_TOL,
    FleetScenario,
    multi_batched_policies,
    simulate_multi_batch,
)
from repro.session import FleetSpec, ScenarioSpec, Session, SweepGrid, TraceSpec

GOLD_FRAMES = 16


def _fleet_session(policy="offload", params=None, **fleet_kw):
    fleet_kw.setdefault("capacity", 2)
    return Session(
        ScenarioSpec(
            policy=PolicySpec(policy, params or {}),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(mbps=6.0),
            fleet=FleetSpec(**fleet_kw),
        )
    )


def _assert_fleet_reports_equal(ref, bat):
    assert len(ref.points) == len(bat.points)
    for pr, pb in zip(ref.points, bat.points):
        assert pr.overrides == pb.overrides
        assert len(pr.streams) == len(pb.streams), pr.overrides
        for sr, sb in zip(pr.streams, pb.streams):
            for f in EQUIV_INT_FIELDS:
                assert getattr(sr, f) == getattr(sb, f), (pr.overrides, f)
            assert abs(sr.accuracy_sum - sb.accuracy_sum) <= MULTI_TOL, pr.overrides
            assert sr.elapsed == sb.elapsed
        for key in ("allocation", "server_jobs", "grants", "denials"):
            assert pr.meta[key] == pb.meta[key], (pr.overrides, key)
        assert (
            abs(pr.meta["server_utilization"] - pb.meta["server_utilization"])
            <= MULTI_TOL
        ), pr.overrides


# ---------------------------------------------------------------------------
# Registry <-> backend sync
# ---------------------------------------------------------------------------


def test_registry_flag_matches_fleet_planner_table():
    flagged = {n for n in available_policies() if get_policy(n).batched_multi}
    planners = set(multi_batched_policies())
    # Every batched_multi policy has a dedicated fleet planner and vice
    # versa — no replication shortcuts left in the table.
    assert planners == flagged


def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="no batched fleet backend"):
        simulate_multi_batch("local", [], [FleetScenario()])


# ---------------------------------------------------------------------------
# Golden equivalence: batched fleet == simulate_multi
# ---------------------------------------------------------------------------


def test_fleet_grid_matches_reference_small():
    """Fast lane: one allocation pair, one fleet size, contention included
    (6 Mbps across 2 clients forces denials + stretched fifo uploads)."""
    session = _fleet_session()
    grid = SweepGrid(
        bandwidth_mbps=(2.5, 6.0, 12.0),
        n_clients=(2,),
        allocation=("weighted_fair", "fifo"),
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert ref.backend == "reference" and bat.backend == "batched"
    assert bat.meta["engine"] == "sim_multi_batch"
    _assert_fleet_reports_equal(ref, bat)


@pytest.mark.slow
@pytest.mark.parametrize(
    "params", [{}, {"alpha": 150.0}], ids=["accuracy-mode", "utility-mode"]
)
def test_fleet_grid_matches_reference_full(params):
    """The full golden lattice: every allocation policy, mixed fleet sizes,
    deadlines tight enough to force completion-audit misses."""
    session = _fleet_session(params=params)
    grid = SweepGrid(
        bandwidth_mbps=(1.0, 4.0, 9.0),
        deadline_ms=(150.0, 250.0),
        n_clients=(1, 2, 4),
        allocation=("weighted_fair", "priority", "fifo"),
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    _assert_fleet_reports_equal(ref, bat)


@pytest.mark.slow
def test_fleet_grid_matches_reference_weights_priorities():
    session = Session(
        ScenarioSpec(
            policy=PolicySpec("offload"),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(mbps=9.0),
            fleet=FleetSpec(
                n_clients=4,
                allocation="priority",
                capacity=1,
                weights=(3.0, 1.0, 1.0, 0.5),
                priorities=(0, 0, 2, 2),
            ),
        )
    )
    grid = SweepGrid(bandwidth_mbps=(4.0, 9.0), deadline_ms=(175.0, 200.0))
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    _assert_fleet_reports_equal(ref, bat)


def test_direct_backend_call_matches_simulate_multi():
    """One scenario through the raw module API (no Session), asserting the
    MultiStreamStats shape and the scheduler-audit meta."""
    fleet = make_fleet(2, policy=PolicySpec("offload"))
    sched = EdgeServerScheduler(fleet, policy="weighted_fair", capacity=2)
    ms_ref = simulate_multi(sched, Trace.constant(6.0), GOLD_FRAMES)
    (ms_bat, meta), = simulate_multi_batch(
        "offload",
        list(fleet[0].models),
        [
            FleetScenario(
                n_frames=GOLD_FRAMES,
                bandwidth_bps=6.0e6,
                n_clients=2,
                allocation="weighted_fair",
                capacity=2,
            )
        ],
    )
    assert ms_bat.server_jobs == ms_ref.server_jobs
    assert abs(ms_bat.server_busy_s - ms_ref.server_busy_s) <= MULTI_TOL
    assert abs(ms_bat.aggregate_accuracy - ms_ref.aggregate_accuracy) <= MULTI_TOL
    assert ms_bat.miss_rates == ms_ref.miss_rates
    assert meta == {"grants": sched.audit.grants, "denials": sched.audit.denials}


def test_aggregate_accuracy_consistent_with_per_client_stats():
    """MultiStreamStats.aggregate_accuracy must be derivable from the
    audited per-client stats on both backends (the fleet mean over all
    frames, missed = 0) — no hidden accounting."""
    for ms in (
        simulate_multi(
            EdgeServerScheduler(make_fleet(3, policy="offload"), policy="fifo"),
            Trace.constant(4.0),
            GOLD_FRAMES,
        ),
        simulate_multi_batch(
            "offload",
            list(make_fleet(1)[0].models),
            [FleetScenario(n_frames=GOLD_FRAMES, bandwidth_bps=4.0e6,
                           n_clients=3, allocation="fifo")],
        )[0][0],
    ):
        total = sum(s.frames_total for s in ms.per_client)
        acc = sum(s.accuracy_sum for s in ms.per_client)
        assert ms.aggregate_accuracy == pytest.approx(acc / total, abs=0)
        for s in ms.per_client:
            # offload rounds are horizon-1: every frame is processed,
            # missed, or skipped — never double-counted.
            assert s.frames_processed + s.frames_missed_deadline <= s.frames_total
            assert s.frames_offloaded == s.frames_processed


# ---------------------------------------------------------------------------
# Golden fleet lattices for the DP planners (newly batched_multi in this PR):
# per-client planning over granted bandwidth + shared-link contention must
# reproduce the reference event loop — ints exact, accuracy within MULTI_TOL.
# ---------------------------------------------------------------------------

PLANNERS = [
    ("max_accuracy", {}),
    ("max_utility", {"alpha": 150.0}),
    ("jax_accuracy", {}),
    ("jax_utility", {"alpha": 150.0}),
]
PLANNER_IDS = [p for p, _ in PLANNERS]


@pytest.mark.parametrize("policy,params", PLANNERS, ids=PLANNER_IDS)
def test_planner_fleet_grid_matches_reference_small(policy, params):
    """Fast lane: every planner, shared 6 Mbps link across 2 clients,
    weighted_fair (denials at capacity) + fifo (uncapped reservations)."""
    session = _fleet_session(policy=policy, params=params)
    grid = SweepGrid(n_clients=(2,), allocation=("weighted_fair", "fifo"))
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert bat.backend == "batched" and bat.meta["engine"] == "sim_multi_batch"
    _assert_fleet_reports_equal(ref, bat)


@pytest.mark.slow
@pytest.mark.parametrize("policy,params", PLANNERS, ids=PLANNER_IDS)
def test_planner_fleet_golden_lattice_constant(policy, params):
    """The full constant-trace lattice: every allocation policy, mixed fleet
    sizes, bandwidths spanning starved to comfortable."""
    session = _fleet_session(policy=policy, params=params)
    grid = SweepGrid(
        bandwidth_mbps=(1.0, 4.0, 9.0),
        n_clients=(1, 2, 4),
        allocation=("weighted_fair", "priority", "fifo"),
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    _assert_fleet_reports_equal(ref, bat)


@pytest.mark.slow
@pytest.mark.parametrize("policy,params", PLANNERS, ids=PLANNER_IDS)
def test_planner_fleet_golden_lattice_piecewise(policy, params):
    """Piecewise shared link: uploads granted at 6 Mbps drain into a
    1.5 Mbps trough; the fluid rates re-evaluate at every event boundary."""
    session = Session(
        ScenarioSpec(
            policy=PolicySpec(policy, params),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(
                kind="piecewise", points=((0.0, 6.0), (0.2, 1.5), (0.35, 9.0))
            ),
            fleet=FleetSpec(n_clients=2, capacity=2),
        )
    )
    grid = SweepGrid(
        n_clients=(1, 3), allocation=("weighted_fair", "priority", "fifo")
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert bat.meta["engine"] == "sim_multi_batch"
    _assert_fleet_reports_equal(ref, bat)


@pytest.mark.slow
@pytest.mark.parametrize("policy,params", PLANNERS, ids=PLANNER_IDS)
def test_planner_fleet_capacity_zero_and_backlog_gated(policy, params):
    """Admission edge cases: capacity 0 denies every lease (plans must fall
    back to local-only rounds) and a tight backlog limit on a starved link
    shuts the allocation gate mid-run."""
    cap0 = _fleet_session(policy=policy, params=params, capacity=0)
    grid0 = SweepGrid(n_clients=(2,), allocation=("weighted_fair", "fifo"))
    _assert_fleet_reports_equal(
        cap0.run_sweep(grid0, backend="reference"),
        cap0.run_sweep(grid0, backend="batched"),
    )
    gated = Session(
        ScenarioSpec(
            policy=PolicySpec(policy, params),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(mbps=1.0),
            fleet=FleetSpec(n_clients=3, capacity=2, backlog_limit=0.05),
        )
    )
    gridb = SweepGrid(allocation=("weighted_fair",))
    _assert_fleet_reports_equal(
        gated.run_sweep(gridb, backend="reference"),
        gated.run_sweep(gridb, backend="batched"),
    )


@pytest.mark.slow
@pytest.mark.parametrize("policy,params", PLANNERS, ids=PLANNER_IDS)
def test_planner_fleet_weights_priorities(policy, params):
    """Non-uniform weights + priority tiers: effective-weight shares,
    priority reservations and the intra-tick plan order all bite."""
    session = Session(
        ScenarioSpec(
            policy=PolicySpec(policy, params),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(mbps=9.0),
            fleet=FleetSpec(
                n_clients=4,
                allocation="priority",
                capacity=1,
                weights=(3.0, 1.0, 1.0, 0.5),
                priorities=(0, 0, 2, 2),
            ),
        )
    )
    grid = SweepGrid(bandwidth_mbps=(4.0, 9.0))
    _assert_fleet_reports_equal(
        session.run_sweep(grid, backend="reference"),
        session.run_sweep(grid, backend="batched"),
    )


# ---------------------------------------------------------------------------
# Fallback routing
# ---------------------------------------------------------------------------


def test_offloading_fleet_grid_routes_batched_without_warning(caplog):
    """Regression for the retired PR 5 fallback: fleet grids of the
    offloading planners used to log "no batched fleet backend" and run the
    reference loop.  They now route through the dedicated fleet planner in
    ``sim_multi_batch`` with no fallback warning and no ``fallback`` meta."""
    for policy, params in (("max_accuracy", {}), ("max_utility", {"alpha": 150.0})):
        session = _fleet_session(policy=policy, params=params)
        grid = SweepGrid(bandwidth_mbps=(6.0,), n_clients=(2,))
        with caplog.at_level(logging.WARNING, logger="repro.session"):
            report = session.run_sweep(grid, backend="batched")
        assert report.backend == "batched"
        assert report.meta["engine"] == "sim_multi_batch"
        assert "fallback" not in report.meta
        assert not any("falling back" in r.message for r in caplog.records)
        caplog.clear()


def test_python_only_fleet_grid_warns_and_falls_back(caplog):
    """The genuine fallback still exists: a policy with no vectorized fleet
    backend at all (``local``) logs the documented warning and runs the
    reference loop."""
    session = _fleet_session(policy="local")
    grid = SweepGrid(bandwidth_mbps=(6.0,), n_clients=(2,))
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        report = session.run_sweep(grid, backend="batched")
    assert report.backend == "reference"
    assert "no batched fleet backend" in report.meta["fallback"]
    assert any("falling back" in r.message for r in caplog.records)
    # auto mode falls back silently (it never promised a batched engine).
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        auto = session.run_sweep(grid)
    assert auto.backend == "reference" and not caplog.records


def test_piecewise_trace_fleet_grid_matches_reference():
    """Time-varying shared link: the fleet engine replays the piecewise
    trace on device (allocation at round start, fluid rates at every event
    boundary) and must match the reference event loop — this used to be a
    fallback case."""
    session = Session(
        ScenarioSpec(
            policy=PolicySpec("offload"),
            n_frames=GOLD_FRAMES,
            trace=TraceSpec(
                kind="piecewise", points=((0.0, 6.0), (0.2, 1.5), (0.35, 9.0))
            ),
            fleet=FleetSpec(n_clients=2, capacity=2),
        )
    )
    grid = SweepGrid(
        n_clients=(1, 2, 3), allocation=("weighted_fair", "priority", "fifo")
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert bat.backend == "batched" and bat.meta["engine"] == "sim_multi_batch"
    _assert_fleet_reports_equal(ref, bat)
    # the varying trace really bites: some uploads started at 6 Mbps finish
    # into the 1.5 Mbps trough and miss their deadlines.
    assert any(p.max_miss_rate > 0 for p in bat.points)


def test_direct_fleet_scenario_piecewise_segments():
    """FleetScenario.bw_segments drives the engine directly (no Session):
    equivalence against simulate_multi over the same Trace.piecewise."""
    pts = ((0.0, 5.0), (0.25, 1.0))
    fleet = make_fleet(2, policy=PolicySpec("offload"))
    sched = EdgeServerScheduler(fleet, policy="weighted_fair", capacity=2)
    ms_ref = simulate_multi(sched, Trace.piecewise(list(pts)), GOLD_FRAMES)
    (ms_bat, _), = simulate_multi_batch(
        "offload",
        list(fleet[0].models),
        [
            FleetScenario(
                n_frames=GOLD_FRAMES,
                bw_segments=tuple((t, v * 1e6) for t, v in pts),
                n_clients=2,
                allocation="weighted_fair",
                capacity=2,
            )
        ],
    )
    assert ms_bat.server_jobs == ms_ref.server_jobs
    assert ms_bat.miss_rates == ms_ref.miss_rates
    assert abs(ms_bat.aggregate_accuracy - ms_ref.aggregate_accuracy) <= MULTI_TOL


# ---------------------------------------------------------------------------
# simulate_multi audit/error paths: structured PlanError, not string parsing
# ---------------------------------------------------------------------------


def test_simulate_multi_audits_bad_plans_through_structured_errors():
    """A policy that plans an NPU decision past its deadline: the audit must
    flag it through ``PlanError.frame`` (simulate_multi consumes the
    structured field, never the message text) and count every round's bad
    frame as missed without crediting accuracy."""
    fleet = make_fleet(1, policy="local")
    stream = fleet[0].stream
    bad_plan = RoundPlan(
        decisions=[
            Decision(0, Where.NPU, 0, stream.r_max, start=0.0, finish=stream.deadline + 1.0)
        ],
        horizon=1,
        npu_busy_until=0.0,
    )
    # The structured surface itself: typed frame ids plus readable text.
    errors = validate_plan(bad_plan, gamma=stream.gamma, deadline=stream.deadline)
    assert errors, "deadline overrun must produce PlanErrors"
    assert {e.frame for e in errors} == {0}
    assert all(isinstance(e.frame, int) for e in errors)
    assert "deadline" in str(errors[0])

    fleet[0]._policy = lambda models, stream, net, npu_free=0.0: bad_plan
    sched = EdgeServerScheduler(fleet, policy="weighted_fair", capacity=2)
    ms = simulate_multi(sched, Trace.constant(6.0), 5)
    s = ms.per_client[0]
    assert s.frames_missed_deadline == 5
    assert s.frames_processed == 0
    assert s.accuracy_sum == 0.0
    # Non-strict mode skips plan validation: the bad plan is taken at face
    # value and credited (defence-in-depth is opt-out, but explicit).
    sched2 = EdgeServerScheduler(
        make_fleet(1, policy="local"), policy="weighted_fair", capacity=2
    )
    sched2.clients[0]._policy = lambda models, stream, net, npu_free=0.0: bad_plan
    ms2 = simulate_multi(sched2, Trace.constant(6.0), 5, strict=False)
    assert ms2.per_client[0].frames_missed_deadline == 0
    assert ms2.per_client[0].frames_processed == 5


# ---------------------------------------------------------------------------
# The fleet benchmark's smoke ladders at 10 points: a bandwidth x
# allocation grid of interacting fleets on a 6 Mbps link with 4 server
# slots.  Offload runs 30 frames over fleets of 4; the max_accuracy DP
# runs 12 frames on a 10 ms grid over fleets of 2.
# ---------------------------------------------------------------------------

LADDERS = {
    "offload": ({}, 30, 4),
    "max_accuracy": ({"grid": 10e-3}, 12, 2),
}


@pytest.mark.parametrize("policy", sorted(LADDERS))
def test_ladder_grid_batched_matches_reference(policy):
    params, frames, n_clients = LADDERS[policy]
    grid = SweepGrid(bandwidth_mbps=(2.0, 4.0, 6.0, 9.0, 12.0), n_clients=(n_clients,),
                     allocation=("weighted_fair", "fifo"))
    session = Session(
        ScenarioSpec(
            policy=PolicySpec(policy, params),
            n_frames=frames,
            trace=TraceSpec(mbps=6.0),
            fleet=FleetSpec(capacity=4),
        )
    )
    ref = session.run_sweep(grid, backend="reference")
    bat = session.run_sweep(grid, backend="batched")
    assert bat.backend == "batched" and bat.meta["engine"] == "sim_multi_batch"
    _assert_fleet_reports_equal(ref, bat)
