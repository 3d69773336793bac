"""Golden equivalence for the vectorized sweep backend.

The contract under test (docs/simulation.md): for every registered
``batched=True`` policy, ``Session.run_sweep(grid, backend="batched")``
reproduces the reference simulator's audited per-scenario stats across a
>= 100-point grid that exercises window padding (mixed fps), bin padding
(mixed deadlines/grids), the infeasible horizon-1 path (deadline below
every NPU latency), and policy-param axes.  The jax_* planners are
**bit-identical** (same f32 kernels); the network-aware ``max_accuracy`` /
``max_utility`` planners replay float64 Python references, so their
certified contract is integer stats exact + accuracy sums within
``AUDIT_TOL`` — on constant AND piecewise traces.  Plus: registry flag <->
planner table sync, fleet-axis replication vs the real ``run_multi``,
fallback routing (incl. fleet grids of offload-capable batched policies),
the piecewise-base trace-override warning, and the sweep CLI.
"""
from __future__ import annotations

import json
import logging

import pytest

from repro.core import PolicySpec
from repro.core.audit import AUDIT_TOL
from repro.core.registry import available_policies, get_policy
from repro.core.sim_batch import batched_policies, simulate_batch
from repro.session import (
    FleetSpec,
    ScenarioSpec,
    Session,
    SweepGrid,
    SweepReport,
    TraceSpec,
)

# Every batched policy with (base params, the param axis swept in the golden
# grid).  test_registry_flag below fails if a policy registers batched=True
# without joining this table — new backends must enter the golden sweep.
BATCHED_PARAMS: dict[str, tuple[dict, dict]] = {
    "jax_accuracy": ({}, {"grid": (1e-3, 2e-3)}),
    "jax_utility": ({"alpha": 200.0}, {"alpha": (50.0, 200.0)}),
    "max_accuracy": ({}, {"grid": (1e-3, 2e-3)}),
    "max_utility": ({"alpha": 200.0}, {"alpha": (50.0, 200.0)}),
}

# The network-aware planners replay float64 Python DPs: integer stats must
# match exactly, accuracy sums within AUDIT_TOL (the jax_* planners stay
# bit-identical — tolerance 0).
NET_POLICIES = frozenset({"max_accuracy", "max_utility"})

INT_FIELDS = (
    "frames_processed",
    "frames_missed_deadline",
    "frames_offloaded",
    "frames_total",
    "schedule_calls",
)
STATS_FIELDS = ("accuracy_sum",) + INT_FIELDS

GOLD_FRAMES = 24

PIECEWISE = TraceSpec(
    kind="piecewise", points=((0.0, 3.0), (0.3, 0.8), (0.9, 6.0)), rtt_ms=60.0
)


def _golden_grid(param_axis: dict) -> SweepGrid:
    # 2 x 5 x 5 x 2 = 100 points; deadline 10 ms < min t_npu (17 ms) forces
    # the infeasible skip-all rounds, mixed fps forces window padding.
    return SweepGrid(
        bandwidth_mbps=(1.0, 2.5),
        deadline_ms=(10.0, 100.0, 150.0, 200.0, 350.0),
        fps=(10.0, 24.0, 30.0, 50.0, 60.0),
        params=param_axis,
    )


def _assert_points_equal(ref, bat, acc_tol: float = 0.0):
    assert len(ref.points) == len(bat.points)
    for pr, pb in zip(ref.points, bat.points):
        assert pr.overrides == pb.overrides
        assert len(pr.streams) == len(pb.streams)
        for sr, sb in zip(pr.streams, pb.streams):
            for f in INT_FIELDS:
                assert getattr(sr, f) == getattr(sb, f), (pr.overrides, f)
            assert abs(sr.accuracy_sum - sb.accuracy_sum) <= acc_tol, pr.overrides


def _acc_tol(name: str) -> float:
    return AUDIT_TOL if name in NET_POLICIES else 0.0


# Detect+track planners are batched too, but plan a different workload
# kind; their golden grids live in tests/test_tracking.py.
TRACK_POLICIES = frozenset(
    n for n in available_policies() if get_policy(n).workloads == ("track",)
)


def test_registry_flag_matches_backend_table():
    flagged = {n for n in available_policies() if get_policy(n).batched}
    assert set(batched_policies()) == flagged
    # new batched classify policies join this sweep; track ones join
    # test_tracking.py's (TRACK_POLICIES is derived, so neither can hide)
    assert set(BATCHED_PARAMS) | TRACK_POLICIES == flagged
    assert not (set(BATCHED_PARAMS) & TRACK_POLICIES)


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(BATCHED_PARAMS))
def test_batched_backend_matches_reference_exactly(name):
    base_params, axis = BATCHED_PARAMS[name]
    grid = _golden_grid(axis)
    assert len(grid) >= 100
    spec = ScenarioSpec(policy=PolicySpec(name, base_params), n_frames=GOLD_FRAMES)
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    assert ref.backend == "reference" and bat.backend == "batched"
    assert len(bat.points) == len(grid)
    _assert_points_equal(ref, bat, acc_tol=_acc_tol(name))


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(NET_POLICIES))
def test_network_planners_match_reference_on_piecewise_traces(name):
    """The paper's planners under a time-varying trace: bandwidth steps
    across segment boundaries mid-stream, an rtt axis varies the offload
    budget, and a 10 ms deadline forces the skip path — the batched
    stats must still match the reference loop point for point."""
    base_params, _ = BATCHED_PARAMS[name]
    grid = SweepGrid(
        deadline_ms=(10.0, 150.0, 200.0, 350.0),
        fps=(10.0, 30.0, 60.0),
        rtt_ms=(40.0, 100.0),
    )
    spec = ScenarioSpec(
        policy=PolicySpec(name, base_params), n_frames=36, trace=PIECEWISE
    )
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    assert bat.backend == "batched"
    _assert_points_equal(ref, bat, acc_tol=AUDIT_TOL)


@pytest.mark.parametrize("name", sorted(NET_POLICIES))
def test_network_planners_small_constant_and_piecewise(name):
    """Fast-lane cousin of the slow goldens: a handful of points on both
    trace kinds, asserting the same equivalence contract."""
    base_params, _ = BATCHED_PARAMS[name]
    for trace in (TraceSpec(mbps=2.5), PIECEWISE):
        spec = ScenarioSpec(
            policy=PolicySpec(name, base_params), n_frames=16, trace=trace
        )
        grid = SweepGrid(deadline_ms=(150.0, 250.0), fps=(30.0,))
        ref = Session(spec).run_sweep(grid, backend="reference")
        bat = Session(spec).run_sweep(grid, backend="batched")
        assert bat.backend == "batched"
        _assert_points_equal(ref, bat, acc_tol=AUDIT_TOL)
        # the planners really do offload under a healthy network
        if trace.kind == "constant":
            assert any(p.stats.frames_offloaded > 0 for p in bat.points)


def test_infeasible_deadline_is_skip_not_miss():
    """Deadline below every NPU latency: the reference emits horizon-1 SKIP
    rounds (no processing, no deadline misses, one schedule call per frame);
    the batched backend must reproduce that path, not approximate it."""
    spec = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=12)
    rep = Session(spec).run_sweep(SweepGrid(deadline_ms=(10.0,)), backend="batched")
    st = rep.points[0].stats
    assert st.frames_processed == 0
    assert st.frames_missed_deadline == 0
    assert st.schedule_calls == 12  # one skip round per frame


def test_fleet_axis_replication_matches_run_multi():
    grid = SweepGrid(n_clients=(1, 3))
    spec = ScenarioSpec(
        policy=PolicySpec("jax_utility", {"alpha": 200.0}),
        n_frames=GOLD_FRAMES,
        fleet=FleetSpec(capacity=2),
    )
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    _assert_points_equal(ref, bat)
    assert [len(p.streams) for p in bat.points] == [1, 3]
    # Local-only planners now route through the fleet engine's single-lane
    # backend (one lane per scenario, stats replicated per client) instead
    # of the old post-hoc replication; the scheduler audit comes along.
    assert bat.meta["engine"] == "sim_multi_batch"
    # Local-only plans still *request* bandwidth each round in the
    # reference, so the statically reconstructed audit must agree.
    for pr, pb in zip(ref.points, bat.points):
        assert pb.meta["grants"] == pr.meta["grants"]
        assert pb.meta["denials"] == pr.meta["denials"]
    assert all(s.frames_offloaded == 0 for s in bat.points[1].streams)


def test_width_axis_partitions_exactly():
    grid = SweepGrid(fps=(20.0, 50.0), params={"width": (16, 64)})
    spec = ScenarioSpec(policy=PolicySpec("jax_utility", {"alpha": 120.0}), n_frames=18)
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    _assert_points_equal(ref, bat)


def test_large_width_still_supported():
    """The registry puts no upper bound on the Pareto-front width; the sort
    rewrite must not impose one (regression: a packed-payload variant once
    asserted on width > 1024)."""
    spec = ScenarioSpec(
        policy=PolicySpec("jax_utility", {"alpha": 200.0, "width": 2048}), n_frames=6
    )
    ref = Session(spec).run_sweep(SweepGrid(), backend="reference")
    bat = Session(spec).run_sweep(SweepGrid(), backend="batched")
    _assert_points_equal(ref, bat)
    assert ref.points[0].stats.frames_processed > 0


def test_python_policy_falls_back_with_warning(caplog):
    spec = ScenarioSpec(policy=PolicySpec("local"), n_frames=6)
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep = Session(spec).run_sweep(SweepGrid(bandwidth_mbps=(2.5,)), backend="batched")
    assert rep.backend == "reference"
    assert "fallback" in rep.meta
    assert any("no batched backend" in r.getMessage() for r in caplog.records)
    # auto-routing picks reference silently for Python-only policies
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        auto = Session(spec).run_sweep(SweepGrid(bandwidth_mbps=(2.5,)))
    assert auto.backend == "reference" and not caplog.records


def test_simulate_batch_rejects_unbatched_policy():
    with pytest.raises(ValueError, match="no batched backend"):
        simulate_batch("local", [], [])


@pytest.mark.parametrize("name", sorted(NET_POLICIES))
def test_offloading_policy_fleet_grid_routes_to_fleet_engine(name, caplog):
    """Fleet grids of max_accuracy/max_utility used to log a documented
    fallback (contention made per-client replication wrong, and no fleet
    planner existed).  The dedicated fleet planners now serve them batched:
    no fallback warning, ``meta["engine"]`` confirms the engine, and the
    stats match the reference event loop."""
    base_params, _ = BATCHED_PARAMS[name]
    spec = ScenarioSpec(
        policy=PolicySpec(name, base_params), n_frames=8,
        fleet=FleetSpec(n_clients=2, capacity=2),
    )
    grid = SweepGrid(n_clients=(1, 2))
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep = Session(spec).run_sweep(grid, backend="batched")
    assert rep.backend == "batched"
    assert rep.meta["engine"] == "sim_multi_batch"
    assert "fallback" not in rep.meta
    assert not any("falling back" in r.getMessage() for r in caplog.records)
    ref = Session(spec).run_sweep(grid, backend="reference")
    _assert_points_equal(ref, rep)
    assert [len(p.streams) for p in rep.points] == [1, 2]


def test_utility_fast_width_overflow_rerun_is_exact(monkeypatch):
    """The Max-Utility planner first runs a narrow Pareto width and reruns
    lanes whose fronts outgrow it at the reference cap.  Force the narrow
    pass to overflow on every round (width 2) and check the spliced results
    still match the reference loop — the fast path must never trade
    exactness."""
    import repro.core.sim_batch as sb

    monkeypatch.setattr(sb, "_UTIL_FAST_WIDTH", 2)
    spec = ScenarioSpec(
        policy=PolicySpec("max_utility", {"alpha": 200.0}), n_frames=12
    )
    grid = SweepGrid(deadline_ms=(200.0, 350.0), fps=(30.0,))
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    assert bat.backend == "batched"
    _assert_points_equal(ref, bat, acc_tol=AUDIT_TOL)
    assert any(p.stats.frames_processed > 0 for p in bat.points)


def test_utility_prune_epsilon_window_matches_reference():
    """The reference's dominance bar is the last KEPT utility; candidates
    rejected inside the 1e-12 epsilon must not raise it.  NPU accuracies
    separated at the 13th decimal make candidate utilities collide within
    the epsilon — a cummax-based prune drops front entries the reference
    keeps (regression for the keep-fold in _utility_dp64)."""
    from repro.core import StreamSpec, Trace, profile_ms, simulate
    from repro.core.sim_batch import BatchScenario, simulate_batch

    models = [
        profile_ms(n, t_npu_ms=20.0, t_server_ms=9.0,
                   acc_server={45: 0.2, 224: 0.6}, acc_npu={224: a})
        for n, a in (("a", 0.5), ("b", 0.5 + 4e-13), ("c", 0.5 + 1.1e-12))
    ]
    spec = PolicySpec("max_utility", {"alpha": 200.0})
    for fps, dl, n in ((30.0, 0.2, 18), (50.0, 0.35, 24), (10.0, 0.1, 12)):
        stream = StreamSpec(fps=fps, deadline=dl)
        got, = simulate_batch(
            "max_utility", models,
            [BatchScenario(stream=stream, n_frames=n, params=spec.resolved)],
        )
        ref = simulate(spec.build(), models, stream, Trace.constant(2.5), n)
        for f in INT_FIELDS:
            assert getattr(got, f) == getattr(ref, f), (fps, dl, n, f)
        assert abs(got.accuracy_sum - ref.accuracy_sum) <= AUDIT_TOL


@pytest.mark.parametrize("fps", [10.0, 15.0, 24.0, 30.0, 60.0])
def test_segment_heads_match_trace_lookup(fps):
    """The integer segment lookup picks, at every round head, the segment
    ``Trace.at(head * gamma)`` picks — including boundaries that fall
    exactly on a frame time (0.3 s and 0.9 s at 30 fps), where a device
    float compare is decided by the last bit of ``head * gamma``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.sim_batch import _upload_at_head, segment_arrays, segment_heads
    from repro.core.simulator import Trace

    points = [(0.0, 3.0), (0.1, 1.0), (0.3, 0.8), (0.31, 0.5), (0.9, 6.0), (2.0, 0.0)]
    trace = Trace.piecewise(points, rtt_ms=60.0)
    bw_t, bw_v, _ = segment_arrays([[(t, v * 1e6) for t, v in points]])
    gamma = 1.0 / fps
    heads = segment_heads(bw_t, np.array([gamma]))[0]
    with jax.enable_x64(True):
        for h in range(int(3.0 * fps)):
            got = float(_upload_at_head(jnp.asarray(heads), jnp.asarray(bw_v[0]), jnp.int32(h)))
            assert got == trace.at(h * gamma).bandwidth_bps, (h, got)


def test_utility_dp64_overflow_flag():
    """White-box: a width too small for the front sets the overflow flag;
    the reference cap width does not (for this instance)."""
    import jax
    import jax.numpy as jnp

    from repro.core.jax_sched import _utility_dp64
    from repro.core.profiles import PAPER_MODELS

    with jax.enable_x64(True):
        t_npu = jnp.array([m.t_npu for m in PAPER_MODELS], jnp.float64)
        acc = jnp.array(
            [m.acc_npu[max(m.acc_npu)] for m in PAPER_MODELS], jnp.float64
        )
        kw = dict(
            n_frames=8, gamma=jnp.float64(1 / 30.0), deadline=jnp.float64(0.35),
            alpha=jnp.float64(200.0), npu_free=jnp.float64(0.0),
            first_arrival=jnp.float64(0.0), window=jnp.float64(8 / 30.0),
        )
        *_, ov_small = _utility_dp64(t_npu, acc, 8, width=2, **kw)
        *_, ov_large = _utility_dp64(t_npu, acc, 8, width=256, **kw)
    assert bool(ov_small) and not bool(ov_large)


def test_bandwidth_axis_overriding_piecewise_trace_warns_and_records(caplog):
    """A bandwidth_mbps axis replaces the base trace; on a piecewise base
    that silently drops the time-varying profile — run_sweep must log a
    warning and record the override in the affected points' meta."""
    spec = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=6, trace=PIECEWISE)
    grid = SweepGrid(bandwidth_mbps=(1.0, 2.5), deadline_ms=(200.0,))
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep = Session(spec).run_sweep(grid)
    assert any(
        "piecewise base trace" in r.getMessage() for r in caplog.records
    ), "silent trace override must warn"
    assert all("trace_override" in p.meta for p in rep.points)
    assert "bandwidth_mbps" in rep.points[0].meta["trace_override"]
    # constant base trace: the axis is the normal parameterization — silent
    caplog.clear()
    spec_c = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=6)
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep_c = Session(spec_c).run_sweep(grid)
    assert not caplog.records
    assert all("trace_override" not in p.meta for p in rep_c.points)
    # an rtt_ms-only axis preserves the piecewise profile: no override
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="repro.session"):
        rep_r = Session(spec).run_sweep(SweepGrid(rtt_ms=(50.0, 100.0)))
    assert not caplog.records
    assert all("trace_override" not in p.meta for p in rep_r.points)


def test_sweep_grid_validation_and_points():
    grid = SweepGrid(bandwidth_mbps=(1.0, 2.0), params={"alpha": (50.0,)})
    assert len(grid) == 2
    assert grid.points()[0] == {"bandwidth_mbps": 1.0, "alpha": 50.0}
    assert len(SweepGrid()) == 1 and SweepGrid().points() == [{}]
    with pytest.raises(ValueError, match="shadows a scenario axis"):
        SweepGrid(params={"fps": (30.0,)})
    with pytest.raises(ValueError, match="is empty"):
        SweepGrid(params={"alpha": ()})
    with pytest.raises(ValueError, match="unknown SweepGrid axes"):
        SweepGrid.from_json({"bandwidth": [1.0]})
    # scalars and strings are rejected, not silently iterated ("fifo" must
    # not become the 4-point axis ('f','i','f','o'))
    with pytest.raises(ValueError, match="must be a list"):
        SweepGrid.from_json({"bandwidth_mbps": 2.5})
    with pytest.raises(ValueError, match="must be a list"):
        SweepGrid(allocation="fifo")
    with pytest.raises(ValueError, match="must be a list"):
        SweepGrid(params={"alpha": "50"})
    with pytest.raises(ValueError, match="params must be a mapping"):
        SweepGrid.from_json({"params": [50.0]})
    rt = SweepGrid.from_json(json.loads(json.dumps(grid.to_json())))
    assert rt == grid


def test_unknown_backend_rejected():
    spec = ScenarioSpec(policy=PolicySpec("local"), n_frames=6)
    with pytest.raises(ValueError, match="unknown backend"):
        Session(spec).run_sweep(SweepGrid(), backend="warp")


def test_n_clients_axis_rejects_per_client_vectors():
    spec = ScenarioSpec(
        policy=PolicySpec("local"),
        n_frames=6,
        fleet=FleetSpec(n_clients=2, weights=(1.0, 2.0)),
    )
    with pytest.raises(ValueError, match="cannot resize"):
        Session(spec).run_sweep(SweepGrid(n_clients=(1, 2)))


def test_sweep_report_json_round_trip_batched():
    spec = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=12, label="rt")
    rep = Session(spec).run_sweep(SweepGrid(deadline_ms=(150.0, 200.0)))
    rt = SweepReport.from_json(json.loads(json.dumps(rep.to_json())))
    assert rt == rep


def test_sweep_cli_smoke(tmp_path, capsys, compile_cache_dir):
    from repro.session import main

    spec_file = tmp_path / "scenario.json"
    grid_file = tmp_path / "grid.json"
    spec = ScenarioSpec(policy=PolicySpec("local"), n_frames=6, label="cli-sweep")
    spec_file.write_text(json.dumps(spec.to_json()))
    grid_file.write_text(json.dumps(SweepGrid(bandwidth_mbps=(1.0, 2.5)).to_json()))
    assert main(["sweep", str(spec_file), "--grid", str(grid_file)]) == 0
    report = SweepReport.from_json(json.loads(capsys.readouterr().out))
    assert len(report) == 2 and report.base.label == "cli-sweep"

    out_file = tmp_path / "report.json"
    assert main([
        "sweep", str(spec_file), "--grid", str(grid_file), "--out", str(out_file),
    ]) == 0
    assert "2 points via reference backend" in capsys.readouterr().out
    saved = SweepReport.from_json(out_file.read_text())
    assert [p.overrides for p in saved] == [p.overrides for p in report]
    assert [p.stats.accuracy_sum for p in saved] == [p.stats.accuracy_sum for p in report]

    grid_file.write_text('{"bandwidth": [1.0]}')  # unknown axis
    assert main(["sweep", str(spec_file), "--grid", str(grid_file)]) == 2
    err = capsys.readouterr().err
    assert err.startswith("error:") and "Traceback" not in err

    grid_file.write_text('{"bandwidth_mbps": 2.5}')  # scalar axis
    assert main(["sweep", str(spec_file), "--grid", str(grid_file)]) == 2
    err = capsys.readouterr().err
    assert "must be a list" in err and "Traceback" not in err

    # malformed payload shapes that raise TypeError deep in from_json still
    # honor the one-line contract
    grid_file.write_text(json.dumps(SweepGrid(bandwidth_mbps=(1.0,)).to_json()))
    spec_file.write_text('{"policy": {"name": "local"}, "models": 5}')
    assert main(["sweep", str(spec_file), "--grid", str(grid_file)]) == 2
    err = capsys.readouterr().err
    assert err.startswith("error:") and "Traceback" not in err

    # unwritable --out is the same one-line error contract, not a traceback
    grid_file.write_text(json.dumps(SweepGrid(bandwidth_mbps=(1.0,)).to_json()))
    assert main([
        "sweep", str(spec_file), "--grid", str(grid_file),
        "--out", str(tmp_path / "no" / "such" / "dir" / "r.json"),
    ]) == 2
    err = capsys.readouterr().err
    assert err.startswith("error:") and "Traceback" not in err


# The sweep benchmark's smoke cells: 10-point grids at 120 frames.  The
# local-only jax_* planners sweep deadline x fps over a constant 2.5 Mbps
# link; the network-aware planners sweep deadline x rtt over the piecewise
# trace.  Equivalence is the certified contract, AUDIT_TOL for all four.
SMOKE_FRAMES = 120
SMOKE_CELLS = {
    "jax_accuracy": ({}, "jax"),
    "jax_utility": ({"alpha": 200.0}, "jax"),
    "max_accuracy": ({}, "network"),
    "max_utility": ({"alpha": 200.0}, "network"),
}


def _smoke_grid(ladder: str) -> tuple[SweepGrid, TraceSpec]:
    deadlines = (150.0, 200.0, 250.0, 300.0, 350.0)
    if ladder == "jax":
        return SweepGrid(deadline_ms=deadlines, fps=(20.0, 40.0)), TraceSpec(mbps=2.5)
    return SweepGrid(deadline_ms=deadlines, rtt_ms=(50.0, 100.0)), PIECEWISE


@pytest.mark.parametrize("name", sorted(SMOKE_CELLS))
def test_smoke_grid_batched_matches_reference(name):
    params, ladder = SMOKE_CELLS[name]
    grid, trace = _smoke_grid(ladder)
    assert len(grid) == 10
    spec = ScenarioSpec(policy=PolicySpec(name, params), n_frames=SMOKE_FRAMES, trace=trace)
    ref = Session(spec).run_sweep(grid, backend="reference")
    bat = Session(spec).run_sweep(grid, backend="batched")
    assert bat.backend == "batched"
    _assert_points_equal(ref, bat, acc_tol=AUDIT_TOL)
