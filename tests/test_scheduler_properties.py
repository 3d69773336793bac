"""Property tests for the FastVA schedulers (the paper's core contribution).

Invariants:
  * every emitted plan is feasible (deadlines, no NPU overlap);
  * Max-Accuracy >= both Local and Offload on any instance (it contains them);
  * Max-Accuracy / Max-Utility <= the exhaustive optimum on tiny instances;
  * Max-Utility >= Local on the utility objective;
  * the dominance-pruned DP equals a brute-force subset enumeration;
  * JAX DPs == Python DPs.
"""
from __future__ import annotations

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import HealthCheck, example, given, settings  # noqa: E402

from repro.core import (
    PAPER_MODELS,
    ModelProfile,
    NetworkState,
    StreamSpec,
    Trace,
    make_policy,
    network_mbps,
    profile_ms,
    simulate,
)
from repro.core import brute_force, max_accuracy, max_utility
from repro.core.schedule import validate_plan

# Example counts come from the shared profiles in conftest.py
# (HYPOTHESIS_PROFILE=ci|nightly); settings() snapshots the active profile.
SETTINGS = settings()


@st.composite
def model_profiles(draw):
    n = draw(st.integers(1, 3))
    models = []
    for i in range(n):
        t_npu = draw(st.floats(5, 120))
        t_srv = draw(st.floats(5, 120))
        a_srv = draw(st.floats(0.2, 0.95))
        a_npu = draw(st.floats(0.1, 0.9))
        models.append(
            profile_ms(
                f"m{i}",
                t_npu_ms=t_npu,
                t_server_ms=t_srv,
                acc_server={45: a_srv * 0.4, 134: a_srv * 0.8, 224: a_srv},
                acc_npu={224: a_npu},
            )
        )
    return models


@st.composite
def scenario(draw):
    models = draw(model_profiles())
    fps = draw(st.sampled_from([10.0, 20.0, 30.0, 50.0]))
    mbps = draw(st.floats(0.3, 8.0))
    rtt = draw(st.floats(10.0, 150.0))
    return models, StreamSpec(fps=fps), network_mbps(mbps, rtt_ms=rtt)


@given(scenario())
@SETTINGS
def test_max_accuracy_plans_feasible(s):
    models, stream, net = s
    for npu_free in (0.0, 0.05):
        plan = max_accuracy.plan_round(models, stream, net, npu_free=npu_free)
        # npu_free shifts the NPU availability; frames must still meet deadlines
        errors = validate_plan(plan, gamma=stream.gamma, deadline=stream.deadline)
        assert not errors, errors


@given(scenario())
@SETTINGS
def test_max_utility_plans_feasible(s):
    models, stream, net = s
    for alpha in (50.0, 200.0):
        plan = max_utility.plan_round(models, stream, net, alpha=alpha, npu_free=0.0)
        errors = validate_plan(plan, gamma=stream.gamma, deadline=stream.deadline)
        assert not errors, errors


# Counterexamples Hypothesis found on the CPU, pinned so that every run
# checks them: the whole-run dominance below fails on both (ROADMAP Design 9).
_CEX_ACCURACY = (
    [ModelProfile("m0", t_npu=0.005, t_server=0.005,
                  acc_server={45: 0.25, 134: 0.5, 224: 0.625}, acc_npu={224: 0.3125}),
     ModelProfile("m1", t_npu=0.031, t_server=0.005,
                  acc_server={45: 0.2, 134: 0.4, 224: 0.5}, acc_npu={224: 0.5})],
    StreamSpec(fps=50.0),
    NetworkState(bandwidth_bps=500000.0, rtt=0.01),
)
_CEX_UTILITY = (
    [ModelProfile("m0", t_npu=0.079, t_server=0.005,
                  acc_server={45: 0.2, 134: 0.4, 224: 0.5}, acc_npu={224: 0.5625})],
    StreamSpec(fps=20.0),
    NetworkState(bandwidth_bps=1000000.0, rtt=0.01),
)


@given(scenario())
@example(_CEX_ACCURACY)
@SETTINGS
def test_max_accuracy_dominates_baselines(s):
    models, stream, net = s
    tr = Trace(lambda t: net.bandwidth_bps, lambda t: net.rtt)
    n = 60
    acc_ma = simulate(make_policy("max_accuracy"), models, stream, tr, n).mean_accuracy
    acc_lo = simulate(make_policy("local"), models, stream, tr, n).mean_accuracy
    acc_of = simulate(make_policy("offload"), models, stream, tr, n).mean_accuracy
    assert acc_ma >= acc_lo - 1e-6
    assert acc_ma >= acc_of - 1e-6


@given(scenario())
@example(_CEX_UTILITY)
@SETTINGS
def test_max_utility_dominates_local(s):
    """Max-Utility contains a Local-equivalent candidate per round, so it can
    only trail Local through round-BOUNDARY effects (the NPU-backlog state at
    which each policy happens to re-plan differs).  Bound that slack at 1%;
    on the paper's own profiles the dominance is exact (see
    test_paper_claims_reproduce)."""
    models, stream, net = s
    tr = Trace(lambda t: net.bandwidth_bps, lambda t: net.rtt)
    for alpha in (50.0, 200.0):
        u_mu = simulate(make_policy("max_utility", alpha=alpha), models, stream, tr, 60).utility(alpha)
        u_lo = simulate(make_policy("local", alpha=alpha), models, stream, tr, 60).utility(alpha)
        assert u_mu >= u_lo * 0.99 - 1e-5


@given(scenario())
@SETTINGS
def test_policies_below_exhaustive_optimum(s):
    models, stream, net = s
    n = 4
    opt = brute_force.exhaustive_best(models, stream, net, n)
    tr = Trace(lambda t: net.bandwidth_bps, lambda t: net.rtt)
    acc_ma = simulate(make_policy("max_accuracy"), models, stream, tr, n).mean_accuracy
    assert acc_ma <= opt + 1e-6
    alpha = 100.0
    opt_u = brute_force.exhaustive_best(models, stream, net, n, alpha=alpha)
    u_mu = simulate(make_policy("max_utility", alpha=alpha), models, stream, tr, n).utility(alpha)
    assert u_mu <= opt_u + 1e-4


@given(scenario())
@SETTINGS
def test_grid_dp_below_exhaustive(s):
    models, stream, net = s
    n = 4
    exh = brute_force.exhaustive_best(models, stream, net, n)
    grid = brute_force.optimal_accuracy(models, stream, net, n, grid=1e-3)
    assert grid <= exh + 1e-6
    # and converges from below with a fine grid
    assert grid >= exh - 0.25


@given(scenario(), st.integers(1, 8))
@SETTINGS
def test_jax_dps_match_python(s, n_frames):
    from repro.core.jax_sched import local_accuracy_dp_jax, local_utility_dp_jax
    from repro.core.max_accuracy import local_dp
    from repro.core.max_utility import local_utility_dp

    models, stream, net = s
    gamma, T = stream.gamma, stream.deadline
    py = local_dp(models, n_frames=n_frames, gamma=gamma, deadline=T, npu_free=0.0, first_arrival=gamma)
    jt, jm = local_accuracy_dp_jax(
        models, n_frames=n_frames, gamma=gamma, deadline=T, npu_free=0.0, first_arrival=gamma
    )
    if py.feasible:
        assert abs(py.total_accuracy - jt) < 1e-4
    else:
        assert jt < -1e17

    w = n_frames * gamma
    alpha = 100.0
    pu = local_utility_dp(
        models, n_frames=n_frames, gamma=gamma, deadline=T, alpha=alpha, npu_free=0.0,
        first_arrival=0.0, window=w,
    )
    ju, jd = local_utility_dp_jax(
        models, n_frames=n_frames, gamma=gamma, deadline=T, alpha=alpha, npu_free=0.0,
        first_arrival=0.0, window=w,
    )
    # The f32 DP may pick a boundary-different schedule; the property that
    # matters: its schedule is feasible and achieves the same utility when
    # re-evaluated in f64.
    t = 0.0
    acc_sum, m_count = 0.0, 0
    for k, j in jd:
        arrival = k * gamma
        start = max(t, arrival)
        t = start + models[j].t_npu
        assert t <= arrival + T + 1e-5, "JAX schedule infeasible"
        acc_sum += models[j].acc_npu[224]
        m_count += 1
    ju64 = (m_count / w + alpha * acc_sum / m_count) if m_count else 0.0
    assert ju64 >= pu.utility - max(1e-3, 1e-3 * abs(pu.utility))
    assert ju64 <= pu.utility + max(1e-3, 1e-3 * abs(pu.utility))


def test_dominance_pruning_is_lossless():
    """The pruned DP must equal brute-force enumeration over local subsets."""
    models = list(PAPER_MODELS)
    stream = StreamSpec(fps=30)
    gamma, T, alpha = stream.gamma, stream.deadline, 150.0
    n = 6
    w = n * gamma

    from itertools import product

    best = 0.0
    local_models = [j for j, m in enumerate(models) if m.runs_local]
    for choice in product([None, *local_models], repeat=n):
        t = 0.0
        acc, m_count = 0.0, 0
        ok = True
        for k, j in enumerate(choice):
            if j is None:
                continue
            arrival = k * gamma
            start = max(t, arrival)
            t = start + models[j].t_npu
            if t > arrival + T + 1e-12:
                ok = False
                break
            acc += models[j].acc_npu[224]
            m_count += 1
        if ok and m_count:
            best = max(best, m_count / w + alpha * acc / m_count)
    from repro.core.max_utility import local_utility_dp

    dp = local_utility_dp(
        models, n_frames=n, gamma=gamma, deadline=T, alpha=alpha, npu_free=0.0,
        first_arrival=0.0, window=w,
    )
    assert dp.utility == pytest.approx(best, abs=1e-6)


def test_paper_claims_reproduce():
    """Quantitative claims from §VI with the paper's own profile constants."""
    models = list(PAPER_MODELS)
    stream = StreamSpec(fps=30)
    # Offload collapses when it cannot sustain the frame rate (Fig. 5b).
    st_off = simulate(make_policy("offload"), models, stream, Trace.constant(0.5), 120)
    assert st_off.mean_accuracy == 0.0
    # Local == Max-Accuracy at low bandwidth; Max-Accuracy wins at high B (Fig. 5).
    lo = simulate(make_policy("local"), models, stream, Trace.constant(1.0), 120).mean_accuracy
    ma_low = simulate(make_policy("max_accuracy"), models, stream, Trace.constant(1.0), 120).mean_accuracy
    ma_high = simulate(make_policy("max_accuracy"), models, stream, Trace.constant(3.5), 120).mean_accuracy
    assert ma_low == pytest.approx(lo, abs=1e-6)
    assert ma_high >= ma_low
    # DeepDecision under-utilizes the NPU vs Local at 30fps (paper §VI.C).
    dd = simulate(make_policy("deepdecision"), models, stream, Trace.constant(1.0), 120).mean_accuracy
    assert lo > dd
    # Max-Accuracy ~= Optimal (Fig. 7b) on the grid DP.
    opt = brute_force.optimal_accuracy(models, stream, network_mbps(2.5), 30, grid=2e-3)
    ma = simulate(make_policy("max_accuracy"), models, stream, Trace.constant(2.5), 30).mean_accuracy
    assert abs(opt - ma) < 0.05
