"""Property tests for the vectorized multi-stream fleet backend.

Random inputs rather than the curated golden lattices:

  * for arbitrary model profiles (including server-only models and models
    with empty NPU accuracy tables), fleet shapes (size, allocation,
    capacity, backlog limit, weights, priorities), and constant|piecewise
    shared-link traces, every fleet planner through
    ``sim_multi_batch.simulate_multi_batch`` reproduces the reference
    ``simulate_multi`` event loop — integer stats exactly, accuracy and
    server busy time within ``MULTI_TOL``, scheduler grants/denials exact;
  * the fluid water-filling kernel never reserves more than the link
    offers: rates are non-negative, per-transfer caps are respected, and
    the total reservation never exceeds B.

Fleet/stream *shape* values are drawn from small sets (allocation, N,
capacity, frame counts, fps, deadlines are static to the jit cache); model
latencies, bandwidths, rtt, weights, and alpha stay continuous — they are
traced, not compiled.
"""
from __future__ import annotations

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core import (  # noqa: E402
    EdgeServerScheduler,
    PolicySpec,
    Trace,
    make_fleet,
    simulate_multi,
)
from repro.core.profiles import StreamSpec, profile_ms  # noqa: E402
from repro.core.registry import get_policy  # noqa: E402
from repro.core.sim_multi_batch import (  # noqa: E402
    EQUIV_INT_FIELDS,
    MULTI_TOL,
    FleetScenario,
    _fleet_physics,
    multi_batched_policies,
    simulate_multi_batch,
)

# Example counts come from the shared profiles in conftest.py
# (HYPOTHESIS_PROFILE=ci|nightly); settings() snapshots the active profile.
SETTINGS = settings()


@st.composite
def model_sets(draw):
    n = draw(st.integers(1, 3))
    models = []
    for i in range(n):
        runs_local = draw(st.booleans()) if n > 1 else True
        has_acc = draw(st.booleans())
        models.append(
            profile_ms(
                f"m{i}",
                t_npu_ms=draw(st.floats(5, 250)) if runs_local else float("inf"),
                t_server_ms=draw(st.floats(5, 120)),
                acc_server={45: 0.2, 224: draw(st.floats(0.3, 0.95))},
                acc_npu={224: draw(st.floats(0.1, 0.9))} if has_acc else {},
            )
        )
    return models


@st.composite
def traces(draw):
    rtt_ms = draw(st.floats(20.0, 150.0))
    if draw(st.booleans()):
        return ("constant", draw(st.floats(0.2, 12.0)), rtt_ms, ())
    points = tuple(
        (t, draw(st.floats(0.2, 12.0)))
        for t in sorted(draw(st.sets(st.sampled_from((0.0, 0.1, 0.25, 0.4, 0.8)),
                                     min_size=1, max_size=3)))
    )
    return ("piecewise", None, rtt_ms, points)


def _build_trace(kind, mbps, rtt_ms, points) -> Trace:
    if kind == "constant":
        return Trace.constant(mbps, rtt_ms=rtt_ms)
    return Trace.piecewise(list(points), rtt_ms=rtt_ms)


def _segments(kind, mbps, rtt_ms, points):
    if kind == "constant":
        return ((0.0, mbps * 1e6),)
    return tuple((t, v * 1e6) for t, v in sorted(points))


@st.composite
def fleet_cases(draw):
    models = draw(model_sets())
    # classify planners only: the registry refuses a track planner on the
    # classify workload these fleets carry
    policy = draw(st.sampled_from(sorted(
        p for p in multi_batched_policies() if "classify" in get_policy(p).workloads
    )))
    if policy in ("max_utility", "jax_utility"):
        params = {"alpha": draw(st.floats(1.0, 400.0))}
    elif policy in ("max_accuracy", "jax_accuracy"):
        params = {"grid": draw(st.sampled_from((1e-3, 2e-3)))}
    else:
        params = {"alpha": draw(st.floats(1.0, 400.0))} if draw(st.booleans()) else {}
    n = draw(st.integers(1, 3))
    fleet = dict(
        n_clients=n,
        allocation=draw(st.sampled_from(("weighted_fair", "priority", "fifo"))),
        capacity=draw(st.sampled_from((0, 1, 2))),
        backlog_limit=draw(st.sampled_from((0.0, 0.05))),
        weights=tuple(draw(st.floats(0.25, 4.0)) for _ in range(n)),
        priorities=tuple(draw(st.integers(0, 2)) for _ in range(n)),
    )
    stream = StreamSpec(
        fps=draw(st.sampled_from((10.0, 30.0))),
        deadline=draw(st.sampled_from((100.0, 200.0, 350.0))) / 1e3,
    )
    return models, policy, params, stream, draw(st.sampled_from((4, 8, 12))), fleet, draw(traces())


@SETTINGS
@given(fleet_cases())
def test_fleet_batched_stats_equal_simulate_multi(case):
    models, policy, params, stream, n_frames, fleet_kw, tr = case
    spec = PolicySpec(policy, params)
    clients = make_fleet(
        fleet_kw["n_clients"],
        stream=stream,
        models=models,
        policy=spec,
        weights=fleet_kw["weights"],
        priorities=fleet_kw["priorities"],
    )
    sched = EdgeServerScheduler(
        clients,
        policy=fleet_kw["allocation"],
        capacity=fleet_kw["capacity"],
        backlog_limit=fleet_kw["backlog_limit"],
    )
    ms_ref = simulate_multi(sched, _build_trace(*tr), n_frames)
    (ms_bat, meta), = simulate_multi_batch(
        policy,
        models,
        [
            FleetScenario(
                stream=stream,
                n_frames=n_frames,
                bw_segments=_segments(*tr),
                rtt=tr[2] / 1e3,
                params=spec.resolved,
                **fleet_kw,
            )
        ],
    )
    for sr, sb in zip(ms_ref.per_client, ms_bat.per_client):
        for f in EQUIV_INT_FIELDS:
            assert getattr(sr, f) == getattr(sb, f), (policy, fleet_kw, tr, f)
        assert abs(sr.accuracy_sum - sb.accuracy_sum) <= MULTI_TOL, (policy, fleet_kw, tr)
    assert ms_bat.server_jobs == ms_ref.server_jobs
    assert abs(ms_bat.server_busy_s - ms_ref.server_busy_s) <= MULTI_TOL
    assert meta == {"grants": sched.audit.grants, "denials": sched.audit.denials}


# ---------------------------------------------------------------------------
# Water-filling reservation invariant: the fluid link never over-commits.
# ---------------------------------------------------------------------------


@SETTINGS
@given(
    n=st.integers(1, 6),
    data=st.data(),
    bandwidth=st.floats(0.0, 2e7),
)
def test_waterfill_reservation_never_exceeds_link(n, data, bandwidth):
    weights = np.array(
        data.draw(st.lists(st.floats(0.0, 5.0), min_size=n, max_size=n)), np.float64
    )
    active = np.array(
        data.draw(st.lists(st.booleans(), min_size=n, max_size=n)), bool
    )
    caps = np.array(
        data.draw(
            st.lists(st.floats(1e3, 1e8) | st.just(float("inf")), min_size=n, max_size=n)
        ),
        np.float64,
    )
    with jax.enable_x64(True):
        phys = _fleet_physics(
            "weighted_fair", n, 2, 4,
            bw_t=jnp.zeros((1,)), bw_v=jnp.full((1,), bandwidth),
            rtt=jnp.float64(0.05), L=jnp.float64(0.0),
            w_fluid=jnp.maximum(jnp.asarray(weights), 1e-9),
            w_eff=jnp.asarray(weights), tot_w=jnp.float64(max(weights.sum(), 1.0)),
            prio=jnp.zeros((n,), jnp.int32),
        )
        rates = np.asarray(phys.waterfill(jnp.float64(bandwidth), jnp.asarray(active), jnp.asarray(caps)))
    tol = 1e-9 * max(bandwidth, 1.0)
    assert (rates >= 0.0).all()
    assert (rates[~active] == 0.0).all()
    assert (rates <= caps + tol).all()
    assert rates.sum() <= bandwidth + tol
