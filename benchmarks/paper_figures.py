"""Benchmarks reproducing each FastVA table/figure with the paper's own
constants (Table II profiles, 200 ms deadline, 5 resolutions, 100 ms delay).

Each function returns a list of (name, us_per_call, derived) rows where
``derived`` is the figure's y-value and ``us_per_call`` is the mean wall time
of one policy round (the schedule-decision cost the paper reports < 1 ms).

Every figure sweep is one ``Session.run_sweep`` over a declarative
``SweepGrid`` (bandwidth/fps/rtt/policy-param axes), so adding a policy —
including the ``brute_force`` oracle and the jitted ``jax_*`` DPs, which the
sweep engine routes through the vectorized ``sim_batch`` backend — is just
another name in a tuple.  One-off cells (fig 7's oracle gap) still use a
single-point ``ScenarioSpec``.

    PYTHONPATH=src python benchmarks/paper_figures.py   # every row, as CSV
"""
from __future__ import annotations

import time

from repro.core import PAPER_MODELS, PAPER_STREAM, PolicySpec, StreamSpec, brute_force, network_mbps
from repro.session import ScenarioSpec, Session, SweepGrid, TraceSpec

N_FRAMES = 120
POLICIES = ("max_accuracy", "local", "offload", "deepdecision")


def _sim(
    policy: str,
    mbps: float,
    *,
    params: dict | None = None,
    fps: float | None = None,
    rtt_ms: float = 100.0,
    n_frames: int = N_FRAMES,
):
    """One front-door cell: build the spec, run the audited simulator."""
    spec = ScenarioSpec(
        policy=PolicySpec(policy, params or {}),
        n_frames=n_frames,
        stream=PAPER_STREAM if fps is None else StreamSpec(fps=fps),
        trace=TraceSpec(mbps=mbps, rtt_ms=rtt_ms),
    )
    return Session(spec).run_sim().stats


def _sweep(
    policy: str,
    *,
    params: dict | None = None,
    params_axes: dict | None = None,
    n_frames: int = N_FRAMES,
    **axes,
):
    """One figure sweep: the base paper scenario crossed with ``axes``
    (scenario axes as kwargs, policy-param axes via ``params_axes``)."""
    spec = ScenarioSpec(
        policy=PolicySpec(policy, params or {}),
        n_frames=n_frames,
        trace=TraceSpec(mbps=2.5),
        label=f"paper_figures/{policy}",
    )
    return Session(spec).run_sweep(SweepGrid(params=params_axes or {}, **axes))


def _row(name: str, stats, derived: float):
    us = stats.schedule_time / max(stats.schedule_calls, 1) * 1e6 if stats else 0.0
    return (name, us, derived)


def table2_profiles():
    """Table II: per-model processing times and accuracy (paper constants
    drive all scheduling benches; derived = top-1 accuracy)."""
    rows = []
    for m in PAPER_MODELS:
        rows.append((f"table2/{m.name}/npu", m.t_npu * 1e6, m.accuracy(224, where="npu")))
        rows.append((f"table2/{m.name}/server", m.t_server * 1e6, m.accuracy(224, where="server")))
    return rows


def fig4_accuracy_resolution():
    rows = []
    for m in PAPER_MODELS:
        for r in PAPER_STREAM.resolutions:
            rows.append((f"fig4/{m.name}/r{r}", 0.0, m.accuracy(r, where="server")))
    return rows


def fig5_bandwidth_accuracy():
    rows = []
    for pol in POLICIES:
        rep = _sweep(pol, bandwidth_mbps=(0.5, 1.0, 1.5, 2.0, 2.5, 3.0, 3.5))
        for pt in rep:
            st = pt.stats
            rows.append(_row(f"fig5/B{pt.overrides['bandwidth_mbps']}/{pol}", st, st.mean_accuracy))
    return rows


def fig6_framerate_accuracy():
    rows = []
    for pol in POLICIES:
        rep = _sweep(pol, bandwidth_mbps=(3.0,), fps=(10, 20, 30, 40, 50))
        for pt in rep:
            st = pt.stats
            rows.append(_row(f"fig6/fps{pt.overrides['fps']}/{pol}", st, st.mean_accuracy))
    return rows


def fig7_optimal_gap():
    """Fig 7b: Optimal minus Max-Accuracy (derived = the gap, ~0)."""
    rows = []
    for mbps in (1.0, 2.0, 3.0):
        for fps in (20, 30, 40):
            t0 = time.perf_counter()
            opt = brute_force.optimal_accuracy(
                list(PAPER_MODELS), StreamSpec(fps=fps), network_mbps(mbps), 40, grid=2e-3
            )
            dt = (time.perf_counter() - t0) * 1e6
            st = _sim("max_accuracy", mbps, fps=fps, n_frames=40)
            rows.append((f"fig7/B{mbps}_fps{fps}/gap", dt, max(opt - st.mean_accuracy, 0.0)))
    return rows


def fig8_delay_accuracy():
    rows = []
    for pol in POLICIES:
        rep = _sweep(pol, bandwidth_mbps=(3.0,), fps=(30, 50), rtt_ms=(50, 100, 150, 200))
        for pt in rep:
            o, st = pt.overrides, pt.stats
            rows.append(_row(f"fig8/d{o['rtt_ms']}_fps{o['fps']}/{pol}", st, st.mean_accuracy))
    return rows


def fig9_bandwidth_utility():
    rows = []
    for pol in ("max_utility", "local", "offload", "deepdecision"):
        rep = _sweep(pol, params={"alpha": 200.0},
                     bandwidth_mbps=(0.5, 1.5, 2.5, 3.5), params_axes={"alpha": (200.0, 50.0)})
        for pt in rep:
            o, st = pt.overrides, pt.stats
            rows.append(_row(f"fig9/a{o['alpha']:.0f}_B{o['bandwidth_mbps']}/{pol}",
                             st, st.utility(o["alpha"])))
    return rows


def fig10_framerate_utility():
    rows = []
    for pol in ("max_utility", "local", "offload"):
        rep = _sweep(pol, params={"alpha": 200.0},
                     bandwidth_mbps=(2.5,), fps=(10, 30, 50), params_axes={"alpha": (200.0, 50.0)})
        for pt in rep:
            o, st = pt.overrides, pt.stats
            rows.append(_row(f"fig10/a{o['alpha']:.0f}_fps{o['fps']}/{pol}",
                             st, st.utility(o["alpha"])))
    return rows


def fig11_delay_utility():
    rows = []
    for pol in ("max_utility", "local", "offload"):
        rep = _sweep(pol, params={"alpha": 200.0},
                     bandwidth_mbps=(2.0,), rtt_ms=(50, 100, 150), params_axes={"alpha": (200.0, 50.0)})
        for pt in rep:
            o, st = pt.overrides, pt.stats
            rows.append(_row(f"fig11/a{o['alpha']:.0f}_d{o['rtt_ms']}/{pol}",
                             st, st.utility(o["alpha"])))
    return rows


def oracle_gap_sweep():
    """Beyond-paper: the oracle and the jitted DPs as *policies*, swept
    uniformly with the heuristics through the sweep front door (the jax_*
    policies route through the batched sim_batch backend here).
    derived = mean accuracy (or utility); the oracle upper-bounds each cell
    up to its time grid (default 5 ms — tighten ``grid`` to close the gap)."""
    rows = []
    for pol in ("max_accuracy", "brute_force", "jax_accuracy", "local"):
        rep = _sweep(pol, n_frames=60, bandwidth_mbps=(1.0, 2.5))
        for pt in rep:
            st = pt.stats
            rows.append(_row(f"oracle/B{pt.overrides['bandwidth_mbps']}/{pol}",
                             st, st.mean_accuracy))
    alpha = 200.0
    for pol in ("max_utility", "brute_force", "jax_utility"):
        rep = _sweep(pol, params={"alpha": alpha}, n_frames=60, bandwidth_mbps=(2.5,))
        rows.append(_row(f"oracle/a{alpha:.0f}_B2.5/{pol}", rep.points[0].stats,
                         rep.points[0].stats.utility(alpha)))
    return rows


def sched_latency():
    """Paper §VI.A: 'running time ... less than 1 ms'.  Derived = ms/round."""
    from repro.core.jax_sched import local_accuracy_dp_jax, local_utility_dp_jax
    from repro.core.max_accuracy import plan_round as ma_round
    from repro.core.max_utility import plan_round as mu_round

    models = list(PAPER_MODELS)
    net = network_mbps(2.5)
    rows = []
    for name, fn in [
        ("sched/max_accuracy_py", lambda: ma_round(models, PAPER_STREAM, net)),
        ("sched/max_utility_py", lambda: mu_round(models, PAPER_STREAM, net, alpha=200.0)),
        ("sched/accuracy_dp_jax", lambda: local_accuracy_dp_jax(
            models, n_frames=6, gamma=1 / 30, deadline=0.2, npu_free=0.0, first_arrival=1 / 30)),
        ("sched/utility_dp_jax", lambda: local_utility_dp_jax(
            models, n_frames=6, gamma=1 / 30, deadline=0.2, alpha=200.0, npu_free=0.0,
            first_arrival=1 / 30, window=0.2)),
    ]:
        fn()  # warm
        t0 = time.perf_counter()
        n = 30
        for _ in range(n):
            fn()
        us = (time.perf_counter() - t0) / n * 1e6
        rows.append((name, us, us / 1e3))  # derived = ms
    return rows


ALL = [
    table2_profiles,
    fig4_accuracy_resolution,
    fig5_bandwidth_accuracy,
    fig6_framerate_accuracy,
    fig7_optimal_gap,
    fig8_delay_accuracy,
    fig9_bandwidth_utility,
    fig10_framerate_utility,
    fig11_delay_utility,
    oracle_gap_sweep,
    sched_latency,
]


def main() -> None:
    """Print every figure's rows as ``name,us_per_call,derived`` CSV."""
    print("name,us_per_call,derived")
    for figure in ALL:
        for name, us, derived in figure():
            print(f"{name},{us:.2f},{derived:.6f}", flush=True)


if __name__ == "__main__":
    main()
