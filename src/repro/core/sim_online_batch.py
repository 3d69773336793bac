"""Vectorized online-adaptation backend: observe -> replan -> execute on device.

``Session.run_online`` replays the paper's §VI adaptivity story one Python
round at a time: the policy plans against the EWMA belief of
:class:`~repro.core.controller.BandwidthEstimator` (bandwidth shaded by the
pessimism factor, RTT seeded from the first observation), while execution is
audited against the *true* trace — offload finish times are recomputed at
real bandwidth over a serially-occupied uplink, and each upload feeds the
estimator back.  This module executes that whole loop for a *batch* of
scenarios as one jit+vmap program: per lane, a ``lax.while_loop`` over rounds
whose carry holds the estimator state (EWMA bps / RTT), the NPU horizon, and
the true-link occupancy next to the audit accumulators.

Exactness contract (golden-tested in ``tests/test_online_batch.py``): for
every scenario, integer stats (processed / missed / offloaded / rounds) are
**exact** and accuracy sums match the fixed ``run_online`` reference within
:data:`~repro.core.audit.AUDIT_TOL`.  The planning phase is byte-for-byte
the network-aware programs of :mod:`repro.core.sim_batch` with two
substitutions — the bandwidth the planner sees is the carried belief
``bps * pessimism`` instead of a trace lookup, and the RTT is the carried
EWMA instead of a constant — and the execution phase renders ``run_online``'s
offload callback:

  * ``start = max(net_free, t0)`` — the true link is a serial resource
    carried across rounds (a belief-driven offload storm queues up);
  * ``finish = ((start + t_up_true) + rtt_true) + t_server``, compared
    against ``(t0 + deadline) + AUDIT_TOL`` unconditionally (true-completion
    accounting is not gated on ``strict`` — only plan-side NPU audits are);
  * the estimator updates ``bps <- (1-beta)*bps + beta*sample`` with
    ``sample = nbits / t_up_true`` (0 on a dead link: the belief decays, it
    is never poisoned by ``inf``), each product wrapped in
    :func:`~repro.core.jax_sched._no_fma` so XLA cannot contract the two
    f64 multiplies into an fma and drift off the reference bits.

Only the head frame of a round ever offloads (both planners emit a single
SERVER decision at frame 0), so each round makes at most one estimator
observation pair — exactly the reference's cadence.  Policies registered
``batched_online=True`` have a planner here; ``Session.run_sweep(mode=
"online")`` falls back to per-point ``run_online`` for everything else.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .audit import AUDIT_TOL
from .bucketing import quant_bins as _quant_bins
from .jax_sched import NEG, _accuracy_dp64, _no_fma, _utility_dp64
from .profiles import ModelProfile, StreamSpec
from .registry import get_policy
from .schedule import StreamStats
from .sim_batch import (
    _UTIL_CAP,
    _UTIL_FAST_WIDTH,
    _audit_scan,
    _collect,
    _common,
    _net_arrays,
    _net_group_key,
    _offload_tables,
    _stitch,
    _upload_at_head,
)
from .sweep_shard import LaneProgram

__all__ = ["OnlineScenario", "batched_online_policies", "simulate_online_batch"]


@dataclass(frozen=True)
class OnlineScenario:
    """One online grid point: the scenario a ``run_online`` call would see.

    ``bw_segments`` / ``rtt`` describe the **true** network (the same padded
    piecewise layout as :class:`~repro.core.sim_batch.BatchScenario`); the
    estimator fields describe the belief machinery.  ``init_bps=None``
    seeds the belief from the true trace at t=0 — exactly
    ``BandwidthEstimator(init_bps=trace.at(0.0).bandwidth_bps)`` in
    ``run_online`` — and the believed RTT always seeds from the true RTT
    (the reference's pre-loop ``observe_rtt(trace.at(0.0).rtt)``, which
    *replaces* the stub prior now that the first sample seeds)."""

    stream: StreamSpec = field(default_factory=StreamSpec)
    n_frames: int = 120
    params: Mapping[str, Any] = field(default_factory=dict)
    rtt: float = 0.100
    bw_segments: tuple[tuple[float, float], ...] = ((0.0, 2.5e6),)
    init_bps: float | None = None
    beta: float = 0.3
    pessimism: float = 0.9


def _barrier(x):
    """Identity that XLA must not optimize across (see ``_true_offload``)."""
    return jax.lax.optimization_barrier(x)


_ONLINE: dict[str, Callable[..., list[tuple[StreamStats, dict]]]] = {}


def _online(name: str):
    def deco(fn):
        _ONLINE[name] = fn
        return fn

    return deco


def batched_online_policies() -> tuple[str, ...]:
    """Policy names with an online backend (mirrors ``batched_online=True``
    in the registry; ``tests/test_online_batch.py`` asserts the sync)."""
    return tuple(sorted(_ONLINE))


def simulate_online_batch(
    policy: str,
    models: Sequence[ModelProfile],
    scenarios: Sequence[OnlineScenario],
    *,
    strict: bool = True,
) -> list[tuple[StreamStats, dict]]:
    """Run the online loop for ``policy`` over every scenario in one compiled
    program.  Returns ``(stats, meta)`` per scenario in order, where ``meta``
    carries what ``run_online`` reports: the round count and the estimator's
    final believed bandwidth (``estimated_bps``).  Raises ``ValueError`` for
    policies without an online backend — silent fallback lives in
    ``Session.run_sweep(mode="online")``.
    """
    fn = _ONLINE.get(policy)
    if fn is None:
        raise ValueError(
            f"policy {policy!r} has no batched online backend; "
            f"available: {batched_online_policies()}"
        )
    get_policy(policy)  # surface unknown-policy errors with the registry text
    if not scenarios:
        return []
    return fn(list(models), list(scenarios), bool(strict))


def _bw_at0(segments: Sequence[tuple[float, float]]) -> float:
    """True bandwidth at t=0 under ``Trace.piecewise`` semantics: the last
    segment with ``t_start <= 0`` wins; before the first segment's start the
    first value applies."""
    segs = sorted((float(t), float(v)) for t, v in segments) or [(0.0, 0.0)]
    v0 = segs[0][1]
    for t, v in segs:
        if t <= 0.0:
            v0 = v
    return v0


def _estimator_arrays(group: list[OnlineScenario]):
    """Per-lane estimator constants: beta, (1-beta) (precomputed once, the
    same f64 subtraction the reference performs per call), pessimism, and
    the belief's initial bandwidth."""
    beta = np.array([s.beta for s in group], np.float64)
    omb = 1.0 - beta
    pess = np.array([s.pessimism for s in group], np.float64)
    bps0 = np.array(
        [s.init_bps if s.init_bps is not None else _bw_at0(s.bw_segments) for s in group],
        np.float64,
    )
    return beta, omb, pess, bps0


def _with_meta(stats: list[StreamStats], bps_final, pess) -> list[tuple[StreamStats, dict]]:
    # estimator.state().bandwidth_bps == _bps * pessimism — the belief the
    # next round would have planned with.
    return [
        (st, {"rounds": int(st.schedule_calls), "estimated_bps": float(b * p)})
        for st, b, p in zip(stats, np.asarray(bps_final), np.asarray(pess))
    ]


# ---------------------------------------------------------------------------
# Shared execution phase: run_online's offload callback as array expressions.
# The planning phase above it decided use_off / r_off / j_off from the
# *belief*; this fold completes the head-frame upload on the *true* network,
# keeps the link serially occupied, and feeds the estimator back.
# ---------------------------------------------------------------------------


def _true_offload(*, active, use_off, r_off, j_off, head, t0, deadline, rtt, beta, omb,
                  bps, rttb, netf, acc_sum, proc, miss, offl,
                  nbits8, acc_sv, seg_head, t_up_seg, t_srv, rounded, rounded2):
    tup_t = _upload_at_head(seg_head, t_up_seg, head)[r_off]  # the reference's trace.at(t0)
    start = jnp.maximum(netf, t0)  # d.start == 0.0 for both planners' heads
    fin = ((start + tup_t) + rtt) + t_srv[j_off]
    ok = fin <= (t0 + deadline) + AUDIT_TOL  # true completion: never strict-gated
    srv_take = active & use_off & ok
    acc_sum = acc_sum + jnp.where(srv_take, acc_sv[j_off, r_off], 0.0)
    proc = proc + srv_take.astype(jnp.int32)
    offl = offl + srv_take.astype(jnp.int32)
    miss = miss + (active & use_off & ~ok).astype(jnp.int32)
    netf = jnp.where(active & use_off, start + tup_t, netf)
    # observe_upload: sample = nbits / seconds; a dead link (t_up = inf)
    # still observes — sample 0.0 decays the belief, matching the reference.
    # The denominator goes through an optimization barrier: XLA's algebraic
    # simplifier otherwise cancels nbits / (nbits / bw) back to bw, skipping
    # the double rounding the reference performs (observed: device samples
    # came back exactly 800000.0 where the host gets 799999.9999999999 for
    # an 0.8 Mbps link; select- and multiply-gates both get reassociated
    # away, only the barrier holds).  The outer barrier stops the second
    # rewrite in the chain: beta * (nbits / d) -> (beta * nbits) / d, which
    # re-rounds the EWMA increment.
    sample = _barrier(jnp.where(tup_t > 0.0, nbits8[r_off] / _barrier(tup_t), 0.0))
    # The EWMA increments are adds of two products — both must round to f64
    # before the add, so both go through _no_fma selects, and the two selects
    # MUST gate on *different* (not provably equal) predicates.  With a shared
    # predicate, LLVM instcombine folds add(select(p,a,x), select(p,b,y)) into
    # select(p, a+b, x+y) and then contracts one mul into an fma; with the
    # surrounding update-select's own predicate, XLA drops the redundant inner
    # select instead.  ``rounded``/``rounded2`` are distinct always-true
    # comparisons of the same traced value, opaque to both rewrites.
    upd = active & use_off & (tup_t > 0.0)  # the <=0 guard (never real here)
    bps = jnp.where(
        upd,
        _no_fma(omb * bps, rounded) + _no_fma(beta * sample, rounded2),
        bps,
    )
    updr = active & use_off  # observe_rtt has no guard
    rttb = jnp.where(
        updr,
        _no_fma(omb * rttb, rounded) + _no_fma(beta * rtt, rounded2),
        rttb,
    )
    return bps, rttb, netf, acc_sum, proc, miss, offl


# ---------------------------------------------------------------------------
# Max-Accuracy online: the sim_batch program's planning phase against the
# carried belief, then the true-execution fold.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _online_accuracy_program(W: int, NBINS: int, S: int, J: int, R: int, strict: bool):
    def one(gamma, deadline, rtt, grid, beta, omb, pess, bps0, n_active, n_frames,
            arr0, dl0, arr1, dl1, dur, arrivals, acc_stat,
            nbits8, acc_sv, seg_head, t_up_seg, t_srv, acc_dp, t_npu64):
        ks = jnp.arange(W, dtype=jnp.int32)

        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, bps, rttb, netf, acc_sum, proc, miss, offl, rounds, npu_s = c
            active = head < n_frames
            rounded = n_frames > 0  # traced, always true: _no_fma's gate
            rounded2 = n_frames > -1  # distinct gate: see _true_offload
            t0 = _no_fma(head.astype(jnp.float64) * gamma, rounded)
            npu_free = jnp.maximum(0.0, busy - t0)
            start_bin = jnp.ceil(jnp.maximum(npu_free, 0.0) / grid).astype(jnp.int32)
            # estimator.state(): the belief, not a trace lookup.
            bw_b = bps * pess
            t_up = jnp.where(bw_b > 0.0, nbits8 / bw_b, jnp.inf)  # [R]
            budget = deadline - t_up - rttb  # [R] believed RTT
            fits = t_srv[:, None] <= budget[None, :]  # [J, R]
            a_cand = jnp.where(fits, acc_sv, -jnp.inf)
            j_best = jnp.argmax(a_cand, axis=0).astype(jnp.int32)  # first max
            a_best = jnp.max(a_cand, axis=0)
            r_ok = (budget > 0.0) & jnp.any(fits, axis=0)
            n_l = jnp.floor(jnp.where(r_ok, t_up, 0.0) / gamma)
            n_l = jnp.clip(n_l, 0, W).astype(jnp.int32)  # [R]
            cho1, par1, mh1, ab1, alive1 = _accuracy_dp64(
                dur, acc_dp, arr1, dl1, start_bin, n_frames=W, nbins=NBINS
            )
            nlm1 = jnp.clip(n_l - 1, 0, W - 1)
            nb1 = jnp.ceil(
                (gamma + _no_fma((n_l.astype(jnp.float64) - 1.0) * gamma, rounded)
                 + deadline) / grid
            ).astype(jnp.int32) + 2
            dp_ok = jnp.where(n_l == 0, True, alive1[nlm1] & (start_bin < nb1))
            dp_tot = jnp.where(n_l == 0, 0.0, mh1[nlm1])
            feas = r_ok & dp_ok
            norm = jnp.where(feas, (a_best + dp_tot) / (n_l + 1).astype(jnp.float64), NEG)
            r_star = jnp.argmax(norm).astype(jnp.int32)  # first max = lowest r
            off_exists = feas[r_star]
            off_norm = norm[r_star]

            cho0, par0, mh0, ab0, alive0 = _accuracy_dp64(
                dur, acc_dp, arr0, dl0, start_bin, n_frames=W, nbins=NBINS
            )
            A = jnp.sum((alive0 & (ks < n_active)).astype(jnp.int32), dtype=jnp.int32)
            nb0 = jnp.ceil(
                (_no_fma((A.astype(jnp.float64) - 1.0) * gamma, rounded) + deadline)
                / grid
            ).astype(jnp.int32) + 2
            loc_exists = (A >= 1) & (start_bin < nb0)
            loc_norm = jnp.where(
                loc_exists, mh0[jnp.clip(A - 1, 0, W - 1)] / A.astype(jnp.float64), NEG
            )
            use_loc = loc_exists & (loc_norm > jnp.where(off_exists, off_norm, NEG))
            use_off = off_exists & ~use_loc

            nn = jnp.where(use_off, n_l[r_star], jnp.where(use_loc, A, 0))

            def backtrack(cho, par, b0, upto):
                def bt(b, k):
                    on = k < upto
                    bc = jnp.clip(b, 0, NBINS - 1)
                    pick = jnp.where(on, cho[k, bc], -1)
                    return jnp.where(on & (pick >= 0), par[k, bc], b), pick

                _, picks_rev = jax.lax.scan(
                    bt, b0, jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
                )
                return picks_rev[::-1]

            picks_off = backtrack(cho1, par1, ab1[nlm1[r_star]], jnp.where(use_off, nn, 0))
            picks_loc = backtrack(cho0, par0, ab0[jnp.clip(A - 1, 0, W - 1)],
                                  jnp.where(use_loc, nn, 0))
            picks = jnp.where(use_off, picks_off, picks_loc)

            # True-world execution of the head offload (decision order:
            # SERVER first, then the NPU frames of the audit fold).
            bps, rttb, netf, acc_sum, proc, miss, offl = _true_offload(
                active=active, use_off=use_off, r_off=r_star, j_off=j_best[r_star],
                head=head, t0=t0, deadline=deadline, rtt=rtt, beta=beta, omb=omb,
                bps=bps, rttb=rttb, netf=netf, acc_sum=acc_sum, proc=proc,
                miss=miss, offl=offl, nbits8=nbits8, acc_sv=acc_sv,
                seg_head=seg_head, t_up_seg=t_up_seg, t_srv=t_srv, rounded=rounded,
                rounded2=rounded2,
            )

            fa = jnp.where(use_off, gamma, 0.0)
            gate = active & (picks >= 0) & (ks < nn)
            free0 = jnp.maximum(npu_free, 0.0)
            free_end, acc_sum, proc, miss, npu_s = _audit_scan(
                head=head, frame_offset=jnp.where(use_off, 1, 0),
                n_frames=n_frames, n_active=n_active, arrivals=fa + arrivals,
                deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat,
                picks=picks, gate=gate, free0=free0, acc_sum=acc_sum,
                proc=proc, miss=miss, npu_s=npu_s, W=W, J=J, strict=strict,
            )
            busy_until = jnp.where(use_off | use_loc, free_end, npu_free)
            horizon = jnp.where(
                use_off, n_l[r_star] + 1, jnp.where(use_loc, A, 1)
            ).astype(jnp.int32)
            head = jnp.where(active, head + horizon, head)
            busy = jnp.where(active, t0 + busy_until, busy)
            rounds = rounds + active.astype(jnp.int32)
            return head, busy, bps, rttb, netf, acc_sum, proc, miss, offl, rounds, npu_s

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            bps0, rtt,  # belief seeds: init_bps and the pre-loop observe_rtt
            jnp.zeros((), jnp.float64),  # true-link occupancy
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[5], out[6], out[7], out[9], out[10], out[8], out[2]

    return LaneProgram(one, (0,) * 21 + (None,) * 3)


@_online("max_accuracy")
def _run_online_max_accuracy(models, scenarios, strict):
    t_srv = np.array([m.t_server for m in models], np.float64)
    acc_dp = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float64
    )

    def run_group(key, group):
        W, R = key
        c = _common(models, group, W)
        grid = np.array([float(s.params["grid"]) for s in group], np.float64)
        arr0 = np.ceil(c.arrivals / grid[:, None]).astype(np.int32)
        dl0 = np.floor((c.arrivals + c.deadline[:, None]) / grid[:, None]).astype(np.int32)
        arrivals1 = c.gamma[:, None] + c.arrivals
        arr1 = np.ceil(arrivals1 / grid[:, None]).astype(np.int32)
        dl1 = np.floor((arrivals1 + c.deadline[:, None]) / grid[:, None]).astype(np.int32)
        horizon_t = c.gamma + (c.n_active.astype(np.float64) - 1.0) * c.gamma + c.deadline
        NBINS = _quant_bins(int((np.ceil(horizon_t / grid) + 2).max()))
        with np.errstate(invalid="ignore"):
            dur_f = np.ceil(c.t_npu64[None, :] / grid[:, None])
        dur = np.where(np.isfinite(dur_f), np.minimum(dur_f, NBINS), NBINS).astype(np.int32)
        nbits8, acc_sv = _offload_tables(models, group)
        rtt, seg_head, t_up_seg, S = _net_arrays(group, c.gamma, nbits8)
        beta, omb, pess, bps0 = _estimator_arrays(group)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _online_accuracy_program(c.W, NBINS, S, c.J, R, strict)(
                c.gamma, c.deadline, rtt, grid, beta, omb, pess, bps0,
                c.n_active, c.n_frames, arr0, dl0, arr1, dl1, dur,
                c.arrivals, c.acc_stat64, nbits8, acc_sv, seg_head, t_up_seg,
                t_srv, acc_dp, c.t_npu64,
            )
            out = [np.asarray(a) for a in out]
        stats = _collect(c, out[:5], time.perf_counter() - t0, offloaded=out[5])
        return _with_meta(stats, out[6], pess)

    return _stitch(scenarios, _net_group_key, run_group)


# ---------------------------------------------------------------------------
# Max-Utility online: same substitution on the sim_batch utility program,
# keeping its fast-width pass + overflow-lane rerun at the exact cap.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _online_utility_program(W: int, S: int, J: int, R: int, strict: bool, width: int):
    def one(gamma, deadline, rtt, alpha, fps, beta, omb, pess, bps0, n_w, n_frames,
            arrivals, acc_stat, nbits8, acc_sv, seg_head, t_up_seg, t_srv, acc_dp, t_npu64):
        ks = jnp.arange(W, dtype=jnp.int32)

        def backtrack(u_final, parents, actions):
            slot0 = jnp.argmax(u_final).astype(jnp.int32)

            def bt(s, k):
                ok = s >= 0
                sc = jnp.clip(s, 0, width - 1)
                pick = jnp.where(ok, actions[k, sc], -1)
                return jnp.where(ok, parents[k, sc], s), pick

            _, picks_rev = jax.lax.scan(
                bt, slot0, jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
            )
            return picks_rev[::-1]

        def cand_stats(picks, acc0):
            def f(carry, pick):
                n, a = carry
                takes = pick >= 0
                j = jnp.clip(pick, 0, J - 1)
                return (
                    n + takes.astype(jnp.int32),
                    a + jnp.where(takes, acc_stat[j], 0.0),
                ), None

            (n, a), _ = jax.lax.scan(f, (jnp.int32(0), acc0), picks)
            return n, a

        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, bps, rttb, netf, acc_sum, proc, miss, offl, rounds, npu_s, ovf = c
            active = head < n_frames
            rounded = n_frames > 0  # traced, always true: _no_fma's gate
            rounded2 = n_frames > -1  # distinct gate: see _true_offload
            t0 = _no_fma(head.astype(jnp.float64) * gamma, rounded)
            npu_free = jnp.maximum(0.0, busy - t0)
            # estimator.state(): the belief, not a trace lookup.
            bw_b = bps * pess
            t_up = jnp.where(bw_b > 0.0, nbits8 / bw_b, jnp.inf)  # [R]
            feas = (t_up[:, None] + t_srv[None, :] + rttb) <= deadline  # [R, J]
            rate = jnp.minimum(1.0 / jnp.maximum(t_up, 1e-9), fps)
            score = rate[:, None] + _no_fma(
                alpha * jnp.swapaxes(acc_sv, 0, 1), rounded
            )  # [R, J]
            flat = jnp.where(feas, score, -jnp.inf).reshape(-1)
            off_exists = jnp.any(feas)
            pick_rj = jnp.argmax(flat).astype(jnp.int32)
            r0 = pick_rj // J
            j0 = pick_rj - r0 * J
            t_up0 = jnp.where(off_exists, t_up[r0], 0.0)
            n_l = jnp.clip(jnp.floor(t_up0 / gamma), 0, W).astype(jnp.int32)
            n_plan = jnp.maximum(n_l, n_w - 1)
            win1 = jnp.maximum(jnp.maximum(n_plan, 1).astype(jnp.float64) * gamma, gamma)
            (_, u1, _, _), par1, act1, ov1 = _utility_dp64(
                t_npu64, acc_dp, n_plan, n_frames=W, width=width,
                gamma=gamma, deadline=deadline, alpha=alpha, npu_free=npu_free,
                first_arrival=gamma, window=win1,
            )
            win2 = jnp.maximum(n_w.astype(jnp.float64) * gamma, gamma)
            (_, u2, _, _), par2, act2, ov2 = _utility_dp64(
                t_npu64, acc_dp, n_w, n_frames=W, width=width,
                gamma=gamma, deadline=deadline, alpha=alpha, npu_free=npu_free,
                first_arrival=jnp.float64(0.0), window=win2,
            )
            ovf = ovf | (active & (ov1 | ov2))
            picks1 = backtrack(u1, par1, act1)
            picks2 = backtrack(u2, par2, act2)
            srv_acc = acc_sv[j0, r0]
            n1, a_off = cand_stats(picks1, srv_acc)
            n2, a_loc = cand_stats(picks2, jnp.float64(0.0))
            p_off = (n1 + 1).astype(jnp.float64)
            h_off = jnp.maximum(n_plan + 1, 1).astype(jnp.float64)
            u_off = jnp.where(
                off_exists, p_off / (h_off * gamma) + alpha * a_off / p_off, NEG
            )
            u_loc = jnp.where(
                n2 > 0,
                n2.astype(jnp.float64) / (n_w.astype(jnp.float64) * gamma)
                + alpha * a_loc / n2.astype(jnp.float64),
                0.0,
            )
            use_off = off_exists & (u_off >= u_loc)  # first candidate wins ties
            use_loc = ~use_off & (n2 > 0)

            nn = jnp.where(use_off, n_plan, jnp.where(use_loc, n_w, 0))
            picks = jnp.where(use_off, picks1, picks2)

            bps, rttb, netf, acc_sum, proc, miss, offl = _true_offload(
                active=active, use_off=use_off, r_off=r0, j_off=jnp.clip(j0, 0, J - 1),
                head=head, t0=t0, deadline=deadline, rtt=rtt, beta=beta, omb=omb,
                bps=bps, rttb=rttb, netf=netf, acc_sum=acc_sum, proc=proc,
                miss=miss, offl=offl, nbits8=nbits8, acc_sv=acc_sv,
                seg_head=seg_head, t_up_seg=t_up_seg, t_srv=t_srv, rounded=rounded,
                rounded2=rounded2,
            )

            fa = jnp.where(use_off, gamma, 0.0)
            gate = active & (picks >= 0) & (ks < nn)
            free0 = jnp.maximum(npu_free, 0.0)
            free_end, acc_sum, proc, miss, npu_s = _audit_scan(
                head=head, frame_offset=jnp.where(use_off, 1, 0),
                n_frames=n_frames, n_active=n_w, arrivals=fa + arrivals,
                deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat,
                picks=picks, gate=gate, free0=free0, acc_sum=acc_sum,
                proc=proc, miss=miss, npu_s=npu_s, W=W, J=J, strict=strict,
            )
            busy_until = jnp.where(use_off | use_loc, free_end, npu_free)
            horizon = jnp.where(
                use_off, n_plan + 1, jnp.where(use_loc, n_w, 1)
            ).astype(jnp.int32)
            head = jnp.where(active, head + horizon, head)
            busy = jnp.where(active, t0 + busy_until, busy)
            rounds = rounds + active.astype(jnp.int32)
            return head, busy, bps, rttb, netf, acc_sum, proc, miss, offl, rounds, npu_s, ovf

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            bps0, rtt,  # belief seeds: init_bps and the pre-loop observe_rtt
            jnp.zeros((), jnp.float64),  # true-link occupancy
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), bool),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[5], out[6], out[7], out[9], out[10], out[8], out[2], out[11]

    return LaneProgram(one, (0,) * 17 + (None,) * 3)


@_online("max_utility")
def _run_online_max_utility(models, scenarios, strict):
    t_srv = np.array([m.t_server for m in models], np.float64)
    acc_dp = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float64
    )

    def run_group(key, group):
        W, R = key
        c = _common(models, group, W)
        alpha = np.array([float(s.params["alpha"]) for s in group], np.float64)
        fps = np.array([s.stream.fps for s in group], np.float64)
        nbits8, acc_sv = _offload_tables(models, group)
        rtt, seg_head, t_up_seg, S = _net_arrays(group, c.gamma, nbits8)
        beta, omb, pess, bps0 = _estimator_arrays(group)
        lane_args = (c.gamma, c.deadline, rtt, alpha, fps, beta, omb, pess, bps0,
                     c.n_active, c.n_frames, c.arrivals, c.acc_stat64,
                     nbits8, acc_sv, seg_head, t_up_seg)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _online_utility_program(c.W, S, c.J, R, strict, _UTIL_FAST_WIDTH)(
                *lane_args, t_srv, acc_dp, c.t_npu64,
            )
            out = [np.array(a) for a in out]
            overflowed = np.nonzero(out[7])[0]
            if overflowed.size:
                # A Pareto front outgrew the fast width in these lanes: rerun
                # just them at the reference prune cap and splice back.
                sub = _online_utility_program(c.W, S, c.J, R, strict, _UTIL_CAP)(
                    *(a[overflowed] for a in lane_args), t_srv, acc_dp, c.t_npu64,
                )
                for dst, src in zip(out[:7], sub[:7]):
                    dst[overflowed] = np.asarray(src)
        stats = _collect(c, out[:5], time.perf_counter() - t0, offloaded=out[5])
        return _with_meta(stats, out[6], pess)

    return _stitch(scenarios, _net_group_key, run_group)
