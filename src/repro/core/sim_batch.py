"""Vectorized fleet-sweep backend: whole scenario grids as ONE tensor program.

The reference simulator (``simulator.simulate``) replays one stream at a
time in a Python event loop — every figure sweep pays interpreter cost per
frame per grid point.  This module executes and audits the same round plans
for a *batch* of scenarios (bandwidth × deadline × fps × policy-param grid
points) as a single jit+vmap program: per scenario, a ``lax.while_loop``
over scheduling rounds whose body (a) runs the policy's jitted DP
(:mod:`repro.core.jax_sched`), (b) backtracks the argmax schedule, and
(c) applies the shared audit contract of :mod:`repro.core.audit` — all on
device, returning per-scenario :class:`~repro.core.schedule.StreamStats`
tensors (accuracy sum, processed/missed counts, NPU occupancy).

Exactness contract (golden-tested in ``tests/test_sim_batch.py``): for every
scenario in the batch, the returned stats are **bit-identical** to
``simulate(PolicySpec(name, params).build(), ...)`` — same bin
discretization, same f32 DP recurrences, same f64 audit arithmetic in the
same order.  Three mechanisms make that possible:

  * every host-side quantity the reference computes in float64 (bin edges,
    arrival times, windows, f32 casts of policy params) is precomputed here
    with the identical numpy expressions;
  * the only round-coupled quantity, ``npu_free``, is carried on device in
    float64 — the module runs its programs inside ``jax.enable_x64`` and
    the DP kernels pin their own dtypes so the f32 recurrences do not
    silently widen;
  * fixed shapes come from *padding*, never truncation: windows pad to the
    batch-max frame count ``W`` (padded frames are identity no-ops in the
    kernels) and the Max-Accuracy time grid pads to the batch-max bin count
    (padded bins provably stay ``NEG`` and cannot enter any argmax).

Policies registered with ``batched=True`` have a planner here; ``Session
.run_sweep`` falls back to the reference loop for everything else.  Two
planner families exist:

  * the local-plan jitted DPs ``jax_accuracy`` / ``jax_utility`` — their
    plans never offload, so ``frames_offloaded`` is always 0 and no network
    state is consulted;
  * the paper's own ``max_accuracy`` / ``max_utility`` heuristics — these
    are *network-aware*: each scenario carries an on-device network model
    (``BatchScenario.rtt`` plus piecewise-constant bandwidth segments),
    every round looks the bandwidth up at its start time exactly as the
    reference calls ``trace.at(t0)``, and the round program renders the
    offload phase (per-resolution upload times, feasible-server-model
    argmax, normalized-score candidate selection) as array expressions
    around the f64 DP twins of :mod:`repro.core.jax_sched`.  Segment
    arrays pad to the batch maximum with ``t_start = +inf`` sentinels,
    which a right-bisecting step lookup can provably never select.

Their equivalence scope differs: the jax_* planners are bit-identical to
the reference by construction (same f32 kernels), while the network-aware
planners replay float64 Python references — the certified contract is
integer stats exact and accuracy sums within :data:`~repro.core.audit
.AUDIT_TOL` (in practice the golden grids come out bit-equal too; see
docs/simulation.md).
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
from .bucketing import quant_pow2 as _quant_pow2
from .bucketing import quant_w as _quant_w
from .jax_sched import (
    NEG,
    _accuracy_dp,
    _accuracy_dp64,
    _no_fma,
    _utility_dp,
    _utility_dp64,
)
from .profiles import ModelProfile, StreamSpec
from .registry import get_policy
from .schedule import StreamStats
from .sweep_shard import LaneProgram
from .tracking import WorkloadSpec, interval_means, retention, retention_powers

__all__ = ["BatchScenario", "batched_policies", "simulate_batch"]


@dataclass(frozen=True)
class BatchScenario:
    """One grid point as the batched backend sees it: a stream shape, a frame
    budget, the policy's *resolved* parameter dict (defaults filled in, e.g.
    ``PolicySpec(...).resolved``), and the on-device network model.

    ``bw_segments`` is the piecewise-constant bandwidth trace as sorted
    ``(t_start_s, bandwidth_bps)`` segments — a constant trace is a single
    segment at ``t_start = 0``; before the first segment's start the first
    value applies (``simulator.Trace.piecewise`` semantics).  The local-only
    ``jax_*`` planners never consult the network; the network-aware
    ``max_accuracy`` / ``max_utility`` planners look bandwidth up at every
    round's start time.

    ``workload`` is the executor's world truth (``tracking.WorkloadSpec``):
    the ``track_*`` planners require ``kind="track"`` and score tracked
    frames with its decay curve; the classification planners require the
    default ``kind="classify"``."""

    stream: StreamSpec = field(default_factory=StreamSpec)
    n_frames: int = 120
    params: Mapping[str, Any] = field(default_factory=dict)
    rtt: float = 0.100
    bw_segments: tuple[tuple[float, float], ...] = ((0.0, 2.5e6),)
    workload: WorkloadSpec = field(default_factory=WorkloadSpec)


_PLANNERS: dict[str, Callable[..., list[StreamStats]]] = {}


def _planner(name: str):
    def deco(fn):
        _PLANNERS[name] = fn
        return fn

    return deco


def batched_policies() -> tuple[str, ...]:
    """Policy names this backend can execute (mirrors ``batched=True`` in the
    registry; ``tests/test_sim_batch.py`` asserts the two stay in sync)."""
    return tuple(sorted(_PLANNERS))


def simulate_batch(
    policy: str,
    models: Sequence[ModelProfile],
    scenarios: Sequence[BatchScenario],
    *,
    strict: bool = True,
) -> list[StreamStats]:
    """Run ``policy`` over every scenario in one compiled program.

    Returns one audited :class:`StreamStats` per scenario, in order,
    bit-identical to the reference ``simulate`` loop.  Raises ``ValueError``
    for policies without a batched planner — callers that want a silent
    fallback should route through ``Session.run_sweep`` instead.
    """
    fn = _PLANNERS.get(policy)
    if fn is None:
        raise ValueError(
            f"policy {policy!r} has no batched backend; available: {batched_policies()}"
        )
    entry = get_policy(policy)
    for s in scenarios:
        if s.workload.kind not in entry.workloads:
            raise ValueError(
                f"policy {policy!r} plans {'/'.join(entry.workloads)} workloads, "
                f"not {s.workload.kind!r}"
            )
    if not scenarios:
        return []
    return fn(list(models), list(scenarios), bool(strict))


# ---------------------------------------------------------------------------
# Shared host-side precomputation (float64 numpy — mirrors the reference
# wrappers in jax_sched expression by expression).
# ---------------------------------------------------------------------------


def _window_frames(stream: StreamSpec, params: Mapping[str, Any]) -> int:
    """Mirror of the plan-round wrappers' window choice."""
    wf = params.get("window_frames")
    if wf is not None:
        return int(wf)
    return max(int(np.floor(stream.deadline / stream.gamma)), 1)


# Scenario grouping: one monolithic batch would force every lane to pay the
# batch-max window, bin count, AND round count (a vmapped while_loop runs
# until the deepest lane finishes).  Scenarios are instead partitioned into
# shape-homogeneous groups keyed on *quantized* shapes — the shared
# bucketing policy lives in :mod:`repro.core.bucketing` (window ladder, bin
# quanta, pow2 pads; never-shrink/monotone/idempotent, hypothesis-tested) —
# which bounds in-group padding waste by ~2x while keeping the jit cache
# small and stable across sweeps AND making repeated sweeps hit the
# persistent compilation cache (see repro.core.compile_cache).  Padding is
# provably inert (see module docstring), so the partition cannot change any
# result — only wall-clock.


def _stitch(scenarios, key_fn, run_group) -> list[StreamStats]:
    """Partition ``scenarios`` by ``key_fn``, run each group, reassemble in
    the original order."""
    groups: dict[Any, list[int]] = {}
    for i, s in enumerate(scenarios):
        groups.setdefault(key_fn(s), []).append(i)
    stats: list[StreamStats | None] = [None] * len(scenarios)
    for key in sorted(groups):
        idx = groups[key]
        for i, st in zip(idx, run_group(key, [scenarios[i] for i in idx])):
            stats[i] = st
    return stats  # type: ignore[return-value]


@dataclass
class _Common:
    """Per-group arrays shared by both planners."""

    B: int
    J: int
    W: int  # padded window (quantized group maximum)
    n_active: np.ndarray  # [B] i32 real window per scenario
    gamma: np.ndarray  # [B] f64
    deadline: np.ndarray  # [B] f64
    n_frames: np.ndarray  # [B] i32
    arrivals: np.ndarray  # [B, W] f64, k * gamma
    t_npu64: np.ndarray  # [J] f64 (inf for server-only models)
    acc_dp32: np.ndarray  # [J] f32 — the DP's accuracy table (raw max key)
    acc_stat64: np.ndarray  # [B, J] f64 — audit accuracy at the stream's r_max


def _common(
    models: list[ModelProfile], scenarios: list[BatchScenario], W: int | None = None
) -> _Common:
    B, J = len(scenarios), len(models)
    n_active = np.array([_window_frames(s.stream, s.params) for s in scenarios], np.int32)
    W = int(n_active.max()) if W is None else int(W)
    gamma = np.array([s.stream.gamma for s in scenarios], np.float64)
    deadline = np.array([s.stream.deadline for s in scenarios], np.float64)
    n_frames = np.array([s.n_frames for s in scenarios], np.int32)
    arrivals = np.arange(W, dtype=np.float64)[None, :] * gamma[:, None]
    t_npu64 = np.array([m.t_npu for m in models], np.float64)
    acc_dp32 = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float32
    )
    acc_stat64 = np.array(
        [[m.accuracy(s.stream.r_max, where="npu") for m in models] for s in scenarios],
        np.float64,
    )
    return _Common(B, J, W, n_active, gamma, deadline, n_frames, arrivals,
                   t_npu64, acc_dp32, acc_stat64)


def _collect(
    c: _Common, out, wall_s: float, offloaded: np.ndarray | None = None
) -> list[StreamStats]:
    acc_sum, proc, miss, rounds, npu_busy = (np.asarray(a) for a in out)
    if offloaded is None:
        offloaded = np.zeros(c.B, np.int32)  # local-only planners never offload
    # The whole group schedules in one device program; apportion its wall
    # time by round count so schedule_time/schedule_calls stays the honest
    # amortized per-round cost (what figure rows report as us_per_call).
    total_rounds = max(int(rounds.sum()), 1)
    return [
        StreamStats(
            frames_total=int(c.n_frames[b]),
            frames_processed=int(proc[b]),
            frames_missed_deadline=int(miss[b]),
            frames_offloaded=int(offloaded[b]),
            accuracy_sum=float(acc_sum[b]),
            elapsed=float(c.n_frames[b] * c.gamma[b]),
            schedule_calls=int(rounds[b]),
            schedule_time=wall_s * float(rounds[b]) / total_rounds,
            npu_busy_s=float(npu_busy[b]),
        )
        for b in range(c.B)
    ]


def _audit_scan(*, head, n_frames, n_active, arrivals, deadline, t_npu64, acc_stat,
                picks, gate, free0, acc_sum, proc, miss, npu_s, W, J, strict,
                frame_offset=0):
    """On-device rendering of the :mod:`repro.core.audit` contract for the
    NPU frames of a round: sequential f64 fold over the (padded) window in
    frame order, so accuracy accumulates exactly as the reference loop's
    repeated ``+=``.  ``gate[k]`` says whether frame ``k`` really executes;
    ``frame_offset`` is the plan-frame id of DP frame 0 (1 when the round's
    head frame offloaded — the offload phase accounts it before this scan,
    preserving decision order)."""

    def au(carry, xs):
        free, a_s, pr, ms, nb = carry
        k, pick, act = xs
        j = jnp.clip(pick, 0, J - 1)
        arr_k = arrivals[k]
        start = jnp.maximum(free, arr_k)
        finish = start + t_npu64[j]
        if strict:
            bad = act & (finish > (arr_k + deadline) + AUDIT_TOL)
        else:
            bad = jnp.zeros_like(act)
        in_range = (head + frame_offset + k) < n_frames
        take = act & (~bad) & in_range
        a_s = a_s + jnp.where(take, acc_stat[j], 0.0)
        pr = pr + take.astype(jnp.int32)
        ms = ms + bad.astype(jnp.int32)  # missed counts even past-stream frames
        nb = nb + jnp.where(act, t_npu64[j], 0.0)
        free = jnp.where(act, finish, free)
        return (free, a_s, pr, ms, nb), None

    ks = jnp.arange(W, dtype=jnp.int32)
    carry, _ = jax.lax.scan(au, (free0, acc_sum, proc, miss, npu_s), (ks, picks, gate))
    return carry


# ---------------------------------------------------------------------------
# jax_accuracy: Max-Accuracy local DP over a (padded) time-bin grid.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _accuracy_program(W: int, NBINS: int, J: int, strict: bool):
    def one(gamma, deadline, grid, n_active, nbins_real, n_frames,
            arr_bins, dl_bins, dur, arrivals, acc_stat, t_npu64, acc_dp32):
        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, acc_sum, proc, miss, rounds, npu_s = c
            active = head < n_frames  # lane gating under vmap-of-while
            t0 = head.astype(jnp.float64) * gamma
            npu_free = jnp.maximum(0.0, busy - t0)
            # Reference: int(np.ceil(max(npu_free, 0.0) / grid)), clipped to
            # the scenario's REAL bin count (not the padded one) — the clip
            # target is observable when npu_free overruns the horizon.
            start_bin = jnp.ceil(jnp.maximum(npu_free, 0.0) / grid).astype(jnp.int32)
            start_bin = jnp.clip(start_bin, 0, nbins_real - 1)
            H, choices, parents = _accuracy_dp(
                dur, acc_dp32, arr_bins, dl_bins, start_bin, n_active,
                n_frames=W, nbins=NBINS,
            )
            feasible = jnp.max(H) > NEG / 2
            b0 = jnp.argmax(H).astype(jnp.int32)

            def bt(b, k):
                bc = jnp.clip(b, 0, NBINS - 1)
                pick = choices[k, bc]
                return jnp.where(pick >= 0, parents[k, bc], b), pick

            _, picks_rev = jax.lax.scan(
                bt, b0, jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
            )
            picks = picks_rev[::-1]

            gate = active & feasible & (jnp.arange(W, dtype=jnp.int32) < n_active)
            free0 = jnp.maximum(npu_free, 0.0)
            free_end, acc_sum, proc, miss, npu_s = _audit_scan(
                head=head, n_frames=n_frames, n_active=n_active, arrivals=arrivals,
                deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat, picks=picks,
                gate=gate, free0=free0, acc_sum=acc_sum, proc=proc, miss=miss,
                npu_s=npu_s, W=W, J=J, strict=strict,
            )
            # Infeasible window: the reference emits a horizon-1 SKIP round
            # that leaves the NPU carry untouched.
            busy_until = jnp.where(feasible, free_end, npu_free)
            horizon = jnp.where(feasible, n_active, 1)
            head = jnp.where(active, head + horizon, head)
            busy = jnp.where(active, t0 + busy_until, busy)
            rounds = jnp.where(active, rounds + 1, rounds)
            return head, busy, acc_sum, proc, miss, rounds, npu_s

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float64),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[5], out[6]

    return LaneProgram(one, (0,) * 11 + (None,) * 2)


@_planner("jax_accuracy")
def _run_accuracy(models, scenarios, strict):
    def run_group(W, group):
        c = _common(models, group, W)
        grid = np.array([float(s.params["grid"]) for s in group], np.float64)
        # Bin arithmetic in f64 on the host — the same numpy expressions as
        # local_accuracy_dp_jax, vectorized over the batch.
        arr_bins = np.ceil(c.arrivals / grid[:, None]).astype(np.int32)
        dl_bins = np.floor((c.arrivals + c.deadline[:, None]) / grid[:, None]).astype(np.int32)
        horizon_t = (c.n_active.astype(np.float64) - 1.0) * c.gamma + c.deadline
        nbins_real = (np.ceil(horizon_t / grid) + 2).astype(np.int32)
        NBINS = _quant_bins(int(nbins_real.max()))
        # inf (server-only) and over-horizon durations clamp to NBINS: both
        # are unreachable in-bin exactly as the reference's raw values are.
        with np.errstate(invalid="ignore"):
            dur_f = np.ceil(c.t_npu64[None, :] / grid[:, None])
        dur = np.where(np.isfinite(dur_f), np.minimum(dur_f, NBINS), NBINS).astype(np.int32)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _accuracy_program(c.W, NBINS, c.J, strict)(
                c.gamma, c.deadline, grid, c.n_active, nbins_real, c.n_frames,
                arr_bins, dl_bins, dur, c.arrivals, c.acc_stat64,
                c.t_npu64, c.acc_dp32,
            )
            out = [np.asarray(a) for a in out]
        return _collect(c, out, time.perf_counter() - t0)

    return _stitch(
        scenarios, lambda s: _quant_w(_window_frames(s.stream, s.params)), run_group
    )


# ---------------------------------------------------------------------------
# jax_utility: Max-Utility Pareto-front DP (skips allowed).
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _utility_program(W: int, width: int, J: int, strict: bool):
    def one(gamma, deadline, n_active, n_frames, g32, d32, a32, w32,
            arrivals, acc_stat, t_npu64, t_npu32, acc_dp32):
        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, acc_sum, proc, miss, rounds, npu_s = c
            active = head < n_frames
            t0 = head.astype(jnp.float64) * gamma
            npu_free = jnp.maximum(0.0, busy - t0)
            (_, u, _, _), parents, actions, _ = _utility_dp(
                t_npu32, acc_dp32, n_active,
                n_frames=W, width=width, gamma=g32, deadline=d32, alpha=a32,
                npu_free=npu_free.astype(jnp.float32),
                first_arrival=jnp.float32(0.0), window=w32,
            )
            slot0 = jnp.argmax(u).astype(jnp.int32)

            def bt(s, k):
                ok = s >= 0
                sc = jnp.clip(s, 0, width - 1)
                pick = jnp.where(ok, actions[k, sc], -1)
                return jnp.where(ok, parents[k, sc], s), pick

            _, picks_rev = jax.lax.scan(
                bt, slot0, jnp.arange(W - 1, -1, -1, dtype=jnp.int32)
            )
            picks = picks_rev[::-1]

            gate = active & (picks >= 0)  # only picked frames execute; rest SKIP
            free0 = jnp.maximum(npu_free, 0.0)
            free_end, acc_sum, proc, miss, npu_s = _audit_scan(
                head=head, n_frames=n_frames, n_active=n_active, arrivals=arrivals,
                deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat, picks=picks,
                gate=gate, free0=free0, acc_sum=acc_sum, proc=proc, miss=miss,
                npu_s=npu_s, W=W, J=J, strict=strict,
            )
            head = jnp.where(active, head + n_active, head)  # horizon is always n
            busy = jnp.where(active, t0 + free_end, busy)
            rounds = jnp.where(active, rounds + 1, rounds)
            return head, busy, acc_sum, proc, miss, rounds, npu_s

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float64),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[5], out[6]

    return LaneProgram(one, (0,) * 10 + (None,) * 3)


@_planner("jax_utility")
def _run_utility(models, scenarios, strict):
    # ``width`` is a compiled Pareto-front shape, so it joins the group key
    # (a width axis in a sweep grid costs one compile per distinct value).
    def run_group(key, group):
        W, width = key
        c = _common(models, group, W)
        alpha = np.array([float(s.params["alpha"]) for s in group], np.float64)
        # The f32 casts the reference wrapper performs, precomputed in bulk.
        g32 = c.gamma.astype(np.float32)
        d32 = c.deadline.astype(np.float32)
        a32 = alpha.astype(np.float32)
        window = np.maximum(c.n_active.astype(np.float64) * c.gamma, c.gamma)
        w32 = window.astype(np.float32)
        t_npu32 = c.t_npu64.astype(np.float32)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _utility_program(c.W, width, c.J, strict)(
                c.gamma, c.deadline, c.n_active, c.n_frames,
                g32, d32, a32, w32, c.arrivals, c.acc_stat64,
                c.t_npu64, t_npu32, c.acc_dp32,
            )
            out = [np.asarray(a) for a in out]
        return _collect(c, out, time.perf_counter() - t0)

    return _stitch(
        scenarios,
        lambda s: (_quant_w(_window_frames(s.stream, s.params)), int(s.params["width"])),
        run_group,
    )


# ---------------------------------------------------------------------------
# Network-aware planners: the paper's Max-Accuracy / Max-Utility heuristics.
# Each round is the reference plan_round rendered as array expressions —
# bandwidth looked up at the round's start time, per-resolution upload
# times, feasible-server-model argmax, the f64 local-phase DP twins of
# jax_sched, and candidate selection on the reference's normalized scores —
# followed by the shared audit fold.  Host-side precomputation mirrors the
# reference expression by expression (frame bits, accuracy tables, bin
# edges), all in float64.
# ---------------------------------------------------------------------------

# max_utility._prune's cap: the width at which _utility_dp64's truncation
# coincides with the reference.  The planner first runs a narrow FAST width
# (the Pareto sort dominates kernel cost and scales ~width·log(width); real
# fronts hold a few dozen entries) and reruns only the lanes whose overflow
# flag reports a front outgrew it — exactness is never traded for speed.
_UTIL_CAP = 256
_UTIL_FAST_WIDTH = 64


def _trace_bw(bw_t: jax.Array, bw_v: jax.Array, t: jax.Array) -> jax.Array:
    """Bandwidth at time ``t``: the step function ``Trace.piecewise``
    defines — the last segment with ``t_start <= t`` wins, and before the
    first segment's start the first value applies.  Padded sentinel
    segments carry ``t_start = +inf``, so the right-bisection can provably
    never select them (any finite ``t`` bisects before every ``inf``)."""
    idx = jnp.searchsorted(bw_t, t, side="right") - 1
    return bw_v[jnp.clip(idx, 0, bw_t.shape[0] - 1)]


def segment_arrays(
    segs_list: Sequence[Sequence[tuple[float, float]]],
) -> tuple[np.ndarray, np.ndarray, int]:
    """Pad per-scenario ``(t_start, bps)`` segment lists into [B, S] tensors.

    The single definition of the on-device trace layout, shared with the
    fleet engine (``sim_multi_batch``): segments sort like
    ``Trace.piecewise``, S pads to the batch's power-of-two maximum, and
    sentinel entries carry ``t_start = +inf`` (never selectable by
    ``_trace_bw``'s right bisection) with the last real value repeated.
    """
    B = len(segs_list)
    clean = [
        sorted((float(t), float(v)) for t, v in segs) or [(0.0, 0.0)]
        for segs in segs_list
    ]
    S = _quant_pow2(max(len(segs) for segs in clean))
    bw_t = np.full((B, S), np.inf, np.float64)
    bw_v = np.zeros((B, S), np.float64)
    for i, segs in enumerate(clean):
        bw_t[i, : len(segs)] = [t for t, _ in segs]
        bw_v[i, : len(segs)] = [v for _, v in segs]
        bw_v[i, len(segs):] = segs[-1][1]
    return bw_t, bw_v, S


def segment_heads(bw_t: np.ndarray, gamma: np.ndarray) -> np.ndarray:
    """[B, S] int32: for each trace segment, the first round head ``h`` whose
    start time ``h * gamma`` — the reference's float64 product — reaches
    the segment's start; sentinel (``+inf``) segments never start.

    Decided on the host in IEEE float64, so on device the segment in force
    at a round is an integer comparison of ``head`` against these.  A
    device float compare would put a boundary that falls on a frame time
    (0.3 s at 30 fps) on either side, by the last bit of ``head * gamma``
    — and a TPU's emulated float64 does not round that product as IEEE."""
    g = np.asarray(gamma, np.float64)[:, None]
    fin = np.isfinite(bw_t)
    with np.errstate(invalid="ignore"):
        h = np.where(fin, np.maximum(np.ceil(bw_t / g), 0.0), 0.0)
    while True:  # ceil(t / gamma) is within an ulp-step of the product's answer
        down = fin & (h > 0) & ((h - 1.0) * g >= bw_t)
        up = fin & (h * g < bw_t)
        if not (down.any() or up.any()):
            break
        h = h - down + up
    return np.where(fin, h, np.iinfo(np.int32).max).astype(np.int32)


def _net_arrays(
    group: list[BatchScenario], gamma: np.ndarray, nbits8: np.ndarray
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Per-scenario network tensors: rtt [B], the first head of each trace
    segment [B, S] (:func:`segment_heads`), and each segment's upload time
    per offered resolution [B, S, R] — ``nbits / bandwidth``, the
    reference's ``upload_time``, divided on the host in IEEE float64."""
    bw_t, bw_v, S = segment_arrays([s.bw_segments for s in group])
    rtt = np.array([s.rtt for s in group], np.float64)
    bw = bw_v[:, :, None]
    with np.errstate(divide="ignore"):
        t_up = np.where(bw > 0.0, nbits8[:, None, :] / np.where(bw > 0.0, bw, 1.0), np.inf)
    return rtt, segment_heads(bw_t, gamma), t_up, S


def _upload_at_head(seg_head: jax.Array, t_up_seg: jax.Array, head: jax.Array) -> jax.Array:
    """Upload times [R] under the trace segment in force at round ``head``:
    the reference's ``trace.at(head * gamma).upload_time(...)``, found by
    integer head (the last segment whose first head is <= ``head``; before
    the first segment's start the first applies)."""
    idx = jnp.searchsorted(seg_head, head, side="right") - 1
    return t_up_seg[jnp.clip(idx, 0, seg_head.shape[0] - 1)]


def _offload_tables(
    models: list[ModelProfile], group: list[BatchScenario]
) -> tuple[np.ndarray, np.ndarray]:
    """Host-precomputed offload tables: frame payload bits [B, R] (the exact
    ``frame_bytes(r) * 8.0`` the reference feeds ``upload_time``) and server
    accuracy [B, J, R] at each scenario's offered resolutions."""
    nbits8 = np.array(
        [[s.stream.frame_bytes(r) * 8.0 for r in s.stream.resolutions] for s in group],
        np.float64,
    )
    acc_sv = np.array(
        [
            [[m.accuracy(r, where="server") for r in s.stream.resolutions] for m in models]
            for s in group
        ],
        np.float64,
    )
    return nbits8, acc_sv


def _net_group_key(s: BatchScenario) -> tuple[int, int]:
    return (_quant_w(_window_frames(s.stream, s.params)), len(s.stream.resolutions))


@lru_cache(maxsize=None)
def _max_accuracy_program(W: int, NBINS: int, S: int, J: int, R: int, strict: bool):
    def one(gamma, deadline, rtt, grid, n_active, n_frames,
            arr0, dl0, arr1, dl1, dur, arrivals, acc_stat,
            acc_sv, seg_head, t_up_seg, t_srv, acc_dp, t_npu64):
        ks = jnp.arange(W, dtype=jnp.int32)

        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, acc_sum, proc, miss, offl, rounds, npu_s = c
            active = head < n_frames
            rounded = n_frames > 0  # traced, always true: _no_fma's gate
            t0 = _no_fma(head.astype(jnp.float64) * gamma, rounded)
            npu_free = jnp.maximum(0.0, busy - t0)
            start_bin = jnp.ceil(jnp.maximum(npu_free, 0.0) / grid).astype(jnp.int32)
            t_up = _upload_at_head(seg_head, t_up_seg, head)  # [R] at trace.at(t0)
            budget = deadline - t_up - rtt  # [R]
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
            # The reference sizes each DP instance at ceil(horizon/grid)+2
            # bins and declares start_bin >= nbins infeasible; rebuild that
            # per-candidate bound from the shared prefix scan.
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
            # local_window_plan tries nn = n..1 and keeps the first feasible;
            # aliveness is prefix-monotone, so that is the leading-alive
            # count (and the start_bin bound only loosens as nn grows).
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

            # Backtrack both DPs on [W] vectors (a second cheap scan beats
            # materializing a [W, NBINS] select of the winner's tables).
            def backtrack(cho, par, b0, upto):
                def bt(b, k):
                    on = k < upto  # prefix records: frames past upto not ours
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

            # Head-frame offload first: decision order is SERVER, then NPUs.
            srv_fin = (t_up[r_star] + rtt) + t_srv[j_best[r_star]]
            if strict:
                srv_bad = use_off & (srv_fin > deadline + AUDIT_TOL)
            else:
                srv_bad = jnp.bool_(False)
            srv_take = active & use_off & ~srv_bad
            acc_sum = acc_sum + jnp.where(srv_take, acc_sv[j_best[r_star], r_star], 0.0)
            proc = proc + srv_take.astype(jnp.int32)
            offl = offl + srv_take.astype(jnp.int32)
            miss = miss + (active & srv_bad).astype(jnp.int32)

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
            return head, busy, acc_sum, proc, miss, offl, rounds, npu_s

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[6], out[7], out[5]

    return LaneProgram(one, (0,) * 16 + (None,) * 3)


@_planner("max_accuracy")
def _run_max_accuracy(models, scenarios, strict):
    t_srv = np.array([m.t_server for m in models], np.float64)
    acc_dp = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float64
    )

    def run_group(key, group):
        W, R = key
        c = _common(models, group, W)
        grid = np.array([float(s.params["grid"]) for s in group], np.float64)
        # Bin arithmetic in f64 on the host — the same numpy expressions as
        # max_accuracy.local_dp, for both first_arrival values (0: the pure
        # local window; gamma: the frames buffered behind an offload).
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
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _max_accuracy_program(c.W, NBINS, S, c.J, R, strict)(
                c.gamma, c.deadline, rtt, grid, c.n_active, c.n_frames,
                arr0, dl0, arr1, dl1, dur, c.arrivals, c.acc_stat64,
                acc_sv, seg_head, t_up_seg, t_srv, acc_dp, c.t_npu64,
            )
            out = [np.asarray(a) for a in out]
        return _collect(c, out[:5], time.perf_counter() - t0, offloaded=out[5])

    return _stitch(scenarios, _net_group_key, run_group)


# ---------------------------------------------------------------------------
# Detect+track planners (tracking.py): no bin DP — candidate scoring is
# closed-form (fresh accuracy times a host-precomputed interval mean), so
# the whole round is a handful of array expressions plus a short sequential
# fold over the tracked frames.  One program serves both policies; ``fixed``
# is a compile-time flag (track_fixed scores raw accuracy and always
# consumes ``k`` frames, track_accuracy scores interval means and lets the
# winning candidate set the horizon).  Decay tables (``retention_powers`` /
# ``interval_means``) are computed on the host with the same Python
# arithmetic the reference planners use, so every product on device
# multiplies the identical float64 constants.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _track_program(S: int, J: int, R: int, KQ: int, A: int, strict: bool, fixed: bool):
    def one(gamma, deadline, rtt, n_frames, k_lim, im, ret_pow,
            acc_stat, acc_sv, seg_head, t_up_seg, t_srv, t_npu64):
        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, det_acc, det_frm, acc_sum, proc, miss, offl, rounds, npu_s = c
            active = head < n_frames
            rounded = n_frames > 0  # traced, always true: _no_fma's gate
            t0 = _no_fma(head.astype(jnp.float64) * gamma, rounded)
            npu_free = jnp.maximum(0.0, busy - t0)
            # NPU candidates: j ascending (the concat order below).
            local = jnp.isfinite(t_npu64)
            kf = jnp.where(local, jnp.ceil(t_npu64 / gamma), 0.0)
            k_npu = jnp.maximum(kf.astype(jnp.int32), 1)  # [J] npu_interval
            feas_npu = local & (npu_free + t_npu64 <= deadline) & (k_npu <= k_lim)
            # Offload candidates: the reference's _server_candidates, r asc.
            t_up = _upload_at_head(seg_head, t_up_seg, head)  # [R]
            budget = deadline - t_up - rtt  # [R]
            fits = t_srv[:, None] <= budget[None, :]  # [J, R]
            a_cand = jnp.where(fits, acc_sv, -jnp.inf)
            j_best = jnp.argmax(a_cand, axis=0).astype(jnp.int32)  # first max
            a_best = jnp.max(a_cand, axis=0)
            r_ok = (budget > 0.0) & jnp.any(fits, axis=0)
            k_srv = jnp.floor(jnp.where(r_ok, t_up, 0.0) / gamma).astype(jnp.int32) + 1
            feas_srv = r_ok & (k_srv <= k_lim)
            if fixed:
                s_npu = jnp.where(feas_npu, acc_stat, -jnp.inf)
                s_srv = jnp.where(feas_srv, a_best, -jnp.inf)
            else:
                s_npu = jnp.where(
                    feas_npu, acc_stat * im[jnp.clip(k_npu - 1, 0, KQ - 1)], -jnp.inf
                )
                s_srv = jnp.where(
                    feas_srv, a_best * im[jnp.clip(k_srv - 1, 0, KQ - 1)], -jnp.inf
                )
            # NPU-then-server candidate order with strict > first-wins is
            # exactly a first-maximum argmax over the concatenation (real
            # scores are >= 0, so -inf marks infeasible unambiguously).
            scores = jnp.concatenate([s_npu, s_srv])
            idx = jnp.argmax(scores).astype(jnp.int32)
            exists = scores[idx] > -jnp.inf
            det_npu = exists & (idx < J)
            j_pick = jnp.clip(idx, 0, J - 1)
            r_pick = jnp.clip(idx - J, 0, R - 1)
            d_acc = jnp.where(det_npu, acc_stat[j_pick], a_best[r_pick])
            k_det = jnp.where(det_npu, k_npu[j_pick], k_srv[r_pick])
            if fixed:
                horizon = k_lim  # the interval is consumed even on SKIP
            else:
                horizon = jnp.where(exists, k_det, 1)
            fin_npu = npu_free + t_npu64[j_pick]
            fin_srv = (t_up[r_pick] + rtt) + t_srv[j_best[r_pick]]
            fin = jnp.where(det_npu, fin_npu, fin_srv)
            if strict:
                bad = exists & (fin > deadline + AUDIT_TOL)
            else:
                bad = jnp.bool_(False)
            # Detection first (audit order), then tracked frames ascending.
            take = active & exists & ~bad
            acc_sum = acc_sum + jnp.where(take, d_acc, 0.0)
            proc = proc + take.astype(jnp.int32)
            offl = offl + (take & ~det_npu).astype(jnp.int32)
            miss = miss + (active & bad).astype(jnp.int32)
            det_acc = jnp.where(take, d_acc, det_acc)
            det_frm = jnp.where(take, head, det_frm)
            off0 = jnp.where(exists, 1, 0)  # SKIP tracks the head frame too

            def tr(o, carry):
                a_s, pr = carry
                on = active & (o >= off0) & (o < horizon) & (head + o < n_frames)
                age = jnp.clip(head + o - det_frm, 0, A - 1)
                v = _no_fma(det_acc * ret_pow[age], rounded)
                return a_s + jnp.where(on, v, 0.0), pr + on.astype(jnp.int32)

            acc_sum, proc = jax.lax.fori_loop(0, KQ, tr, (acc_sum, proc))
            npu_s = npu_s + jnp.where(active & det_npu, t_npu64[j_pick], 0.0)
            busy_until = jnp.where(det_npu, fin_npu, npu_free)
            head = jnp.where(active, head + horizon, head)
            busy = jnp.where(active, t0 + busy_until, busy)
            rounds = rounds + active.astype(jnp.int32)
            return head, busy, det_acc, det_frm, acc_sum, proc, miss, offl, rounds, npu_s

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.full((), -1, jnp.int32),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[4], out[5], out[6], out[8], out[9], out[7]

    return LaneProgram(one, (0,) * 11 + (None,) * 2)


def _run_track(models, scenarios, strict, *, fixed: bool):
    t_srv = np.array([m.t_server for m in models], np.float64)
    kname = "k" if fixed else "k_max"

    def key_fn(s):
        # KQ bounds the horizon (and the tracked-frame fold length); A sizes
        # the retention table — ages reach n_frames with the -1 initial state.
        return (_quant_w(int(s.params[kname])), len(s.stream.resolutions),
                _quant_pow2(s.n_frames + 1))

    def run_group(key, group):
        KQ, R, A = key
        c = _common(models, group, W=1)  # windows are a classify concept
        B = len(group)
        k_lim = np.array([int(s.params[kname]) for s in group], np.int32)
        im = np.zeros((B, KQ), np.float64)
        if not fixed:
            # interval_means is prefix-stable, so padding KQ past a lane's
            # k_max cannot change any entry the planner may select.
            for i, s in enumerate(group):
                ret_b = retention(float(s.params["decay"]), float(s.params["density"]))
                im[i, :] = interval_means(ret_b, KQ)
        ret_pow = np.empty((B, A), np.float64)
        for i, s in enumerate(group):
            ret_pow[i, :] = retention_powers(s.workload.retention, A)
        nbits8, acc_sv = _offload_tables(models, group)
        rtt, seg_head, t_up_seg, S = _net_arrays(group, c.gamma, nbits8)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _track_program(S, c.J, R, KQ, A, strict, fixed)(
                c.gamma, c.deadline, rtt, c.n_frames, k_lim, im, ret_pow,
                c.acc_stat64, acc_sv, seg_head, t_up_seg, t_srv, c.t_npu64,
            )
            out = [np.asarray(a) for a in out]
        return _collect(c, out[:5], time.perf_counter() - t0, offloaded=out[5])

    return _stitch(scenarios, key_fn, run_group)


@_planner("track_accuracy")
def _run_track_accuracy(models, scenarios, strict):
    return _run_track(models, scenarios, strict, fixed=False)


@_planner("track_fixed")
def _run_track_fixed(models, scenarios, strict):
    return _run_track(models, scenarios, strict, fixed=True)


@lru_cache(maxsize=None)
def _max_utility_program(W: int, S: int, J: int, R: int, strict: bool, width: int):
    def one(gamma, deadline, rtt, alpha, fps, n_w, n_frames, arrivals, acc_stat,
            acc_sv, seg_head, t_up_seg, t_srv, acc_dp, t_npu64):
        ks = jnp.arange(W, dtype=jnp.int32)

        def backtrack(u_final, parents, actions):
            slot0 = jnp.argmax(u_final).astype(jnp.int32)  # first max = front order

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
            # _round_utility's decision-order f64 fold; the head offload's
            # server accuracy seeds acc0 so the summation order matches.
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
            head, busy, acc_sum, proc, miss, offl, rounds, npu_s, ovf = c
            active = head < n_frames
            rounded = n_frames > 0  # traced, always true: _no_fma's gate
            t0 = _no_fma(head.astype(jnp.float64) * gamma, rounded)
            npu_free = jnp.maximum(0.0, busy - t0)
            t_up = _upload_at_head(seg_head, t_up_seg, head)  # [R]
            # Offload phase: argmax_{j,r} capped-rate + alpha * a(j, r); the
            # reference iterates r-outer/j-inner with strict >, so the first
            # maximum over the r-major flattening wins ties identically.
            feas = (t_up[:, None] + t_srv[None, :] + rtt) <= deadline  # [R, J]
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
            n1, a_off = cand_stats(picks1, srv_acc)  # server acc accumulates first
            n2, a_loc = cand_stats(picks2, jnp.float64(0.0))
            # The true round objective (_round_utility) for both candidates.
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
            srv_fin = (t_up0 + rtt) + t_srv[jnp.clip(j0, 0, J - 1)]
            if strict:
                srv_bad = use_off & (srv_fin > deadline + AUDIT_TOL)
            else:
                srv_bad = jnp.bool_(False)
            srv_take = active & use_off & ~srv_bad
            acc_sum = acc_sum + jnp.where(srv_take, srv_acc, 0.0)
            proc = proc + srv_take.astype(jnp.int32)
            offl = offl + srv_take.astype(jnp.int32)
            miss = miss + (active & srv_bad).astype(jnp.int32)

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
            return head, busy, acc_sum, proc, miss, offl, rounds, npu_s, ovf

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), bool),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[6], out[7], out[5], out[8]

    return LaneProgram(one, (0,) * 12 + (None,) * 3)


@_planner("max_utility")
def _run_max_utility(models, scenarios, strict):
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
        lane_args = (c.gamma, c.deadline, rtt, alpha, fps, c.n_active, c.n_frames,
                     c.arrivals, c.acc_stat64, acc_sv, seg_head, t_up_seg)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _max_utility_program(c.W, S, c.J, R, strict, _UTIL_FAST_WIDTH)(
                *lane_args, t_srv, acc_dp, c.t_npu64,
            )
            out = [np.array(a) for a in out]
            overflowed = np.nonzero(out[6])[0]
            if overflowed.size:
                # A front outgrew the fast width somewhere in these lanes:
                # rerun just them at the reference prune cap (exact for any
                # front size) and splice the results back in.
                sub = _max_utility_program(c.W, S, c.J, R, strict, _UTIL_CAP)(
                    *(a[overflowed] for a in lane_args), t_srv, acc_dp, c.t_npu64,
                )
                for dst, src in zip(out[:6], sub[:6]):
                    dst[overflowed] = np.asarray(src)
        return _collect(c, out[:5], time.perf_counter() - t0, offloaded=out[5])

    return _stitch(scenarios, _net_group_key, run_group)
