"""Vectorized multi-stream fleet backend: grids of *interacting* clients as
ONE tensor program.

``simulator.simulate_multi`` is the ground truth for every multi-client
figure: N phones share one fluid uplink and one edge server, and the
``EdgeServerScheduler`` admission policy (weighted_fair / priority / fifo)
decides who may offload.  It is also a per-event Python loop — a fleet sweep
pays interpreter cost for every upload completion of every grid point.  This
module executes the same physics for a whole batch of fleet scenarios
(bandwidth × deadline × fps × n_clients × allocation grid points) as a
single jit+vmap program:

  * plan events are **tick-synchronized**: every client of a ``make_fleet``
    fleet shares one frame interval, so all round boundaries land on the
    grid ``k * gamma`` and one ``lax.scan`` over ticks replaces the event
    queue.  Within a tick, clients plan sequentially in the reference's
    ``(-priority, -weight, client_id)`` order (a ``fori_loop`` over a
    host-precomputed permutation), because each grant/lease mutates the
    scheduler state the next client sees;
  * between ticks, the shared link drains under an inner ``while_loop``
    that mirrors the reference event iteration: water-filling rates over
    the per-client **head** uploads (radios are serial), earliest-completion
    selection with the reference's ``_EPS``/``_BITS_EPS`` semantics, and a
    **fixed-point** water-filling iteration (at most N cap-resolution
    rounds) in place of ``edge_server.fluid_rates``'s Python loop;
  * the ``EdgeServerScheduler`` allocation arithmetic — effective weights,
    fair shares, capacity/backlog/priority-reservation gates, serial-radio
    link reservation — is re-rendered as pure f64 array expressions over
    per-client lease counters (see ``edge_server.effective_weight`` /
    ``fair_share`` for the scalar originals);
  * the audit is the reference's: offloads score at *actual* completion
    (fluid upload, then a FIFO worker queue over ``capacity`` slots, then
    the RTT) against ``deadline_abs + 1e-9``, exactly as
    ``simulator.simulate_multi`` does.

Equivalence contract (golden-tested in ``tests/test_sim_multi_batch.py``,
property-tested in ``tests/test_sim_multi_batch_properties.py``): integer
stats (frames processed / offloaded / missed, server jobs, grants, denials)
are **exactly equal** to the reference loop, and float stats (accuracy
sums, server busy seconds) agree within :data:`MULTI_TOL`.  The tolerance —
rather than the single-stream backend's bit-identity — exists because the
reference accumulates a few float reductions (fluid total weights,
link-reservation sums, capped-rate subtractions) in *registration* order
while this module accumulates them in client-id order; with the default
equal weights the two orders round identically and the golden grids come
out bit-equal, which the equivalence benchmark records as ``exact_match``.

Five policies have fleet planners here, sharing one set of link/scheduler
closures (:func:`_fleet_physics`):

  * ``offload`` — its round plan is closed-form in the granted bandwidth
    (no DP), so the whole decision vectorizes, while its
    offload-every-round behaviour exercises exactly the shared-link /
    server-queue physics the paper's multi-user results are about;
  * ``max_accuracy`` / ``max_utility`` — the paper's own planners: each
    client's round is the ``sim_batch`` rendering of the reference
    ``plan_round`` (per-resolution upload times against the *granted*
    bandwidth, feasible-server-model argmax, the f64 DP twins of
    :mod:`repro.core.jax_sched` with the ``_no_fma`` tie-break guard,
    normalized-score candidate selection), except the head-frame offload is
    not audited at plan time: it registers an upload on the shared link and
    scores at actual completion, exactly like the reference's
    ``on_offload`` callback.  Clients plan only at their own round
    boundaries (``head[c] == k``), and ``max_utility`` keeps the
    width-64 fast pass + width-256 overflow-rerun protocol;
  * ``jax_accuracy`` / ``jax_utility`` — local-only plans that never
    consult the grant, so every client of a homogeneous fleet follows the
    *identical* trajectory: one lane per scenario runs the single-stream
    program body (bit-identical stats, replicated per client) extended
    with the scheduler's grant/denial counters (every plan event still
    calls ``allocate`` once per client in the reference; the gate outcome
    for a leaseless fleet is a static per-client predicate plus the
    trace's bandwidth sign and the backlog clock).

``Session.run_sweep`` routes fleet grids of all five policies here; see
docs/simulation.md ("Fleet planners") for the capability matrix and the
remaining fallback combinations.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from functools import lru_cache
from types import SimpleNamespace
from typing import Any, Callable, Mapping, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .jax_sched import (
    NEG,
    _accuracy_dp,
    _accuracy_dp64,
    _no_fma,
    _utility_dp,
    _utility_dp64,
)
from .bucketing import quant_bins as _quant_bins
from .bucketing import quant_w as _quant_w
from .profiles import ModelProfile, StreamSpec
from .registry import get_policy
from .schedule import StreamStats
from .sim_batch import (
    _UTIL_CAP,
    _UTIL_FAST_WIDTH,
    BatchScenario,
    _audit_scan,
    _collect,
    _common,
    _trace_bw,
    _window_frames,
    segment_arrays,
)
from .sweep_shard import LaneProgram
from .simulator import _BITS_EPS, _EPS, MultiStreamStats
from .tracking import WorkloadSpec, interval_means, retention, retention_powers

__all__ = [
    "EQUIV_INT_FIELDS",
    "FleetScenario",
    "MULTI_TOL",
    "multi_batched_policies",
    "simulate_multi_batch",
]

# The equivalence contract versus the reference event loop, stated once for
# every consumer (tests/test_sim_multi_batch.py asserts it per golden grid,
# benchmarks/multistream_bench.py per ladder cell): the per-stream integer
# fields below must match EXACTLY, float stats (accuracy sums, server busy
# seconds) within the certified absolute tolerance MULTI_TOL.
MULTI_TOL = 1e-9
EQUIV_INT_FIELDS = (
    "frames_processed",
    "frames_missed_deadline",
    "frames_offloaded",
    "frames_total",
    "schedule_calls",
)

_BIG = 1e18  # "never" sentinel for event times (far above any finish time)
_BIG_I32 = np.iinfo(np.int32).max


@dataclass(frozen=True)
class FleetScenario:
    """One fleet grid point as the batched backend sees it: a homogeneous
    fleet (the ``make_fleet`` shape — one stream spec, per-client weights /
    priorities), a shared network, an allocation policy, and the inner
    policy's *resolved* parameter dict.

    The network is ``bw_segments`` — sorted piecewise-constant
    ``(t_start_s, bandwidth_bps)`` segments replayed on device (allocation
    reads bandwidth at each round's start, the fluid link at every event
    boundary, exactly like the reference's ``trace.at``) — or, when that is
    ``None``, the constant ``bandwidth_bps``.

    ``workload`` is the fleet's world truth (``tracking.WorkloadSpec``):
    the ``track_*`` planners require ``kind="track"`` (detections contend
    on the shared uplink, tracker-carried frames do not), the classification
    planners the default ``kind="classify"``."""

    stream: StreamSpec = field(default_factory=StreamSpec)
    n_frames: int = 120
    bandwidth_bps: float = 2.5e6
    rtt: float = 0.100
    n_clients: int = 2
    allocation: str = "weighted_fair"
    capacity: int = 4
    backlog_limit: float = 0.0
    weights: tuple[float, ...] | None = None
    priorities: tuple[int, ...] | None = None
    params: Mapping[str, Any] = field(default_factory=dict)
    bw_segments: tuple[tuple[float, float], ...] | None = None
    workload: WorkloadSpec = field(default_factory=WorkloadSpec)


_PLANNERS: dict[str, Callable[..., list[tuple[MultiStreamStats, dict]]]] = {}


def _planner(name: str):
    def deco(fn):
        _PLANNERS[name] = fn
        return fn

    return deco


def multi_batched_policies() -> tuple[str, ...]:
    """Policies with a dedicated fleet planner here (exactly the registry's
    ``batched_multi=True`` set; ``tests/test_sim_multi_batch.py`` asserts
    registry and table stay in sync)."""
    return tuple(sorted(_PLANNERS))


def simulate_multi_batch(
    policy: str,
    models: Sequence[ModelProfile],
    scenarios: Sequence[FleetScenario],
    *,
    strict: bool = True,
) -> list[tuple[MultiStreamStats, dict]]:
    """Run ``policy`` fleets over every scenario in one compiled program.

    Returns one ``(MultiStreamStats, meta)`` pair per scenario, in order —
    ``meta`` carries the scheduler's grant/denial counters, mirroring what
    ``Session.run_multi`` reports.  Raises ``ValueError`` for policies
    without a fleet planner; ``Session.run_sweep`` is the front door that
    logs a fallback instead.

    ``strict`` follows the reference exactly: it gates the plan-time audit
    of NPU decisions (``audit_round(..., npu_only=True)`` in
    ``simulate_multi``), while offload deadline misses are always audited
    at actual completion regardless of ``strict``.  The ``offload``
    planner's plans contain no NPU decisions, so it ignores the flag.
    """
    fn = _PLANNERS.get(policy)
    if fn is None:
        raise ValueError(
            f"policy {policy!r} has no batched fleet backend; "
            f"available: {multi_batched_policies()}"
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
# Fixed-shape fleet state.  One scenario = one lane of the vmap; every array
# below is that lane's state.  Upload queues are per-client append-only
# logs of length F (at most one offload per client per tick), so the three
# monotone cursors need no ring arithmetic:
#
#     [0 .. srv-released) .. [.. updone) .. [.. tail)
#      lease popped           at server      upload in flight
#
# A lease exists for every entry in [released, tail); its link share is
# active for entries in [updone, tail) — the serial radio transmits only
# the entry AT updone.  "released" is not a stored cursor: a lease leaves
# the server when its recorded finish time passes, so the count is derived
# from q_srvfin <= t (mirroring the reference's pending_releases queue).
# ---------------------------------------------------------------------------


class _Fleet(NamedTuple):
    now: Any  # [] f64 current simulation time
    q_bits: Any  # [N, F] f64 residual upload bits
    q_cap: Any  # [N, F] f64 scheduler-granted rate cap (inf under fifo)
    q_ddl: Any  # [N, F] f64 absolute deadline
    q_acc: Any  # [N, F] f64 server accuracy credited on an on-time finish
    q_tsrv: Any  # [N, F] f64 server-side service time
    q_bps: Any  # [N, F] f64 leased bandwidth (link reservation while active)
    q_seq: Any  # [N, F] i32 global registration order (tick * N + plan rank)
    q_srvfin: Any  # [N, F] f64 server-job finish time (BIG until assigned)
    tail: Any  # [N] i32 uploads ever registered
    updone: Any  # [N] i32 uploads fully drained off the link
    worker_free: Any  # [KW] f64 per-worker busy-until
    sbu: Any  # [] f64 scheduler backlog estimate (server_busy_until)
    grants: Any  # [] i32
    denials: Any  # [] i32
    sjobs: Any  # [] i32 jobs the server executed
    sbusy: Any  # [] f64 server busy seconds
    accs: Any  # [N] f64 per-client accuracy sums
    proc: Any  # [N] i32 per-client frames processed
    miss: Any  # [N] i32 per-client deadline misses
    offl: Any  # [N] i32 per-client on-time server completions
    head: Any  # [N] i32 next frame each client plans (round boundary)
    busy: Any  # [N] f64 per-client absolute NPU busy-until
    rounds: Any  # [N] i32 per-client plan rounds executed
    npus: Any  # [N] f64 per-client NPU busy seconds (planned occupancy)


def _seq_sum(values):
    """Strictly sequential f64 sum in index order — the reference computes
    its weight/reservation totals with Python's left-to-right ``sum``, and
    an XLA tree reduction would round differently.  Unrolled: the client
    axis is tiny and static, and a ``fori_loop`` of one add costs more in
    loop plumbing than the adds themselves."""
    acc = jnp.float64(0.0)
    for i in range(values.shape[0]):
        acc = acc + values[i]
    return acc


def _fleet_physics(alloc: str, N: int, K: int, F: int, *, bw_t, bw_v, rtt, L,
                   w_fluid, w_eff, tot_w, prio):
    """The shared fleet physics, bound to one lane's arrays: the fluid
    uplink (water-filling rates, event-by-event drain), the completion /
    audit machinery, and the ``EdgeServerScheduler`` allocation + lease
    arithmetic.  Every fleet planner composes these closures with its own
    round rendering, so the link a DP planner contends on is *the same
    code* the golden-tested ``offload`` planner runs."""
    fifo = alloc == "fifo"
    prio_pol = alloc == "priority"
    KW = max(K, 1)  # worker count (the reference's max(int(capacity), 1))
    MAXEV = N * F + N + 4  # completion events are bounded by registrations
    cids = jnp.arange(N, dtype=jnp.int32)

    def bw_at(t):
        # The reference's trace.at(t).bandwidth_bps: piecewise-constant
        # step lookup (constant traces are a single t=0 segment).
        return _trace_bw(bw_t, bw_v, t)

    # -- fluid link: rates over the per-client head uploads ----------------
    def heads(st):
        idx = jnp.clip(st.updone, 0, F - 1)
        active = st.updone < st.tail
        hbits = jnp.where(active, st.q_bits[cids, idx], 0.0)
        hcap = jnp.where(active, st.q_cap[cids, idx], _BIG)
        hseq = jnp.where(active, st.q_seq[cids, idx], _BIG_I32)
        return active, hbits, hcap, hseq

    def waterfill(B, active, caps):
        # Fixed-point rendering of edge_server.fluid_rates: each round
        # either freezes >= 1 capped transfer or assigns final shares,
        # so N (static, tiny) rounds always suffice — unrolled.
        rates = jnp.zeros((N,), jnp.float64)
        remaining = jnp.maximum(B, 0.0)
        act = active
        done = ~jnp.any(active)
        for _ in range(N):
            total_w = _seq_sum(jnp.where(act, w_fluid, 0.0))
            total_w = jnp.where(total_w == 0.0, 1.0, total_w)
            share = remaining * w_fluid / total_w
            live = act & (remaining > _EPS) & ~done
            capped = live & (caps <= share + _EPS)
            none_capped = ~jnp.any(capped)
            # No cap binds: everyone still active takes its share, done.
            rates = jnp.where(live & none_capped, share, rates)
            # Caps bind: freeze them, return leftovers to the pool in
            # client-id order (the reference subtracts sequentially).
            rates = jnp.where(capped, caps, rates)
            sub = remaining
            for i in range(N):
                sub = sub - jnp.where(capped[i], caps[i], 0.0)
            remaining = jnp.where(jnp.any(capped), jnp.maximum(sub, 0.0), remaining)
            act = act & ~capped & ~none_capped
            done = done | jnp.any(live & none_capped) | ~jnp.any(live)
        return rates

    def link_state(st):
        active, hbits, hcap, hseq = heads(st)
        # Rates re-evaluate at every event boundary against the trace's
        # bandwidth at the CURRENT time — the reference's
        # _fluid_rates(trace.at(now).bandwidth_bps, active).
        rates = waterfill(bw_at(st.now), active, hcap)
        finish = jnp.where(
            active & (rates > _EPS), st.now + hbits / rates, _BIG
        )
        return active, hbits, hseq, rates, finish

    # -- a batch of upload completions: worker queue + deadline audit ------
    # At most one upload per client (its head) can be due at once, so
    # the per-client stat updates batch into one scatter per field;
    # only the worker assignment walks the due set sequentially — the
    # reference pops jobs in registration order against a mutating
    # worker pool, and the server-busy accumulator must also grow one
    # job at a time to reproduce the loop's f64 rounding.
    def complete_batch(st, due):
        idx = jnp.clip(st.updone, 0, F - 1)
        tsv = jnp.where(due, st.q_tsrv[cids, idx], 0.0)
        ddl = st.q_ddl[cids, idx]
        acc = st.q_acc[cids, idx]
        _, _, _, hseq = heads(st)
        seqs = jnp.where(due, hseq, _BIG_I32)

        def assign(i, carry):
            wf, jfin, sbusy, left = carry
            c = jnp.argmin(jnp.where(left, seqs, _BIG_I32)).astype(jnp.int32)
            go = left[c]
            wi = jnp.argmin(wf).astype(jnp.int32)
            fin = jnp.maximum(st.now, wf[wi]) + tsv[c]
            wf = wf.at[wi].set(jnp.where(go, fin, wf[wi]))
            jfin = jfin.at[c].set(jnp.where(go, fin, jfin[c]))
            sbusy = sbusy + jnp.where(go, tsv[c], 0.0)
            return wf, jfin, sbusy, left.at[c].set(False)

        wf, jfin, sbusy, _ = jax.lax.fori_loop(
            0, N, assign,
            (st.worker_free, jnp.full((N,), _BIG, jnp.float64), st.sbusy, due),
        )
        ontime = due & (jfin + rtt <= ddl + _EPS)
        return st._replace(
            worker_free=wf,
            q_srvfin=st.q_srvfin.at[cids, idx].set(
                jnp.where(due, jfin, st.q_srvfin[cids, idx])
            ),
            updone=st.updone + due.astype(jnp.int32),
            sjobs=st.sjobs + jnp.sum(due.astype(jnp.int32), dtype=jnp.int32),
            sbusy=sbusy,
            accs=st.accs + jnp.where(ontime, acc, 0.0),
            proc=st.proc + ontime.astype(jnp.int32),
            miss=st.miss + (due & ~ontime).astype(jnp.int32),
            offl=st.offl + ontime.astype(jnp.int32),
        )

    def mop_up(st):
        # Residual-bits mop-up at a boundary advance: the reference's
        # drain pass completes any head below _BITS_EPS regardless of
        # its rate ("transfers that cross zero during an advance").
        active, hbits, _, _ = heads(st)
        return complete_batch(st, active & (hbits <= _BITS_EPS))

    # -- drain the link toward a target time -------------------------------
    # The water-filling state is carried across the while boundary so
    # each event iteration evaluates it exactly once (the cond reuses
    # the body's rates — identical values, half the arithmetic).
    def drain(st, t_target, *, advance_to_target: bool):
        ls0 = link_state(st)

        def cond(carry):
            _, budget, ls = carry
            t_done = jnp.min(ls[4])
            # t_done == _BIG means "no completion will ever happen";
            # without the guard a drain-to-_BIG would spin on it.  Heads
            # at/below _BITS_EPS never enter a drain: the boundary
            # mop-up below (and the reference's own drain pass) clears
            # them before the next event is selected.
            due_soon = (t_done <= t_target + _EPS) & (t_done < _BIG * 0.5)
            return due_soon & (budget > 0)

        def body(carry):
            st, budget, ls = carry
            active, hbits, _, rates, finish = ls
            t_done = jnp.min(finish)
            t_next = jnp.minimum(jnp.minimum(t_done, t_target), _BIG)
            dt = jnp.maximum(t_next - st.now, 0.0)
            idx = jnp.clip(st.updone, 0, F - 1)
            newbits = jnp.maximum(0.0, hbits - rates * dt)
            due = active & (
                ((finish <= t_done + _EPS) & (t_done <= t_next + _EPS))
                | (newbits <= _BITS_EPS)
            )
            st = st._replace(
                now=jnp.maximum(st.now, t_next),
                q_bits=st.q_bits.at[cids, idx].set(
                    jnp.where(active, jnp.where(due, 0.0, newbits), st.q_bits[cids, idx])
                ),
            )
            st = complete_batch(st, due)
            return st, budget - 1, link_state(st)

        st, _, ls = jax.lax.while_loop(cond, body, (st, jnp.int32(MAXEV), ls0))
        if advance_to_target:
            # Partial advance to the tick boundary (rates re-evaluated,
            # exactly the reference's piecewise-constant approximation).
            active, hbits, _, rates, _ = ls
            dt = jnp.maximum(t_target - st.now, 0.0)
            idx = jnp.clip(st.updone, 0, F - 1)
            newbits = jnp.maximum(0.0, hbits - rates * dt)
            st = st._replace(
                now=jnp.maximum(st.now, t_target),
                q_bits=st.q_bits.at[cids, idx].set(
                    jnp.where(active, newbits, st.q_bits[cids, idx])
                ),
            )
            st = mop_up(st)
        return st

    # Serial radios: a client's many leases reserve max(bps) over its
    # link-active entries [updone, tail).  Recomputed from the queues
    # once per tick; plan events then maintain it incrementally (a new
    # lease can only raise its own client's max).
    def active_link_bps(st):
        pos = jnp.arange(F, dtype=jnp.int32)
        act_mask = (pos[None, :] >= st.updone[:, None]) & (
            pos[None, :] < jnp.clip(st.tail, 0, F)[:, None]
        )
        return jnp.max(jnp.where(act_mask, st.q_bps, 0.0), axis=1)  # [N]

    # -- the EdgeServerScheduler allocation gate (one client's allocate) ---
    def allocate(st, c, t0, released, act_bps):
        lease_len = st.tail - released  # [N]
        total = jnp.sum(lease_len)
        B0 = bw_at(t0)  # the reference plans against trace.at(t0)
        if fifo:
            return B0, jnp.bool_(False)
        own = lease_len[c]
        effective = total - jnp.minimum(own, 1)
        backlogged = st.sbu - t0 > L
        if prio_pol:
            free = K - total
            higher_waiting = jnp.sum(
                ((prio > prio[c]) & (lease_len == 0)).astype(jnp.int32)
            )
            reserved = free <= higher_waiting
        else:
            reserved = jnp.bool_(False)
        gated = (effective >= K) | backlogged | reserved
        used = _seq_sum(jnp.where(cids != c, act_bps, 0.0))
        available = jnp.maximum(B0 - used, 0.0)
        share = B0 * w_eff[c] / tot_w
        grant = jnp.minimum(share, available)
        denied = gated | (grant <= 0.0)
        grant = jnp.where(denied, 0.0, grant)
        return grant, denied

    # -- register one head-frame offload on the link + server lease --------
    def register(st, act_bps, c, *, on, t0, seq, grant, bits, ddl, acc, tsv):
        e = jnp.clip(st.tail[c], 0, F - 1)
        cap = jnp.float64(np.inf) if fifo else grant

        def put(q, val):
            return q.at[c, e].set(jnp.where(on, val, q[c, e]))

        sbu = st.sbu
        if not fifo:
            # The reference divides by max(capacity, 1), even at K == 0.
            sbu = jnp.where(on, jnp.maximum(st.sbu, t0) + tsv / KW, st.sbu)
        st = st._replace(
            q_bits=put(st.q_bits, bits),
            q_cap=put(st.q_cap, cap),
            q_ddl=put(st.q_ddl, ddl),
            q_acc=put(st.q_acc, acc),
            q_tsrv=put(st.q_tsrv, tsv),
            q_bps=put(st.q_bps, grant),
            q_seq=put(st.q_seq, seq),
            tail=st.tail.at[c].add(jnp.where(on, 1, 0)),
            sbu=sbu,
        )
        act_bps = act_bps.at[c].set(
            jnp.where(on, jnp.maximum(act_bps[c], grant), act_bps[c])
        )
        return st, act_bps

    def init_state():
        return _Fleet(
            now=jnp.float64(0.0),
            q_bits=jnp.zeros((N, F), jnp.float64),
            q_cap=jnp.full((N, F), _BIG, jnp.float64),
            q_ddl=jnp.zeros((N, F), jnp.float64),
            q_acc=jnp.zeros((N, F), jnp.float64),
            q_tsrv=jnp.zeros((N, F), jnp.float64),
            q_bps=jnp.zeros((N, F), jnp.float64),
            q_seq=jnp.full((N, F), _BIG_I32, jnp.int32),
            q_srvfin=jnp.full((N, F), _BIG, jnp.float64),
            tail=jnp.zeros((N,), jnp.int32),
            updone=jnp.zeros((N,), jnp.int32),
            worker_free=jnp.zeros((KW,), jnp.float64),
            sbu=jnp.float64(0.0),
            grants=jnp.int32(0),
            denials=jnp.int32(0),
            sjobs=jnp.int32(0),
            sbusy=jnp.float64(0.0),
            accs=jnp.zeros((N,), jnp.float64),
            proc=jnp.zeros((N,), jnp.int32),
            miss=jnp.zeros((N,), jnp.int32),
            offl=jnp.zeros((N,), jnp.int32),
            head=jnp.zeros((N,), jnp.int32),
            busy=jnp.zeros((N,), jnp.float64),
            rounds=jnp.zeros((N,), jnp.int32),
            npus=jnp.zeros((N,), jnp.float64),
        )

    def finish(st):
        # Post-stream drain: in-flight uploads finish (and audit) after the
        # last round boundary, exactly as the reference keeps its event loop
        # alive until the link empties.  Anything still queued could not
        # drain (the event budget tripped, or a dead link): every stranded
        # upload is a deadline miss.
        st = drain(st, jnp.float64(_BIG), advance_to_target=False)
        return st._replace(miss=st.miss + (st.tail - st.updone))

    return SimpleNamespace(
        bw_at=bw_at, heads=heads, waterfill=waterfill, link_state=link_state,
        complete_batch=complete_batch, mop_up=mop_up, drain=drain,
        active_link_bps=active_link_bps, allocate=allocate, register=register,
        init_state=init_state, finish=finish,
    )


@lru_cache(maxsize=None)
def _fleet_program(alloc: str, N: int, K: int, F: int, J: int, R: int, S: int):
    """Compile one (allocation policy, fleet size, capacity, frame count)
    shape group of the ``offload`` planner.  J/R are the model/resolution
    table sizes; S is the padded bandwidth-segment count (sentinel segments
    at t_start=+inf are inert — see ``sim_batch._trace_bw``)."""
    fifo = alloc == "fifo"

    def one(bw_t, bw_v, gamma, T, rtt, fps, L, alpha, is_util, w_fluid, w_eff,
            tot_w, prio, order, bits_r, acc_sv, t_srv):
        phys = _fleet_physics(
            alloc, N, K, F, bw_t=bw_t, bw_v=bw_v, rtt=rtt, L=L,
            w_fluid=w_fluid, w_eff=w_eff, tot_w=tot_w, prio=prio,
        )

        # -- one client's plan event: allocate -> plan -> register ---------
        def plan_one(rank, carry):
            st, k, t0, released, act_bps = carry
            c = order[rank]
            grant, denied = phys.allocate(st, c, t0, released, act_bps)
            st = st._replace(
                grants=st.grants + jnp.where(denied, 0, 1),
                denials=st.denials + jnp.where(denied, 1, 0),
            )

            # Closed-form offload round against the granted bandwidth: the
            # reference's per-resolution loop as one [R] expression.
            t_up = bits_r / grant  # inf when grant == 0, like upload_time
            budget = T - t_up - rtt  # [R]
            fits = t_srv[:, None] <= budget[None, :]  # [J, R]
            a_mask = jnp.where(fits, acc_sv, -jnp.inf)
            j_best = jnp.argmax(a_mask, axis=0).astype(jnp.int32)  # first max
            a_best = jnp.max(a_mask, axis=0)
            feasible = (t_up <= gamma) & jnp.any(fits, axis=0)
            util_score = (
                jnp.minimum(1.0 / jnp.maximum(t_up, 1e-9), fps) + alpha * a_best
            )
            score = jnp.where(is_util, util_score, a_best)
            score = jnp.where(feasible, score, -jnp.inf)
            offload = jnp.any(feasible)
            r_pick = jnp.argmax(score).astype(jnp.int32)  # first max wins ties
            j_pick = j_best[r_pick]

            st, act_bps = phys.register(
                st, act_bps, c, on=offload, t0=t0, seq=k * N + rank,
                grant=grant, bits=bits_r[r_pick], ddl=t0 + T,
                acc=acc_sv[j_pick, r_pick], tsv=t_srv[j_pick],
            )
            return st, k, t0, released, act_bps

        # -- the tick scan --------------------------------------------------
        def tick(st, k):
            t0 = k.astype(jnp.float64) * gamma
            st = phys.drain(st, t0, advance_to_target=True)
            # Server slots whose jobs have finished by t0 free their leases.
            released = jnp.sum(
                (st.q_srvfin <= t0 + _EPS).astype(jnp.int32), axis=1
            )
            st, _, _, _, _ = jax.lax.fori_loop(
                0, N, plan_one,
                (st, k.astype(jnp.int32), t0, released, phys.active_link_bps(st)),
            )
            return st, None

        st, _ = jax.lax.scan(tick, phys.init_state(), jnp.arange(F, dtype=jnp.int32))
        st = phys.finish(st)
        return st.accs, st.proc, st.miss, st.grants, st.denials, st.sjobs, st.sbusy

    return LaneProgram(one, (0,) * 14 + (None,) * 3)


# ---------------------------------------------------------------------------
# The DP planner fleet programs: max_accuracy / max_utility.  Each client's
# round is the sim_batch rendering of the reference plan_round — but planned
# against the GRANTED bandwidth, with the head-frame offload registered on
# the shared link (audited at actual completion, like the reference's
# on_offload callback) instead of scored at plan time.  Clients plan only at
# their own round boundaries (head[c] == k); the inter-tick drain runs only
# when somebody plans, so the rate re-evaluation points are exactly the
# reference's event set (plan events + completion events).
# ---------------------------------------------------------------------------


def _dp_backtrack(W: int, NBINS: int):
    """Backtrack an _accuracy_dp64 table on [W] vectors (a second cheap
    scan beats materializing a [W, NBINS] select of the winner's tables)."""

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

    return backtrack


@lru_cache(maxsize=None)
def _acc_fleet_program(alloc: str, N: int, K: int, F: int, W: int, NBINS: int,
                       S: int, J: int, R: int, strict: bool):
    def one(bw_t, bw_v, gamma, deadline, rtt, grid, L, n_active,
            arr0, dl0, arr1, dl1, dur, arrivals, acc_stat,
            w_fluid, w_eff, tot_w, prio, order,
            bits_r, acc_sv, t_srv, acc_dp, t_npu64):
        phys = _fleet_physics(
            alloc, N, K, F, bw_t=bw_t, bw_v=bw_v, rtt=rtt, L=L,
            w_fluid=w_fluid, w_eff=w_eff, tot_w=tot_w, prio=prio,
        )
        ks = jnp.arange(W, dtype=jnp.int32)
        rounded = n_active > 0  # traced, always true: _no_fma's gate
        backtrack = _dp_backtrack(W, NBINS)

        # Both DP variants depend on the shared round state only through the
        # client's own NPU horizon (start_bin): a client's ``busy`` is
        # written by nobody but its own plan, and each client plans at most
        # once per round — so the expensive DP tables for all N clients
        # batch into one vmap OUTSIDE the sequential allocate/register
        # chain, which then runs on cheap scalars.
        def dp_tables(st, t0):
            start_bins = jnp.ceil(
                jnp.maximum(jnp.maximum(0.0, st.busy - t0), 0.0) / grid
            ).astype(jnp.int32)  # [N]
            # One fused vmap over 2N (client x {offload,local}) seeds: the
            # offload (arr1/dl1) and pure-local (arr0/dl0) tables share one
            # scan, halving the sequential DP step count per round.
            arr_b = jnp.concatenate(
                [jnp.broadcast_to(arr1, (N, W)), jnp.broadcast_to(arr0, (N, W))]
            )
            dl_b = jnp.concatenate(
                [jnp.broadcast_to(dl1, (N, W)), jnp.broadcast_to(dl0, (N, W))]
            )
            res = jax.vmap(
                lambda a, d, sb: _accuracy_dp64(
                    dur, acc_dp, a, d, sb, n_frames=W, nbins=NBINS
                )
            )(arr_b, dl_b, jnp.concatenate([start_bins, start_bins]))
            dp1 = tuple(r[:N] for r in res)
            dp0 = tuple(r[N:] for r in res)
            return start_bins, dp1, dp0

        def make_plan_one(k, t0, released, start_bins, dp1, dp0):
            def plan_one(rank, carry):
                (st, act_bps, planning_v, use_off_v, use_loc_v, nn_v,
                 npu_free_v, b0_off_v, b0_loc_v) = carry
                c = order[rank]
                planning = st.head[c] == k
                grant, denied = phys.allocate(st, c, t0, released, act_bps)
                st = st._replace(
                    grants=st.grants + jnp.where(planning & ~denied, 1, 0),
                    denials=st.denials + jnp.where(planning & denied, 1, 0),
                )

                npu_free = jnp.maximum(0.0, st.busy[c] - t0)
                start_bin = start_bins[c]
                # The reference plans against NetworkState(grant, rtt).
                t_up = jnp.where(grant > 0.0, bits_r / grant, jnp.inf)  # [R]
                budget = deadline - t_up - rtt  # [R]
                fits = t_srv[:, None] <= budget[None, :]  # [J, R]
                a_cand = jnp.where(fits, acc_sv, -jnp.inf)
                j_best = jnp.argmax(a_cand, axis=0).astype(jnp.int32)  # first max
                a_best = jnp.max(a_cand, axis=0)
                r_ok = (budget > 0.0) & jnp.any(fits, axis=0)
                n_l = jnp.floor(jnp.where(r_ok, t_up, 0.0) / gamma)
                n_l = jnp.clip(n_l, 0, W).astype(jnp.int32)  # [R]
                _, _, mh1, ab1, alive1 = (a[c] for a in dp1)
                nlm1 = jnp.clip(n_l - 1, 0, W - 1)
                # The reference sizes each DP instance at ceil(horizon/grid)+2
                # bins and declares start_bin >= nbins infeasible; rebuild
                # that per-candidate bound from the shared prefix scan.
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

                _, _, mh0, ab0, alive0 = (a[c] for a in dp0)
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

                # Head-frame offload: register on the shared link (the audit
                # happens at actual completion in complete_batch — the
                # reference's on_offload path, NOT a plan-time score).
                j_star = j_best[r_star]
                st, act_bps = phys.register(
                    st, act_bps, c, on=planning & use_off, t0=t0, seq=k * N + rank,
                    grant=grant, bits=bits_r[r_star], ddl=t0 + deadline,
                    acc=acc_sv[j_star, r_star], tsv=t_srv[j_star],
                )

                horizon = jnp.where(
                    use_off, n_l[r_star] + 1, jnp.where(use_loc, A, 1)
                ).astype(jnp.int32)
                st = st._replace(
                    head=st.head.at[c].add(jnp.where(planning, horizon, 0)),
                    rounds=st.rounds.at[c].add(jnp.where(planning, 1, 0)),
                )
                return (st, act_bps,
                        planning_v.at[c].set(planning),
                        use_off_v.at[c].set(use_off),
                        use_loc_v.at[c].set(use_loc),
                        nn_v.at[c].set(nn),
                        npu_free_v.at[c].set(npu_free),
                        b0_off_v.at[c].set(ab1[nlm1[r_star]]),
                        b0_loc_v.at[c].set(ab0[jnp.clip(A - 1, 0, W - 1)]))

            return plan_one

        # Event-driven rounds, not frame ticks: the laggard client's head IS
        # the next plan event (heads advance by the full DP horizon, so most
        # ticks host no event at all), and a tick nobody plans at is not in
        # the reference's event set — draining there would add fluid-rate
        # re-evaluation points.  Visiting min(head) each iteration replays
        # plan events in exact time order; clients sharing the tick plan in
        # scheduler ``order`` inside plan_one.  Under vmap the while_loop
        # costs the batch-max round count — ~F/W iterations instead of F.
        def round_cond(st):
            return jnp.min(st.head) < F

        def round_body(st):
            k = jnp.min(st.head)
            t0 = _no_fma(k.astype(jnp.float64) * gamma, rounded)
            st = phys.drain(st, t0, advance_to_target=True)
            released = jnp.sum(
                (st.q_srvfin <= t0 + _EPS).astype(jnp.int32), axis=1
            )
            start_bins, dp1, dp0 = dp_tables(st, t0)
            zi = jnp.zeros((N,), jnp.int32)
            zb = jnp.zeros((N,), bool)
            zf = jnp.zeros((N,), jnp.float64)
            (st, _, planning, use_off, use_loc, nn, npu_free,
             b0_off, b0_loc) = jax.lax.fori_loop(
                0, N, make_plan_one(k, t0, released, start_bins, dp1, dp0),
                (st, phys.active_link_bps(st), zb, zb, zb, zi, zf, zi, zi),
            )

            # Picks backtracking and the frame audit depend only on the
            # client's own plan decision (``busy`` feeds nothing until the
            # next round's start_bin), so the heavy scans batch over clients
            # OUTSIDE the sequential allocate/register chain — mirroring the
            # dp_tables hoist on the way in.
            def finalize(c, off_c, loc_c, nn_c, free_c, b0_off_c, b0_loc_c,
                         on_c):
                picks_off = backtrack(dp1[0][c], dp1[1][c], b0_off_c,
                                      jnp.where(off_c, nn_c, 0))
                picks_loc = backtrack(dp0[0][c], dp0[1][c], b0_loc_c,
                                      jnp.where(loc_c, nn_c, 0))
                picks = jnp.where(off_c, picks_off, picks_loc)
                fa = jnp.where(off_c, gamma, 0.0)
                gate = on_c & (picks >= 0) & (ks < nn_c)
                free_end, acc_c, proc_c, miss_c, npu_c = _audit_scan(
                    head=k, frame_offset=jnp.where(off_c, 1, 0),
                    n_frames=F, n_active=n_active, arrivals=fa + arrivals,
                    deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat,
                    picks=picks, gate=gate, free0=jnp.maximum(free_c, 0.0),
                    acc_sum=st.accs[c], proc=st.proc[c], miss=st.miss[c],
                    npu_s=st.npus[c], W=W, J=J, strict=strict,
                )
                busy_until = jnp.where(off_c | loc_c, free_end, free_c)
                return acc_c, proc_c, miss_c, npu_c, busy_until

            acc_v, proc_v, miss_v, npu_v, busy_v = jax.vmap(finalize)(
                jnp.arange(N, dtype=jnp.int32), use_off, use_loc, nn,
                npu_free, b0_off, b0_loc, planning,
            )
            return st._replace(
                accs=jnp.where(planning, acc_v, st.accs),
                proc=jnp.where(planning, proc_v, st.proc),
                miss=jnp.where(planning, miss_v, st.miss),
                npus=jnp.where(planning, npu_v, st.npus),
                busy=jnp.where(planning, t0 + busy_v, st.busy),
            )

        st = jax.lax.while_loop(round_cond, round_body, phys.init_state())
        st = phys.finish(st)
        return (st.accs, st.proc, st.miss, st.offl, st.rounds, st.npus,
                st.grants, st.denials, st.sjobs, st.sbusy)

    return LaneProgram(one, (0,) * 20 + (None,) * 5)


@lru_cache(maxsize=None)
def _util_fleet_program(alloc: str, N: int, K: int, F: int, W: int, S: int,
                        J: int, R: int, strict: bool, width: int):
    def one(bw_t, bw_v, gamma, deadline, rtt, alpha, fps, L, n_w,
            arrivals, acc_stat, w_fluid, w_eff, tot_w, prio, order,
            bits_r, acc_sv, t_srv, acc_dp, t_npu64):
        phys = _fleet_physics(
            alloc, N, K, F, bw_t=bw_t, bw_v=bw_v, rtt=rtt, L=L,
            w_fluid=w_fluid, w_eff=w_eff, tot_w=tot_w, prio=prio,
        )
        ks = jnp.arange(W, dtype=jnp.int32)
        rounded = n_w > 0  # traced, always true: _no_fma's gate

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

        def plan_one(rank, carry):
            st, k, t0, released, act_bps, ovf = carry
            c = order[rank]
            planning = st.head[c] == k
            grant, denied = phys.allocate(st, c, t0, released, act_bps)
            st = st._replace(
                grants=st.grants + jnp.where(planning & ~denied, 1, 0),
                denials=st.denials + jnp.where(planning & denied, 1, 0),
            )

            npu_free = jnp.maximum(0.0, st.busy[c] - t0)
            t_up = jnp.where(grant > 0.0, bits_r / grant, jnp.inf)  # [R]
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
            ovf = ovf | (planning & (ov1 | ov2))
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

            # Head-frame offload: register on the shared link (audited at
            # actual completion — the reference's on_offload path).
            j0c = jnp.clip(j0, 0, J - 1)
            st, act_bps = phys.register(
                st, act_bps, c, on=planning & use_off, t0=t0, seq=k * N + rank,
                grant=grant, bits=bits_r[jnp.clip(r0, 0, R - 1)],
                ddl=t0 + deadline, acc=srv_acc, tsv=t_srv[j0c],
            )

            fa = jnp.where(use_off, gamma, 0.0)
            gate = planning & (picks >= 0) & (ks < nn)
            free0 = jnp.maximum(npu_free, 0.0)
            free_end, acc_c, proc_c, miss_c, npu_c = _audit_scan(
                head=st.head[c], frame_offset=jnp.where(use_off, 1, 0),
                n_frames=F, n_active=n_w, arrivals=fa + arrivals,
                deadline=deadline, t_npu64=t_npu64, acc_stat=acc_stat,
                picks=picks, gate=gate, free0=free0, acc_sum=st.accs[c],
                proc=st.proc[c], miss=st.miss[c], npu_s=st.npus[c],
                W=W, J=J, strict=strict,
            )
            busy_until = jnp.where(use_off | use_loc, free_end, npu_free)
            horizon = jnp.where(
                use_off, n_plan + 1, jnp.where(use_loc, n_w, 1)
            ).astype(jnp.int32)
            st = st._replace(
                accs=st.accs.at[c].set(acc_c),
                proc=st.proc.at[c].set(proc_c),
                miss=st.miss.at[c].set(miss_c),
                npus=st.npus.at[c].set(npu_c),
                head=st.head.at[c].add(jnp.where(planning, horizon, 0)),
                busy=st.busy.at[c].set(jnp.where(planning, t0 + busy_until, st.busy[c])),
                rounds=st.rounds.at[c].add(jnp.where(planning, 1, 0)),
            )
            return st, k, t0, released, act_bps, ovf

        # Event-driven rounds over min(head) — see _acc_fleet_program; the
        # overflow flag rides the carry so a too-narrow Pareto front in ANY
        # round marks the lane for the capped rerun.
        def round_cond(carry):
            st, _ = carry
            return jnp.min(st.head) < F

        def round_body(carry):
            st, ovf = carry
            k = jnp.min(st.head)
            t0 = _no_fma(k.astype(jnp.float64) * gamma, rounded)
            st = phys.drain(st, t0, advance_to_target=True)
            released = jnp.sum(
                (st.q_srvfin <= t0 + _EPS).astype(jnp.int32), axis=1
            )
            st, _, _, _, _, ovf = jax.lax.fori_loop(
                0, N, plan_one,
                (st, k, t0, released, phys.active_link_bps(st), ovf),
            )
            return st, ovf

        st, ovf = jax.lax.while_loop(
            round_cond, round_body, (phys.init_state(), jnp.zeros((), bool))
        )
        st = phys.finish(st)
        return (st.accs, st.proc, st.miss, st.offl, st.rounds, st.npus,
                st.grants, st.denials, st.sjobs, st.sbusy, ovf)

    return LaneProgram(one, (0,) * 16 + (None,) * 5)


# ---------------------------------------------------------------------------
# The local-only planner fleet programs: jax_accuracy / jax_utility.  Their
# plans never read the grant, so every client of a homogeneous fleet follows
# the identical trajectory — one lane per scenario reuses the single-stream
# sim_batch body verbatim (bit-identical per-client stats) and adds the
# scheduler's grant/denial bookkeeping: the reference still calls
# ``allocate`` once per client per plan event, and for a fleet that never
# takes a lease the gate outcome factors into a static per-client predicate
# (capacity <= 0, priority reservation, non-positive effective weight —
# ``den0`` clients) plus two time-varying shared terms (trace bandwidth
# non-positive, backlog clock past the limit) that deny everyone at once.
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _jax_acc_fleet_program(W: int, NBINS: int, S: int, J: int, strict: bool):
    def one(gamma, deadline, grid, n_active, nbins_real, n_frames,
            arr_bins, dl_bins, dur, arrivals, acc_stat,
            n_clients, den0, gated, L, bw_t, bw_v, t_npu64, acc_dp32):
        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, acc_sum, proc, miss, rounds, npu_s, grants, denials = c
            active = head < n_frames  # lane gating under vmap-of-while
            t0 = head.astype(jnp.float64) * gamma
            # Fleet bookkeeping: one allocate() per client per plan event.
            shared_den = gated & (
                (0.0 - t0 > L) | (_trace_bw(bw_t, bw_v, t0) <= 0.0)
            )
            den_n = jnp.where(shared_den, n_clients, den0)
            grants = grants + jnp.where(active, n_clients - den_n, 0)
            denials = denials + jnp.where(active, den_n, 0)
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
            return head, busy, acc_sum, proc, miss, rounds, npu_s, grants, denials

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[5], out[6], out[7], out[8]

    return LaneProgram(one, (0,) * 17 + (None,) * 2)


@lru_cache(maxsize=None)
def _jax_util_fleet_program(W: int, width: int, S: int, J: int, strict: bool):
    def one(gamma, deadline, n_active, n_frames, g32, d32, a32, w32,
            arrivals, acc_stat, n_clients, den0, gated, L, bw_t, bw_v,
            t_npu64, t_npu32, acc_dp32):
        def cond(c):
            return c[0] < n_frames

        def body(c):
            head, busy, acc_sum, proc, miss, rounds, npu_s, grants, denials = c
            active = head < n_frames
            t0 = head.astype(jnp.float64) * gamma
            shared_den = gated & (
                (0.0 - t0 > L) | (_trace_bw(bw_t, bw_v, t0) <= 0.0)
            )
            den_n = jnp.where(shared_den, n_clients, den0)
            grants = grants + jnp.where(active, n_clients - den_n, 0)
            denials = denials + jnp.where(active, den_n, 0)
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
            return head, busy, acc_sum, proc, miss, rounds, npu_s, grants, denials

        init = (
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float64),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float64), jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.int32),
        )
        out = jax.lax.while_loop(cond, body, init)
        return out[2], out[3], out[4], out[5], out[6], out[7], out[8]

    return LaneProgram(one, (0,) * 16 + (None,) * 3)


# ---------------------------------------------------------------------------
# Host drivers: f64 precomputation mirrors the reference expression by
# expression (frame bits, accuracy tables, bin edges, effective weights,
# plan-event ordering), then one compiled program per shape group.
# ---------------------------------------------------------------------------


def _stitch(scenarios, key_fn, run_group) -> list[tuple[MultiStreamStats, dict]]:
    groups: dict[Any, list[int]] = {}
    for i, s in enumerate(scenarios):
        groups.setdefault(key_fn(s), []).append(i)
    out: list[tuple[MultiStreamStats, dict] | None] = [None] * len(scenarios)
    for key in sorted(groups, key=repr):
        idx = groups[key]
        for i, st in zip(idx, run_group(key, [scenarios[i] for i in idx])):
            out[i] = st
    return out  # type: ignore[return-value]


def _segments(group: list[FleetScenario]):
    return segment_arrays(
        [s.bw_segments or ((0.0, s.bandwidth_bps),) for s in group]
    )


def _fleet_host_arrays(group: list[FleetScenario], N: int, alloc: str):
    """Per-lane scheduler tensors, the scalar reference arithmetic verbatim:
    fluid weights floor at ``_EPS`` (the reference's ``max(weight, _EPS)``),
    effective weights and their total use the scheduler's own expressions so
    shares match to the bit, and the plan-event order inside a tick is the
    reference's event key ``(t, -priority, -weight, client_id)``."""
    w = np.array(
        [s.weights if s.weights is not None else (1.0,) * N for s in group],
        np.float64,
    )
    prio = np.array(
        [s.priorities if s.priorities is not None else (0,) * N for s in group],
        np.int32,
    )
    w_fluid = np.maximum(w, _EPS)
    if alloc == "priority":
        w_eff = np.array(
            [[wi * (2.0 ** int(pi)) for wi, pi in zip(wr, pr)]
             for wr, pr in zip(w, prio)],
            np.float64,
        )
    else:
        w_eff = w.copy()
    tot_w = np.array([sum(row) or 1.0 for row in w_eff], np.float64)
    order = np.stack(
        [np.lexsort((np.arange(N), -wr, -pr)) for wr, pr in zip(w, prio)]
    ).astype(np.int32)
    return w_fluid, w_eff, tot_w, prio, order


def _shims(group: list[FleetScenario]) -> list[BatchScenario]:
    """Reuse sim_batch's per-scenario precomputation (``_common``) by
    presenting each fleet point as a single-stream scenario shape."""
    return [
        BatchScenario(stream=s.stream, n_frames=s.n_frames, params=s.params)
        for s in group
    ]


def _fleet_results(group, out, wall):
    """Per-client StreamStats + meta for the DP planner fleet drivers."""
    accs, proc, miss, offl, rounds, npus, grants, denials, sjobs, sbusy = out
    total_rounds = max(int(rounds.sum()), 1)
    results = []
    for b, s in enumerate(group):
        elapsed = s.n_frames * s.stream.gamma
        per_client = [
            StreamStats(
                frames_total=s.n_frames,
                frames_processed=int(proc[b, c]),
                frames_missed_deadline=int(miss[b, c]),
                frames_offloaded=int(offl[b, c]),
                accuracy_sum=float(accs[b, c]),
                elapsed=elapsed,
                schedule_calls=int(rounds[b, c]),
                # One device program schedules the whole group; report the
                # amortized per-round cost (as sim_batch does).
                schedule_time=wall * float(rounds[b, c]) / total_rounds,
                npu_busy_s=float(npus[b, c]),
            )
            for c in range(s.n_clients)
        ]
        ms = MultiStreamStats(
            per_client=per_client,
            server_jobs=int(sjobs[b]),
            server_busy_s=float(sbusy[b]),
            elapsed=elapsed,
        )
        results.append(
            (ms, {"grants": int(grants[b]), "denials": int(denials[b])})
        )
    return results


def _planner_group_key(s: FleetScenario) -> tuple:
    """Shape statics for the DP planner fleet programs: allocation / fleet
    size / capacity / frame count fix the link arrays; resolutions and
    png_ratio fix the (group-shared) payload and server-accuracy tables;
    the quantized window fixes the DP shapes."""
    return (
        s.allocation,
        int(s.n_clients),
        int(s.capacity),
        int(s.n_frames),
        tuple(s.stream.resolutions),
        float(s.stream.png_ratio),
        _quant_w(_window_frames(s.stream, s.params)),
    )


@_planner("offload")
def _run_offload(models, scenarios, strict):
    # ``strict`` has no observable effect here: offload plans contain no NPU
    # decisions, so the plan-time audit's bad set is empty either way.
    del strict
    t_srv = np.array([m.t_server for m in models], np.float64)

    def run_group(key, group):
        alloc, N, K, F, resolutions, png_ratio = key
        B_ = len(group)
        R = len(resolutions)
        # Frame payloads: frame_bytes(r) * 8.0, the value the reference
        # feeds both upload_time and _Upload.bits_left.
        bits_r = np.array(
            [group[0].stream.frame_bytes(r) * 8.0 for r in resolutions], np.float64
        )
        acc_sv = np.array(
            [[m.accuracy(r, where="server") for r in resolutions] for m in models],
            np.float64,
        )
        # Bandwidth trace segments in the shared on-device layout (sorting,
        # power-of-two padding, inert t_start=+inf sentinels — one
        # definition in sim_batch, read back by _trace_bw).
        bw_t, bw_v, S = _segments(group)
        gamma = np.array([s.stream.gamma for s in group], np.float64)
        T = np.array([s.stream.deadline for s in group], np.float64)
        rtt = np.array([s.rtt for s in group], np.float64)
        fps = np.array([s.stream.fps for s in group], np.float64)
        L = np.array([s.backlog_limit for s in group], np.float64)
        alpha_raw = [s.params.get("alpha") for s in group]
        alpha = np.array([a if a is not None else 0.0 for a in alpha_raw], np.float64)
        is_util = np.array([a is not None for a in alpha_raw], bool)
        w_fluid, w_eff, tot_w, prio, order = _fleet_host_arrays(group, N, alloc)

        program = _fleet_program(alloc, N, K, F, len(models), R, S)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = program(
                bw_t, bw_v, gamma, T, rtt, fps, L, alpha, is_util, w_fluid,
                w_eff, tot_w, prio, order, bits_r, acc_sv, t_srv,
            )
            accs, proc, miss, grants, denials, sjobs, sbusy = (
                np.asarray(a) for a in out
            )
        wall = time.perf_counter() - t0

        results = []
        for b, s in enumerate(group):
            elapsed = s.n_frames * s.stream.gamma
            per_client = [
                StreamStats(
                    frames_total=s.n_frames,
                    frames_processed=int(proc[b, c]),
                    frames_missed_deadline=int(miss[b, c]),
                    frames_offloaded=int(proc[b, c]),  # offload-only plans
                    accuracy_sum=float(accs[b, c]),
                    elapsed=elapsed,
                    schedule_calls=F,
                    # One device program schedules the whole group; report
                    # the amortized per-round cost (as sim_batch does).
                    schedule_time=wall * F / max(B_ * N * F, 1),
                    npu_busy_s=0.0,
                )
                for c in range(N)
            ]
            ms = MultiStreamStats(
                per_client=per_client,
                server_jobs=int(sjobs[b]),
                server_busy_s=float(sbusy[b]),
                elapsed=elapsed,
            )
            results.append(
                (ms, {"grants": int(grants[b]), "denials": int(denials[b])})
            )
        return results

    def key_fn(s: FleetScenario) -> tuple:
        return (
            s.allocation,
            int(s.n_clients),
            int(s.capacity),
            int(s.n_frames),
            tuple(s.stream.resolutions),
            float(s.stream.png_ratio),
        )

    return _stitch(scenarios, key_fn, run_group)


@_planner("max_accuracy")
def _run_max_accuracy_fleet(models, scenarios, strict):
    t_srv = np.array([m.t_server for m in models], np.float64)
    acc_dp = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float64
    )

    def run_group(key, group):
        alloc, N, K, F, resolutions, png_ratio, W = key
        c = _common(models, _shims(group), W)
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
        # Tight padding quantum: NBINS is derived per shape group (it is not
        # part of the group key), so a finer quantum costs no extra jit
        # compiles — and the fleet DP pays NBINS x rounds x N per lane,
        # where the single-stream planner pays it only once per window.
        NBINS = _quant_bins(int((np.ceil(horizon_t / grid) + 2).max()), q=32)
        with np.errstate(invalid="ignore"):
            dur_f = np.ceil(c.t_npu64[None, :] / grid[:, None])
        dur = np.where(np.isfinite(dur_f), np.minimum(dur_f, NBINS), NBINS).astype(np.int32)
        bits_r = np.array(
            [group[0].stream.frame_bytes(r) * 8.0 for r in resolutions], np.float64
        )
        acc_sv = np.array(
            [[m.accuracy(r, where="server") for r in resolutions] for m in models],
            np.float64,
        )
        bw_t, bw_v, S = _segments(group)
        rtt = np.array([s.rtt for s in group], np.float64)
        L = np.array([s.backlog_limit for s in group], np.float64)
        w_fluid, w_eff, tot_w, prio, order = _fleet_host_arrays(group, N, alloc)

        program = _acc_fleet_program(alloc, N, K, F, c.W, NBINS, S, c.J,
                                     len(resolutions), strict)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = program(
                bw_t, bw_v, c.gamma, c.deadline, rtt, grid, L, c.n_active,
                arr0, dl0, arr1, dl1, dur, c.arrivals, c.acc_stat64,
                w_fluid, w_eff, tot_w, prio, order,
                bits_r, acc_sv, t_srv, acc_dp, c.t_npu64,
            )
            out = [np.asarray(a) for a in out]
        return _fleet_results(group, out, time.perf_counter() - t0)

    return _stitch(scenarios, _planner_group_key, run_group)


@_planner("max_utility")
def _run_max_utility_fleet(models, scenarios, strict):
    t_srv = np.array([m.t_server for m in models], np.float64)
    acc_dp = np.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for m in models], np.float64
    )

    def run_group(key, group):
        alloc, N, K, F, resolutions, png_ratio, W = key
        c = _common(models, _shims(group), W)
        alpha = np.array([float(s.params["alpha"]) for s in group], np.float64)
        fps = np.array([s.stream.fps for s in group], np.float64)
        bits_r = np.array(
            [group[0].stream.frame_bytes(r) * 8.0 for r in resolutions], np.float64
        )
        acc_sv = np.array(
            [[m.accuracy(r, where="server") for r in resolutions] for m in models],
            np.float64,
        )
        bw_t, bw_v, S = _segments(group)
        rtt = np.array([s.rtt for s in group], np.float64)
        L = np.array([s.backlog_limit for s in group], np.float64)
        w_fluid, w_eff, tot_w, prio, order = _fleet_host_arrays(group, N, alloc)
        lane_args = (bw_t, bw_v, c.gamma, c.deadline, rtt, alpha, fps, L,
                     c.n_active, c.arrivals, c.acc_stat64,
                     w_fluid, w_eff, tot_w, prio, order)
        shared = (bits_r, acc_sv, t_srv, acc_dp, c.t_npu64)

        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _util_fleet_program(
                alloc, N, K, F, c.W, S, c.J, len(resolutions), strict,
                _UTIL_FAST_WIDTH,
            )(*lane_args, *shared)
            out = [np.array(a) for a in out]
            overflowed = np.nonzero(out[10])[0]
            if overflowed.size:
                # A Pareto front outgrew the fast width somewhere in these
                # lanes: rerun just them at the reference prune cap (exact
                # for any front size) and splice the results back in.
                sub = _util_fleet_program(
                    alloc, N, K, F, c.W, S, c.J, len(resolutions), strict,
                    _UTIL_CAP,
                )(*(a[overflowed] for a in lane_args), *shared)
                for dst, src in zip(out[:10], sub[:10]):
                    dst[overflowed] = np.asarray(src)
        return _fleet_results(group, out[:10], time.perf_counter() - t0)

    return _stitch(scenarios, _planner_group_key, run_group)


def _jax_fleet_lane_arrays(group: list[FleetScenario]):
    """Host mirrors of the allocation gates that are *static* for local-only
    plans: no lease is ever taken, so every ``allocate`` call sees the same
    scheduler state and only the trace bandwidth and the backlog clock vary.
    ``den0`` counts clients denied by the static gates (capacity <= 0,
    priority reservation over an empty lease table, non-positive effective
    weight or weight total); ``gated`` marks non-fifo lanes (fifo always
    grants)."""
    n_clients = np.array([s.n_clients for s in group], np.int32)
    den0 = np.zeros(len(group), np.int32)
    gated = np.zeros(len(group), bool)
    L = np.array([s.backlog_limit for s in group], np.float64)
    for i, s in enumerate(group):
        if s.allocation == "fifo":
            continue
        gated[i] = True
        N = s.n_clients
        w = np.array(
            s.weights if s.weights is not None else (1.0,) * N, np.float64
        )
        pr = np.array(
            s.priorities if s.priorities is not None else (0,) * N, np.int64
        )
        if s.allocation == "priority":
            w_eff = np.array(
                [wi * (2.0 ** int(pi)) for wi, pi in zip(w, pr)], np.float64
            )
            reserved = np.array(
                [s.capacity <= int(np.sum(pr > pr[ci])) for ci in range(N)], bool
            )
        else:
            w_eff = w
            reserved = np.zeros(N, bool)
        tot = float(sum(w_eff)) or 1.0
        d0 = (s.capacity <= 0) | reserved | (w_eff <= 0.0) | (tot <= 0.0)
        den0[i] = int(d0.sum())
    bw_t, bw_v, S = _segments(group)
    return n_clients, den0, gated, L, bw_t, bw_v, S


def _replicated_results(group, base, grants, denials):
    """Fleet reports for the local-only planners: every client of a
    homogeneous fleet follows the identical trajectory, so the per-lane
    single-stream stats replicate per client; the server never runs a job
    (no offloads), matching the reference's zero counters."""
    results = []
    for b, (s, st) in enumerate(zip(group, base)):
        per_client = [replace(st) for _ in range(s.n_clients)]
        ms = MultiStreamStats(
            per_client=per_client,
            server_jobs=0,
            server_busy_s=0.0,
            elapsed=st.elapsed,
        )
        results.append(
            (ms, {"grants": int(grants[b]), "denials": int(denials[b])})
        )
    return results


@_planner("jax_accuracy")
def _run_jax_accuracy_fleet(models, scenarios, strict):
    def run_group(W, group):
        c = _common(models, _shims(group), W)
        grid = np.array([float(s.params["grid"]) for s in group], np.float64)
        # Bin arithmetic in f64 on the host — the same numpy expressions as
        # sim_batch._run_accuracy (and local_accuracy_dp_jax before it).
        arr_bins = np.ceil(c.arrivals / grid[:, None]).astype(np.int32)
        dl_bins = np.floor(
            (c.arrivals + c.deadline[:, None]) / grid[:, None]
        ).astype(np.int32)
        horizon_t = (c.n_active.astype(np.float64) - 1.0) * c.gamma + c.deadline
        nbins_real = (np.ceil(horizon_t / grid) + 2).astype(np.int32)
        NBINS = _quant_bins(int(nbins_real.max()))
        with np.errstate(invalid="ignore"):
            dur_f = np.ceil(c.t_npu64[None, :] / grid[:, None])
        dur = np.where(np.isfinite(dur_f), np.minimum(dur_f, NBINS), NBINS).astype(np.int32)
        ncl, den0, gated, L, bw_t, bw_v, S = _jax_fleet_lane_arrays(group)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _jax_acc_fleet_program(c.W, NBINS, S, c.J, strict)(
                c.gamma, c.deadline, grid, c.n_active, nbins_real, c.n_frames,
                arr_bins, dl_bins, dur, c.arrivals, c.acc_stat64,
                ncl, den0, gated, L, bw_t, bw_v, c.t_npu64, c.acc_dp32,
            )
            out = [np.asarray(a) for a in out]
        base = _collect(c, out[:5], time.perf_counter() - t0)
        return _replicated_results(group, base, out[5], out[6])

    return _stitch(
        scenarios, lambda s: _quant_w(_window_frames(s.stream, s.params)), run_group
    )


@_planner("jax_utility")
def _run_jax_utility_fleet(models, scenarios, strict):
    def run_group(key, group):
        W, width = key
        c = _common(models, _shims(group), W)
        alpha = np.array([float(s.params["alpha"]) for s in group], np.float64)
        # The f32 casts the reference wrapper performs, precomputed in bulk.
        g32 = c.gamma.astype(np.float32)
        d32 = c.deadline.astype(np.float32)
        a32 = alpha.astype(np.float32)
        window = np.maximum(c.n_active.astype(np.float64) * c.gamma, c.gamma)
        w32 = window.astype(np.float32)
        t_npu32 = c.t_npu64.astype(np.float32)
        ncl, den0, gated, L, bw_t, bw_v, S = _jax_fleet_lane_arrays(group)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = _jax_util_fleet_program(c.W, width, S, c.J, strict)(
                c.gamma, c.deadline, c.n_active, c.n_frames,
                g32, d32, a32, w32, c.arrivals, c.acc_stat64,
                ncl, den0, gated, L, bw_t, bw_v,
                c.t_npu64, t_npu32, c.acc_dp32,
            )
            out = [np.asarray(a) for a in out]
        base = _collect(c, out[:5], time.perf_counter() - t0)
        return _replicated_results(group, base, out[5], out[6])

    return _stitch(
        scenarios,
        lambda s: (_quant_w(_window_frames(s.stream, s.params)), int(s.params["width"])),
        run_group,
    )


# ---------------------------------------------------------------------------
# Detect+track fleet planners: the sim_batch closed-form round (interval-mean
# candidate scoring, no bin DP) composed with the shared fleet physics.
# Detections contend — an offloaded detection registers on the fluid uplink
# and is audited (and installed into the client's detection state) at actual
# on-time completion, the reference's on_offload path — while tracker-carried
# frames are free local work that scores at the plan event against the state
# current there.  The detection state is the max-det_frame merge of plan-time
# NPU refreshes and completed on-time offloads, recomputed from the upload
# logs after every link drain (NPU refreshes always carry the newest frame at
# their plan event, and completion installs are recency-guarded in the
# reference, so the merge reproduces the event-ordered updates exactly).
# ---------------------------------------------------------------------------


@lru_cache(maxsize=None)
def _track_fleet_program(alloc: str, N: int, K: int, F: int, KQ: int, S: int,
                         J: int, R: int, fixed: bool):
    A = F + 1  # retention-table width: ages reach F with the -1 initial state

    def one(bw_t, bw_v, gamma, deadline, rtt, L, k_lim, im, ret_pow, acc_stat,
            w_fluid, w_eff, tot_w, prio, order, bits_r, acc_sv, t_srv, t_npu64):
        phys = _fleet_physics(
            alloc, N, K, F, bw_t=bw_t, bw_v=bw_v, rtt=rtt, L=L,
            w_fluid=w_fluid, w_eff=w_eff, tot_w=tot_w, prio=prio,
        )
        cids = jnp.arange(N, dtype=jnp.int32)
        rounded = k_lim > 0  # traced, always true: _no_fma's gate
        # NPU candidates are round-invariant: j ascending, npu_interval.
        local = jnp.isfinite(t_npu64)
        kf = jnp.where(local, jnp.ceil(t_npu64 / gamma), 0.0)
        k_npu = jnp.maximum(kf.astype(jnp.int32), 1)  # [J]

        def make_plan_one(k, t0, released):
            def plan_one(rank, pc):
                (st, act_bps, det_acc, det_frm, q_detfrm,
                 planning_v, off0_v, hor_v) = pc
                c = order[rank]
                planning = st.head[c] == k
                grant, denied = phys.allocate(st, c, t0, released, act_bps)
                st = st._replace(
                    grants=st.grants + jnp.where(planning & ~denied, 1, 0),
                    denials=st.denials + jnp.where(planning & denied, 1, 0),
                )
                npu_free = jnp.maximum(0.0, st.busy[c] - t0)
                feas_npu = local & (npu_free + t_npu64 <= deadline) & (k_npu <= k_lim)
                # The reference plans against NetworkState(grant, rtt).
                t_up = jnp.where(grant > 0.0, bits_r / grant, jnp.inf)  # [R]
                budget = deadline - t_up - rtt  # [R]
                fits = t_srv[:, None] <= budget[None, :]  # [J, R]
                a_cand = jnp.where(fits, acc_sv, -jnp.inf)
                j_best = jnp.argmax(a_cand, axis=0).astype(jnp.int32)  # first max
                a_best = jnp.max(a_cand, axis=0)
                r_ok = (budget > 0.0) & jnp.any(fits, axis=0)
                k_srv = jnp.floor(
                    jnp.where(r_ok, t_up, 0.0) / gamma
                ).astype(jnp.int32) + 1
                feas_srv = r_ok & (k_srv <= k_lim)
                if fixed:
                    s_npu = jnp.where(feas_npu, acc_stat, -jnp.inf)
                    s_srv = jnp.where(feas_srv, a_best, -jnp.inf)
                else:
                    s_npu = jnp.where(
                        feas_npu,
                        acc_stat * im[jnp.clip(k_npu - 1, 0, KQ - 1)], -jnp.inf,
                    )
                    s_srv = jnp.where(
                        feas_srv,
                        a_best * im[jnp.clip(k_srv - 1, 0, KQ - 1)], -jnp.inf,
                    )
                # NPU-then-server candidate order with strict > first-wins ==
                # first-maximum argmax over the concatenation (sim_batch's
                # rendering of the reference planners).
                scores = jnp.concatenate([s_npu, s_srv])
                idx = jnp.argmax(scores).astype(jnp.int32)
                exists = scores[idx] > -jnp.inf
                is_npu = exists & (idx < J)
                is_srv = exists & ~is_npu
                j_pick = jnp.clip(idx, 0, J - 1)
                r_pick = jnp.clip(idx - J, 0, R - 1)
                k_det = jnp.where(is_npu, k_npu[j_pick], k_srv[r_pick])
                if fixed:
                    horizon = k_lim  # the interval is consumed even on SKIP
                else:
                    horizon = jnp.where(exists, k_det, 1)
                # NPU detection: scored and state-refreshed at the plan event.
                npu_take = planning & is_npu
                acc_j = acc_stat[j_pick]
                st = st._replace(
                    accs=st.accs.at[c].add(jnp.where(npu_take, acc_j, 0.0)),
                    proc=st.proc.at[c].add(jnp.where(npu_take, 1, 0)),
                    npus=st.npus.at[c].add(
                        jnp.where(npu_take, t_npu64[j_pick], 0.0)
                    ),
                )
                det_acc = det_acc.at[c].set(jnp.where(npu_take, acc_j, det_acc[c]))
                det_frm = det_frm.at[c].set(jnp.where(npu_take, k, det_frm[c]))
                # Offloaded detection: register on the shared link (audited
                # and installed at actual completion); state stays stale for
                # this round's tracked frames, exactly like on_offload.
                on_srv = planning & is_srv
                j_star = j_best[r_pick]
                e = jnp.clip(st.tail[c], 0, F - 1)
                q_detfrm = q_detfrm.at[c, e].set(
                    jnp.where(on_srv, k, q_detfrm[c, e])
                )
                st, act_bps = phys.register(
                    st, act_bps, c, on=on_srv, t0=t0, seq=k * N + rank,
                    grant=grant, bits=bits_r[r_pick], ddl=t0 + deadline,
                    acc=acc_sv[j_star, r_pick], tsv=t_srv[j_star],
                )
                busy_until = jnp.where(is_npu, npu_free + t_npu64[j_pick], npu_free)
                st = st._replace(
                    busy=st.busy.at[c].set(
                        jnp.where(planning, t0 + busy_until, st.busy[c])
                    ),
                    head=st.head.at[c].add(jnp.where(planning, horizon, 0)),
                    rounds=st.rounds.at[c].add(jnp.where(planning, 1, 0)),
                )
                return (st, act_bps, det_acc, det_frm, q_detfrm,
                        planning_v.at[c].set(planning),
                        off0_v.at[c].set(jnp.where(exists, 1, 0)),
                        hor_v.at[c].set(horizon))

            return plan_one

        def round_cond(carry):
            return jnp.min(carry[0].head) < F

        def round_body(carry):
            st, det_acc, det_frm, q_detfrm = carry
            k = jnp.min(st.head)
            t0 = _no_fma(k.astype(jnp.float64) * gamma, rounded)
            st = phys.drain(st, t0, advance_to_target=True)
            released = jnp.sum(
                (st.q_srvfin <= t0 + _EPS).astype(jnp.int32), axis=1
            )
            # Install completed on-time offloaded detections: recency-merge
            # the newest (max det_frame) against the plan-time NPU state.
            done = st.q_srvfin + rtt <= st.q_ddl + _EPS
            m_frm = jnp.where(done, q_detfrm, -1)
            bi = jnp.argmax(m_frm, axis=1).astype(jnp.int32)
            srv_frm = m_frm[cids, bi]
            newer = srv_frm > det_frm
            det_frm = jnp.where(newer, srv_frm, det_frm)
            det_acc = jnp.where(newer, st.q_acc[cids, bi], det_acc)

            zb = jnp.zeros((N,), bool)
            zi = jnp.zeros((N,), jnp.int32)
            (st, _, det_acc, det_frm, q_detfrm,
             planning, off0, hor) = jax.lax.fori_loop(
                0, N, make_plan_one(k, t0, released),
                (st, phys.active_link_bps(st), det_acc, det_frm, q_detfrm,
                 zb, zi, zi),
            )

            # Tracked frames depend only on the client's own post-plan state,
            # so the sequential fold batches over clients OUTSIDE the
            # allocate/register chain (ascending frame order per client —
            # the apply_track_round accumulation order).
            def finalize(c, on_c, off0_c, hor_c):
                def tr(o, a_pr):
                    a_s, pr = a_pr
                    on = on_c & (o >= off0_c) & (o < hor_c) & (k + o < F)
                    age = jnp.clip(k + o - det_frm[c], 0, A - 1)
                    v = _no_fma(det_acc[c] * ret_pow[age], rounded)
                    return a_s + jnp.where(on, v, 0.0), pr + on.astype(jnp.int32)

                return jax.lax.fori_loop(0, KQ, tr, (st.accs[c], st.proc[c]))

            acc_v, proc_v = jax.vmap(finalize)(cids, planning, off0, hor)
            st = st._replace(
                accs=jnp.where(planning, acc_v, st.accs),
                proc=jnp.where(planning, proc_v, st.proc),
            )
            return st, det_acc, det_frm, q_detfrm

        init = (
            phys.init_state(),
            jnp.zeros((N,), jnp.float64),
            jnp.full((N,), -1, jnp.int32),
            jnp.full((N, F), -1, jnp.int32),
        )
        st = phys.finish(jax.lax.while_loop(round_cond, round_body, init)[0])
        return (st.accs, st.proc, st.miss, st.offl, st.rounds, st.npus,
                st.grants, st.denials, st.sjobs, st.sbusy)

    return LaneProgram(one, (0,) * 15 + (None,) * 4)


def _run_track_fleet(models, scenarios, strict, *, fixed: bool):
    # ``strict`` has no observable effect: the plan-time audit is NPU-only
    # here (offloads audit at completion), and the track planners only emit
    # deadline-feasible NPU detections.
    del strict
    t_srv = np.array([m.t_server for m in models], np.float64)
    kname = "k" if fixed else "k_max"

    def key_fn(s: FleetScenario) -> tuple:
        return (
            s.allocation,
            int(s.n_clients),
            int(s.capacity),
            int(s.n_frames),
            tuple(s.stream.resolutions),
            float(s.stream.png_ratio),
            _quant_w(int(s.params[kname])),
        )

    def run_group(key, group):
        alloc, N, K, F, resolutions, png_ratio, KQ = key
        R = len(resolutions)
        c = _common(models, _shims(group), 1)  # windows are a classify concept
        B_ = len(group)
        k_lim = np.array([int(s.params[kname]) for s in group], np.int32)
        im = np.zeros((B_, KQ), np.float64)
        if not fixed:
            # interval_means is prefix-stable: padding KQ past a lane's k_max
            # cannot change any entry the planner may select.
            for i, s in enumerate(group):
                ret_b = retention(float(s.params["decay"]), float(s.params["density"]))
                im[i, :] = interval_means(ret_b, KQ)
        ret_pow = np.empty((B_, F + 1), np.float64)
        for i, s in enumerate(group):
            ret_pow[i, :] = retention_powers(s.workload.retention, F + 1)
        bits_r = np.array(
            [group[0].stream.frame_bytes(r) * 8.0 for r in resolutions], np.float64
        )
        acc_sv = np.array(
            [[m.accuracy(r, where="server") for r in resolutions] for m in models],
            np.float64,
        )
        bw_t, bw_v, S = _segments(group)
        rtt = np.array([s.rtt for s in group], np.float64)
        L = np.array([s.backlog_limit for s in group], np.float64)
        w_fluid, w_eff, tot_w, prio, order = _fleet_host_arrays(group, N, alloc)

        program = _track_fleet_program(alloc, N, K, F, KQ, S, c.J, R, fixed)
        t0 = time.perf_counter()
        with jax.enable_x64(True):
            out = program(
                bw_t, bw_v, c.gamma, c.deadline, rtt, L, k_lim, im, ret_pow,
                c.acc_stat64, w_fluid, w_eff, tot_w, prio, order,
                bits_r, acc_sv, t_srv, c.t_npu64,
            )
            out = [np.asarray(a) for a in out]
        return _fleet_results(group, out, time.perf_counter() - t0)

    return _stitch(scenarios, key_fn, run_group)


@_planner("track_accuracy")
def _run_track_accuracy_fleet(models, scenarios, strict):
    return _run_track_fleet(models, scenarios, strict, fixed=False)


@_planner("track_fixed")
def _run_track_fixed_fleet(models, scenarios, strict):
    return _run_track_fleet(models, scenarios, strict, fixed=True)
