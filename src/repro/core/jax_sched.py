"""Jitted (jax.lax) implementations of the two scheduling DPs.

The Python implementations in max_accuracy/max_utility are the reference
semantics; these run the same recurrences as fixed-shape tensor programs so a
serving loop can schedule *on device* in microseconds (the paper reports
< 1 ms on a phone CPU; ``benchmarks/paper_figures.py::sched_latency``
measures ours).

  local_accuracy_dp_jax   H(k, t) over a time grid     (scan over frames)
  local_utility_dp_jax    fixed-width Pareto front DP  (scan over frames)

Both return enough (choice/parent) state to extract the argmax schedule on
the host; tests assert exact agreement with the Python reference.

The underlying kernels (``_accuracy_dp`` / ``_utility_dp``) are also the
batched entry points used by :mod:`repro.core.sim_batch`: every dtype is
pinned explicitly (so tracing inside an ``enable_x64`` context cannot
silently promote the f32 recurrences to f64 and drift from the reference),
and both take a *traced* ``n_active`` frame count — frames ``k >= n_active``
are pass-through no-ops (identity parents, choice ``-1``), which lets a
``vmap`` over scenarios with different window lengths share one padded
compiled shape.  Registered policies here declare ``batched=True`` so
``Session.run_sweep`` can route them through the vectorized backend.

A second kernel pair (``_accuracy_dp64`` / ``_utility_dp64``) serves the
*network-aware* batched planners for the paper's own ``max_accuracy`` /
``max_utility`` policies: those Python references run their DPs in float64,
so the twins pin f64 (they must trace inside ``enable_x64``) and reproduce
every sequential tie-break of the reference loops.  The offload phase —
upload time from the granted bandwidth, RTT, edge-vs-NPU choice for the
head frame — lives in the round programs of :mod:`repro.core.sim_batch`,
which feed these kernels the local-phase instances each round's bandwidth
implies.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .profiles import ModelProfile, NetworkState, StreamSpec
from .registry import Param, register_policy
from .schedule import Decision, RoundPlan, Where

NEG = -1e18


# ---------------------------------------------------------------------------
# Max-Accuracy local phase (Eq. 7/8)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_frames", "nbins"))
def _accuracy_dp(
    dur: jax.Array,  # [J] duration bins (int32, precomputed host-side in f64)
    acc: jax.Array,  # [J]
    arr_bins: jax.Array,  # [n_frames] int32
    dl_bins: jax.Array,  # [n_frames] int32
    start_bin: jax.Array,  # [] int32
    n_active: jax.Array | int | None = None,  # [] int32; frames >= this are no-ops
    *,
    n_frames: int,
    nbins: int,
):
    J = dur.shape[0]
    bins = jnp.arange(nbins, dtype=jnp.int32)
    if n_active is None:
        n_active = n_frames
    n_active = jnp.asarray(n_active, jnp.int32)

    H0 = jnp.full((nbins,), NEG, dtype=jnp.float32)
    H0 = H0.at[jnp.clip(start_bin, 0, nbins - 1)].set(0.0)

    def step(H, k):
        arr_bin = arr_bins[k]
        dl_bin = dl_bins[k]
        # prefix max (and argmax) of H over [0, arr_bin]
        masked = jnp.where(bins <= arr_bin, H, NEG)
        pre_val = jnp.max(masked)
        pre_arg = jnp.argmax(masked).astype(jnp.int32)

        def per_model(j):
            d = dur[j]
            a = acc[j]
            # Case A: NPU free <= arrival, finish at arr_bin + d.
            fbA = arr_bin + d
            okA = (fbA <= dl_bin) & (fbA < nbins) & (pre_val > NEG / 2)
            valA = jnp.where((bins == fbA) & okA, pre_val + a, NEG)
            parA = jnp.where((bins == fbA) & okA, pre_arg, -1)
            # Case B: free after arrival; target b takes from source b - d.
            src = bins - d
            okB = (src > arr_bin) & (src >= 0) & (bins <= dl_bin)
            gathered = jnp.where(okB, H[jnp.clip(src, 0, nbins - 1)], NEG)
            valB = jnp.where(gathered > NEG / 2, gathered + a, NEG)
            parB = jnp.where(valB > NEG / 2, jnp.clip(src, 0, nbins - 1), -1)
            val = jnp.where(valA >= valB, valA, valB)
            par = jnp.where(valA >= valB, parA, parB)
            return val, par

        vals, pars = jax.vmap(per_model)(jnp.arange(J, dtype=jnp.int32))  # [J, nbins]
        best_j = jnp.argmax(vals, axis=0)  # [nbins]
        Hn = jnp.take_along_axis(vals, best_j[None], axis=0)[0]
        parent = jnp.take_along_axis(pars, best_j[None], axis=0)[0]
        choice = jnp.where(Hn > NEG / 2, best_j.astype(jnp.int32), -1)
        parent = jnp.where(Hn > NEG / 2, parent, -1)
        # Padded frame (k >= n_active): identity pass-through, no decision.
        on = k < n_active
        Hn = jnp.where(on, Hn, H)
        choice = jnp.where(on, choice, -1)
        parent = jnp.where(on, parent, bins)
        return Hn, (choice, parent)

    H, (choices, parents) = jax.lax.scan(step, H0, jnp.arange(n_frames, dtype=jnp.int32))
    return H, choices, parents


def local_accuracy_dp_jax(
    models: Sequence[ModelProfile],
    *,
    n_frames: int,
    gamma: float,
    deadline: float,
    npu_free: float,
    first_arrival: float,
    grid: float = 1e-3,
):
    """Mirror of max_accuracy.local_dp; returns (total, model per frame) or
    (NEG, []) when infeasible."""
    local = [(j, m) for j, m in enumerate(models) if m.runs_local]
    if n_frames <= 0:
        return 0.0, []
    if not local:
        return NEG, []
    acc = jnp.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for _, m in local], dtype=jnp.float32
    )
    horizon = first_arrival + (n_frames - 1) * gamma + deadline
    nbins = int(np.ceil(horizon / grid)) + 2
    # Bin arithmetic in f64 on the host — identical to max_accuracy.local_dp,
    # so the two implementations agree exactly (no f32 boundary flips).
    dur = jnp.asarray([int(np.ceil(m.t_npu / grid)) for _, m in local], jnp.int32)
    arrivals = first_arrival + np.arange(n_frames) * gamma
    arr_bins = jnp.asarray(np.ceil(arrivals / grid).astype(np.int32))
    dl_bins = jnp.asarray(np.floor((arrivals + deadline) / grid).astype(np.int32))
    start_bin = jnp.asarray(int(np.ceil(max(npu_free, 0.0) / grid)), jnp.int32)
    H, choices, parents = _accuracy_dp(
        dur, acc, arr_bins, dl_bins, start_bin, n_frames=n_frames, nbins=nbins
    )
    H = np.asarray(H)
    total = float(H.max())
    if total <= NEG / 2:
        return NEG, []
    choices = np.asarray(choices)
    parents = np.asarray(parents)
    b = int(H.argmax())
    out = []
    for k in range(n_frames - 1, -1, -1):
        out.append(local[int(choices[k, b])][0])
        b = int(parents[k, b])
    out.reverse()
    return total, out


# ---------------------------------------------------------------------------
# Max-Utility local phase (dominance-pruned triples) — fixed-width front
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_frames", "width"))
def _utility_dp(
    t_npu: jax.Array,  # [J]
    acc: jax.Array,  # [J]
    n_active: jax.Array | int | None = None,  # [] int32; frames >= this are no-ops
    *,
    n_frames: int,
    width: int,
    gamma: jax.Array,
    deadline: jax.Array,
    alpha: jax.Array,
    npu_free: jax.Array,
    first_arrival: jax.Array,
    window: jax.Array,
):
    J = t_npu.shape[0]
    BIG_T = 1e9
    if n_active is None:
        n_active = n_frames
    n_active = jnp.asarray(n_active, jnp.int32)

    t0 = jnp.full((width,), BIG_T, dtype=jnp.float32).at[0].set(jnp.maximum(npu_free, 0.0))
    u0 = jnp.full((width,), NEG, dtype=jnp.float32).at[0].set(0.0)
    m0 = jnp.zeros((width,), jnp.int32)
    valid0 = jnp.zeros((width,), bool).at[0].set(True)
    slots = jnp.arange(width, dtype=jnp.int32)

    def step(state, k):
        t, u, m, valid = state
        arrival = first_arrival + k * gamma
        # Candidates: carry-over (slot s, action -1) + process with model j.
        def proc(j):
            t2 = jnp.maximum(t, arrival) + t_npu[j]
            ok = valid & (t2 <= arrival + deadline + 1e-12)
            # f32 division pinned explicitly: under enable_x64, i32/i32 would
            # promote to f64 and drift from the reference recurrence.
            mf = m.astype(jnp.float32)
            mean_term = (mf / (mf + 1)) * (u - mf / window) + alpha * acc[j] / (mf + 1)
            u2 = mean_term + (mf + 1) / window
            return (
                jnp.where(ok, t2, BIG_T),
                jnp.where(ok, u2, NEG),
                jnp.where(ok, m + 1, 0),
                ok,
            )

        pt, pu, pm, pok = jax.vmap(proc)(jnp.arange(J, dtype=jnp.int32))  # [J, width]
        ct = jnp.concatenate([t, pt.reshape(-1)])
        cu = jnp.concatenate([u, pu.reshape(-1)])
        cm = jnp.concatenate([m, pm.reshape(-1)])
        cparent = jnp.concatenate([slots, jnp.tile(slots, J)])
        caction = jnp.concatenate(
            [jnp.full((width,), -1, jnp.int32), jnp.repeat(jnp.arange(J, dtype=jnp.int32), width)]
        )
        cok = jnp.concatenate([valid, pok.reshape(-1)])
        cu = jnp.where(cok, cu, NEG)
        ct = jnp.where(cok, ct, BIG_T)
        # Pareto prune: sort by (t asc, u desc); keep strictly-rising u —
        # exactly the permutation jnp.lexsort((-cu, ct)) produced.  This
        # step runs window-times per scheduling round, and on CPU tuple
        # sorts and batched scatters are serial, so sweep wall-clock lives
        # and dies here.  Invalid candidates need no explicit flag past this
        # point: they carry (BIG_T, NEG) keys, sort strictly after every
        # valid entry (valid t is bounded by arrival+deadline << BIG_T), and
        # NEG can never beat the strictly-rising-u running max below.
        if jax.dtypes.canonicalize_dtype(jnp.int64) == jnp.int64:
            # x64 (the sim_batch sweep path): two SINGLE-int64 sorts — XLA
            # CPU's fast path — replace the slow generic tuple comparator.
            # Each i64 = (order-isomorphic f32 key << 32) | index; the index
            # doubles as the explicit stable tie-break, so sorting by -cu
            # then (stably, via carried rank) by ct yields the identical
            # total order: (ct, -cu, original position).  Original f32 bits
            # flow through the permutation gather untouched.
            def okey(x):  # monotone f32 -> int64 in [-2^31, 2^31)
                b = jax.lax.bitcast_convert_type(x + jnp.float32(0.0), jnp.int32)
                b = b.astype(jnp.int64)
                return jnp.where(b >= 0, b, jnp.int64(-2147483649) - b)

            idx64 = jnp.arange(ct.shape[0], dtype=jnp.int64)
            by_u = jax.lax.sort(((okey(-cu) << 32) | idx64,), num_keys=1)[0]
            idx_u = (by_u & 0xFFFFFFFF).astype(jnp.int32)
            by_t = jax.lax.sort(((okey(ct)[idx_u] << 32) | idx64,), num_keys=1)[0]
            perm = idx_u[(by_t & 0xFFFFFFFF).astype(jnp.int32)]
        else:
            # x32 (the per-round reference path, batch of one): a stable
            # 3-operand sort whose index payload IS the permutation.
            idx = jnp.arange(ct.shape[0], dtype=jnp.int32)
            perm = jax.lax.sort((ct, -cu, idx), num_keys=2, is_stable=True)[2]
        ct, cu, cm = ct[perm], cu[perm], cm[perm]
        cparent, caction = cparent[perm], caction[perm]
        run = jax.lax.cummax(cu)
        prev_run = jnp.concatenate([jnp.array([NEG], dtype=cu.dtype), run[:-1]])
        keep = cu > prev_run + 1e-12
        # Compact keepers to the front, truncate to width: the r-th output
        # slot gathers the r-th keeper (keepers already sit in rank order),
        # located by searchsorted over the keep-count prefix sum.  Exactly
        # the slots/fill values of a scatter-with-drop by rank, scatter-free.
        csum = jnp.cumsum(keep.astype(jnp.int32))
        pos = jnp.clip(
            jnp.searchsorted(csum, jnp.arange(1, width + 1, dtype=jnp.int32)),
            0, ct.shape[0] - 1,
        )
        filled = slots < csum[-1]
        nt = jnp.where(filled, ct[pos], BIG_T)
        nu = jnp.where(filled, cu[pos], NEG)
        nm = jnp.where(filled, cm[pos], 0)
        nok = filled
        nparent = jnp.where(filled, cparent[pos], -1)
        naction = jnp.where(filled, caction[pos], -1)
        # Padded frame (k >= n_active): identity pass-through, no decision.
        on = k < n_active
        nt = jnp.where(on, nt, t)
        nu = jnp.where(on, nu, u)
        nm = jnp.where(on, nm, m)
        nok = jnp.where(on, nok, valid)
        nparent = jnp.where(on, nparent, slots)
        naction = jnp.where(on, naction, -1)
        return (nt, nu, nm, nok), (nparent, naction, nu)

    state, (parents, actions, us) = jax.lax.scan(
        step, (t0, u0, m0, valid0), jnp.arange(n_frames, dtype=jnp.int32)
    )
    return state, parents, actions, us


def local_utility_dp_jax(
    models: Sequence[ModelProfile],
    *,
    n_frames: int,
    gamma: float,
    deadline: float,
    alpha: float,
    npu_free: float,
    first_arrival: float,
    window: float,
    width: int = 64,
):
    """Mirror of max_utility.local_utility_dp; returns (utility, [(k, j)])."""
    if n_frames <= 0:
        return 0.0, []
    local = [(j, m) for j, m in enumerate(models) if m.runs_local]
    if not local:
        return 0.0, []
    t_npu = jnp.array([m.t_npu for _, m in local], dtype=jnp.float32)
    acc = jnp.array(
        [m.acc_npu[max(m.acc_npu)] if m.acc_npu else 0.0 for _, m in local], dtype=jnp.float32
    )
    (t, u, m, valid), parents, actions, us = _utility_dp(
        t_npu,
        acc,
        n_frames=n_frames,
        width=width,
        gamma=jnp.float32(gamma),
        deadline=jnp.float32(deadline),
        alpha=jnp.float32(alpha),
        npu_free=jnp.float32(npu_free),
        first_arrival=jnp.float32(first_arrival),
        window=jnp.float32(max(window, gamma)),
    )
    u = np.asarray(u)
    best_slot = int(u.argmax())
    best_u = float(u[best_slot])
    parents = np.asarray(parents)
    actions = np.asarray(actions)
    decisions: list[tuple[int, int]] = []
    slot = best_slot
    for k in range(n_frames - 1, -1, -1):
        a = int(actions[k, slot])
        if a >= 0:
            decisions.append((k, local[a][0]))
        slot = int(parents[k, slot])
        if slot < 0:
            break
    decisions.reverse()
    return best_u, decisions


# ---------------------------------------------------------------------------
# Reference-faithful float64 twins.  The paper's max_accuracy / max_utility
# policies accumulate their DPs in float64 (numpy arrays / Python floats),
# so the network-aware batched planners (core/sim_batch) cannot reuse the
# f32 kernels above without drifting on ties.  These twins pin f64 — they
# must be traced inside ``jax.enable_x64`` — and keep every
# sequential tie-break of the reference updates (first model wins ties,
# case A beats case B within a model, stable (t, -u) candidate order).
# ---------------------------------------------------------------------------


def _no_fma(product: jax.Array, gate: jax.Array) -> jax.Array:
    """Force ``product`` to round to float64 before it reaches an add.

    XLA CPU's LLVM backend contracts ``mul`` + ``add`` into ``fma`` inside
    fused loops, keeping the product at extended precision — one ulp off
    the Python reference, which is enough to flip a DP tie-break and pick a
    genuinely different schedule.  Neither XLA flags, nor
    ``lax.optimization_barrier``, nor paired bitcasts survive to codegen;
    a select on a *traced* (never constant-foldable, always-true at
    runtime) predicate does: LLVM will not contract across the select
    instruction, so the product is rounded exactly as the reference's
    intermediate assignment rounds it.  Apply to every f64 multiply whose
    result feeds an add on a reference-bit-exact path.
    """
    return jnp.where(gate, product, 0.0)


@functools.partial(jax.jit, static_argnames=("n_frames", "nbins"))
def _accuracy_dp64(
    dur: jax.Array,  # [J] duration bins (int32; ceil(t_npu/grid), clamped to nbins)
    acc: jax.Array,  # [J] f64 raw acc_npu table values (the DP objective)
    arr_bins: jax.Array,  # [n_frames] int32
    dl_bins: jax.Array,  # [n_frames] int32
    start_bin: jax.Array,  # [] int32
    *,
    n_frames: int,
    nbins: int,
):
    """f64 twin of ``max_accuracy.local_dp`` with per-step *prefix records*.

    One scan serves every window length ``nn <= n_frames``: frame ``k``'s
    recurrence touches only frame-local bins (its own ``arr_bin``/``dl_bin``),
    so the DP over frames ``0..nn-1`` is a strict prefix of the DP over
    ``0..n_frames-1``.  The per-step records ``(maxH, argmax bin, alive)``
    therefore equal what ``local_dp(n_frames=nn)`` returns for every ``nn``
    — the Max-Accuracy round program reads the record at ``nn = n_l(B)``
    for each offload resolution and at the largest alive ``nn`` for the
    pure-local candidate, all from a single kernel call.  Deadness
    propagates (a dead ``H`` can never revive), so ``alive`` is
    prefix-monotone, exactly like the reference's per-frame early-out.
    """
    J = dur.shape[0]
    bins = jnp.arange(nbins, dtype=jnp.int32)
    H0 = jnp.full((nbins,), NEG, dtype=jnp.float64)
    H0 = H0.at[jnp.clip(start_bin, 0, nbins - 1)].set(0.0)

    def step(H, k):
        arr_bin = arr_bins[k]
        dl_bin = dl_bins[k]
        masked = jnp.where(bins <= arr_bin, H, NEG)
        pre_val = jnp.max(masked)
        pre_arg = jnp.argmax(masked).astype(jnp.int32)

        def per_model(j):
            d = dur[j]
            a = acc[j]
            fbA = arr_bin + d
            okA = (fbA <= dl_bin) & (fbA < nbins) & (pre_val > NEG / 2)
            valA = jnp.where((bins == fbA) & okA, pre_val + a, NEG)
            parA = jnp.where((bins == fbA) & okA, pre_arg, -1)
            src = bins - d
            okB = (src > arr_bin) & (src >= 0) & (bins <= dl_bin)
            gathered = jnp.where(okB, H[jnp.clip(src, 0, nbins - 1)], NEG)
            valB = jnp.where(gathered > NEG / 2, gathered + a, NEG)
            parB = jnp.where(valB > NEG / 2, jnp.clip(src, 0, nbins - 1), -1)
            val = jnp.where(valA >= valB, valA, valB)
            par = jnp.where(valA >= valB, parA, parB)
            return val, par

        vals, pars = jax.vmap(per_model)(jnp.arange(J, dtype=jnp.int32))  # [J, nbins]
        best_j = jnp.argmax(vals, axis=0)
        Hn = jnp.take_along_axis(vals, best_j[None], axis=0)[0]
        parent = jnp.take_along_axis(pars, best_j[None], axis=0)[0]
        choice = jnp.where(Hn > NEG / 2, best_j.astype(jnp.int32), -1)
        parent = jnp.where(Hn > NEG / 2, parent, -1)
        maxH = jnp.max(Hn)
        argb = jnp.argmax(Hn).astype(jnp.int32)
        return Hn, (choice, parent, maxH, argb, maxH > NEG / 2)

    _, (choices, parents, maxH, argb, alive) = jax.lax.scan(
        step, H0, jnp.arange(n_frames, dtype=jnp.int32)
    )
    return choices, parents, maxH, argb, alive


@functools.partial(jax.jit, static_argnames=("n_frames", "width"))
def _utility_dp64(
    t_npu: jax.Array,  # [J] f64 (inf for server-only models)
    acc: jax.Array,  # [J] f64 raw acc_npu table values
    n_active: jax.Array,  # [] int32; frames >= this are pass-through no-ops
    *,
    n_frames: int,
    width: int,
    gamma: jax.Array,
    deadline: jax.Array,
    alpha: jax.Array,
    npu_free: jax.Array,
    first_arrival: jax.Array,
    window: jax.Array,
):
    """f64 twin of ``max_utility.local_utility_dp`` (Pareto triples).

    Candidate enumeration order (carried triples first, then processed
    candidates slot-major — exactly the reference's ``for tri in U: for j``
    loops), the stable ``(t, -u)`` sort, the 1e-12 dominance epsilon, and
    the cap-overflow rule all mirror the Python reference.  On overflow the
    reference keeps the ``cap`` highest-utility front entries re-sorted by
    ``t`` — since ``u`` rises strictly along the front, that is exactly the
    LAST ``width`` keepers in t-order, rendered here as a rank offset in the
    compaction.

    ``width`` below ``max_utility._prune``'s cap (256) is a *fast path*:
    results are exact as long as no front ever outgrows it, and the
    returned ``overflow`` flag reports whether one did (gated to live
    frames).  Callers must rerun overflowing instances at ``width = 256``,
    where the truncation rule coincides with the reference cap — the sort
    is the kernel's dominant cost and scales ~``width log width``, so the
    narrow first pass is worth the occasional rerun.
    """
    J = t_npu.shape[0]
    BIG_T = jnp.float64(1e9)
    n_active = jnp.asarray(n_active, jnp.int32)
    rounded = n_active >= 0  # traced, always true: _no_fma's opaque gate
    t0 = jnp.full((width,), BIG_T, jnp.float64).at[0].set(jnp.maximum(npu_free, 0.0))
    u0 = jnp.full((width,), NEG, jnp.float64).at[0].set(0.0)
    m0 = jnp.zeros((width,), jnp.int32)
    valid0 = jnp.zeros((width,), bool).at[0].set(True)
    slots = jnp.arange(width, dtype=jnp.int32)
    M = width * (J + 1)

    def step(state, k):
        t, u, m, valid = state
        arrival = first_arrival + _no_fma(k.astype(jnp.float64) * gamma, rounded)

        def proc(j):
            t2 = jnp.maximum(t, arrival) + t_npu[j]
            ok = valid & (t2 <= arrival + deadline + 1e-12)
            mf = m.astype(jnp.float64)
            mean_term = _no_fma(
                (mf / (mf + 1.0)) * (u - mf / window), rounded
            ) + alpha * acc[j] / (mf + 1.0)
            u2 = mean_term + (mf + 1.0) / window
            return (
                jnp.where(ok, t2, BIG_T),
                jnp.where(ok, u2, NEG),
                jnp.where(ok, m + 1, 0),
                ok,
            )

        pt, pu, pm, pok = jax.vmap(proc)(jnp.arange(J, dtype=jnp.int32))  # [J, width]
        # Slot-major processed candidates (transpose before flatten): the
        # stable sort's tie order must equal the reference's cands list.
        ct = jnp.concatenate([t, pt.T.reshape(-1)])
        cu = jnp.concatenate([u, pu.T.reshape(-1)])
        cm = jnp.concatenate([m, pm.T.reshape(-1)])
        cok = jnp.concatenate([valid, pok.T.reshape(-1)])
        cparent = jnp.concatenate([slots, jnp.repeat(slots, J)])
        caction = jnp.concatenate(
            [jnp.full((width,), -1, jnp.int32), jnp.tile(jnp.arange(J, dtype=jnp.int32), width)]
        )
        cu = jnp.where(cok, cu, NEG)
        ct = jnp.where(cok, ct, BIG_T)
        # Stable sort by (t asc, u desc): invalid candidates carry
        # (BIG_T, NEG) keys and sort strictly after every valid entry.
        idx = jnp.arange(M, dtype=jnp.int32)
        perm = jax.lax.sort((ct, -cu, idx), num_keys=2, is_stable=True)[2]
        ct, cu, cm = ct[perm], cu[perm], cm[perm]
        cparent, caction = cparent[perm], caction[perm]
        # The reference's dominance bar is the last KEPT utility, not the
        # running max of all candidates: a candidate rejected inside the
        # 1e-12 epsilon must not raise the bar for its successors (a plain
        # cummax would, dropping front entries the reference keeps when
        # utilities collide within the epsilon).  The fold is inherently
        # sequential; chunking it (16 unrolled folds per scan step) keeps
        # the scan shallow without changing the semantics.
        CH = 16
        pad = (-cu.shape[0]) % CH
        cu_p = jnp.concatenate([cu, jnp.full((pad,), NEG, cu.dtype)])

        def keep_chunk(bar, u_chunk):
            keeps = []
            for i in range(CH):
                k = u_chunk[i] > bar + 1e-12
                bar = jnp.where(k, u_chunk[i], bar)
                keeps.append(k)
            return bar, jnp.stack(keeps)

        _, keep = jax.lax.scan(
            keep_chunk, jnp.float64(NEG), cu_p.reshape(-1, CH)
        )
        keep = keep.reshape(-1)[: cu.shape[0]]
        csum = jnp.cumsum(keep.astype(jnp.int32))
        count = csum[-1]
        drop = jnp.maximum(count - width, 0)  # cap overflow: shed lowest-u keepers
        pos = jnp.clip(jnp.searchsorted(csum, drop + 1 + slots), 0, M - 1)
        filled = slots < (count - drop)
        nt = jnp.where(filled, ct[pos], BIG_T)
        nu = jnp.where(filled, cu[pos], NEG)
        nm = jnp.where(filled, cm[pos], 0)
        nparent = jnp.where(filled, cparent[pos], -1)
        naction = jnp.where(filled, caction[pos], -1)
        # Padded frame (k >= n_active): identity pass-through, no decision.
        on = k < n_active
        step_overflow = on & (count > width)
        nt = jnp.where(on, nt, t)
        nu = jnp.where(on, nu, u)
        nm = jnp.where(on, nm, m)
        nok = jnp.where(on, filled, valid)
        nparent = jnp.where(on, nparent, slots)
        naction = jnp.where(on, naction, -1)
        return (nt, nu, nm, nok), (nparent, naction, step_overflow)

    state, (parents, actions, overflows) = jax.lax.scan(
        step, (t0, u0, m0, valid0), jnp.arange(n_frames, dtype=jnp.int32)
    )
    return state, parents, actions, jnp.any(overflows)


# ---------------------------------------------------------------------------
# The jitted DPs as registered policies: local-only rounds planned on device.
# ---------------------------------------------------------------------------


@register_policy(
    "jax_accuracy",
    params=(
        Param.integer("window_frames", None, nullable=True, doc="DP window; default floor(T/gamma)"),
        Param.number("grid", 1e-3, doc="DP time grid (s)"),
    ),
    doc="Jitted Max-Accuracy local DP (every window frame on the NPU).",
    batched=True,
    # Fleet grids run the dedicated single-lane planner in
    # core/sim_multi_batch: local-only plans never take an uplink lease,
    # so one lane per scenario carries the whole homogeneous fleet while
    # the allocation gates are counted exactly for the meta report.
    batched_multi=True,
)
def plan_round_accuracy(
    models: Sequence[ModelProfile],
    stream: StreamSpec,
    net: NetworkState,
    *,
    npu_free: float = 0.0,
    window_frames: int | None = None,
    grid: float = 1e-3,
) -> RoundPlan:
    """Local-only round via :func:`local_accuracy_dp_jax` — the on-device
    counterpart of the ``local`` baseline's accuracy mode (all frames
    processed; a best-effort skip of the whole window when infeasible)."""
    gamma, T = stream.gamma, stream.deadline
    n = window_frames if window_frames is not None else max(int(np.floor(T / gamma)), 1)
    total, picks = local_accuracy_dp_jax(
        models, n_frames=n, gamma=gamma, deadline=T,
        npu_free=npu_free, first_arrival=0.0, grid=grid,
    )
    if total <= NEG / 2:
        return RoundPlan(decisions=[Decision(0, Where.SKIP)], horizon=1, npu_busy_until=npu_free)
    decisions = []
    free = max(npu_free, 0.0)
    acc_sum = 0.0
    for k, j in enumerate(picks):
        start = max(free, k * gamma)
        free = start + models[j].t_npu
        decisions.append(Decision(k, Where.NPU, j, stream.r_max, start=start, finish=free))
        acc_sum += models[j].accuracy(stream.r_max, where="npu")
    return RoundPlan(
        decisions=decisions, horizon=n, expected_accuracy_sum=acc_sum, npu_busy_until=free
    )


@register_policy(
    "jax_utility",
    params=(
        Param.number("alpha", doc="paper Eq. (9) accuracy weight (required)"),
        Param.integer("window_frames", None, nullable=True, doc="DP window; default floor(T/gamma)"),
        Param.integer("width", 64, doc="Pareto-front width of the jitted DP"),
    ),
    doc="Jitted Max-Utility local DP (dominance-pruned front, skips allowed).",
    batched=True,
    # Fleet grids run the dedicated single-lane planner in
    # core/sim_multi_batch: local-only plans never take an uplink lease,
    # so one lane per scenario carries the whole homogeneous fleet while
    # the allocation gates are counted exactly for the meta report.
    batched_multi=True,
)
def plan_round_utility(
    models: Sequence[ModelProfile],
    stream: StreamSpec,
    net: NetworkState,
    *,
    alpha: float,
    npu_free: float = 0.0,
    window_frames: int | None = None,
    width: int = 64,
) -> RoundPlan:
    """Local-only round via :func:`local_utility_dp_jax` — the on-device
    counterpart of the ``local`` baseline's utility mode."""
    gamma, T = stream.gamma, stream.deadline
    n = window_frames if window_frames is not None else max(int(np.floor(T / gamma)), 1)
    utility, picks = local_utility_dp_jax(
        models, n_frames=n, gamma=gamma, deadline=T, alpha=alpha,
        npu_free=npu_free, first_arrival=0.0, window=n * gamma, width=width,
    )
    chosen = dict(picks)
    decisions = []
    free = max(npu_free, 0.0)
    for k in range(n):
        j = chosen.get(k)
        if j is None:
            decisions.append(Decision(k, Where.SKIP))
            continue
        start = max(free, k * gamma)
        free = start + models[j].t_npu
        decisions.append(Decision(k, Where.NPU, j, stream.r_max, start=start, finish=free))
    return RoundPlan(
        decisions=decisions, horizon=n, expected_utility=utility, npu_busy_until=free
    )
