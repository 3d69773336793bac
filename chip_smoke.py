#!/usr/bin/env python3
"""Smoke run of FastVA's serving path on one TPU chip.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # a four-chip host: the sharded sweep only

Phases of the default run, all in this one process on ``jax.devices()[0]``:

  device       the platform must be a TPU; there is no CPU branch.
  serve        ResNet-50 and SqueezeNet at their published configs (seeded
               random weights, 224x224x3 frames, 1000 classes): the int8
               variant on ``kernels/npu_matmul`` (Mosaic) as the NPU
               endpoints, the unquantized variant (bf16 compute) as
               ``BatchedEndpoint``s behind an ``EdgeBatchServer``, driven
               by ``VideoServer`` with an ``OnlineController`` over the
               paper profiles.
  correctness  edge (bf16 compute) and NPU (int8) logits of one batch-16
               bucket against a float32 forward of the same parameters
               (``models/reference.py``, every layer at Precision.HIGHEST).
  mosaic       the compiled NPU forward holds ``tpu_custom_call`` and no
               ``npu_matmul`` on the path runs in the Pallas interpreter.
  planning     a network-aware ``max_accuracy`` sweep on the batched engine
               (x64 lane programs on the chip) against the reference loop.

``--four-chips`` runs only one sweep grid sharded over a four-device scenario
mesh and compares it, bit for bit, with the same grid on one device.

Any failure exits nonzero.  The last line of standard output is one JSON
object naming the device; it is printed only when every phase passed.
Printed wall times are smoke timings, not metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODELS = ("resnet-50", "squeezenet")
RES = 224
MAX_BATCH = 16
SERVE_POLICIES = ("max_accuracy", "offload", "local")
FRAMES_PER_POLICY = 36
SEED = 0

# Bounds on one batch-16 bucket's logits against the float32 reference
# (relative L2 error; top-1 agreement over the 16 frames).  The edge forward
# computes in bfloat16 (convnets casts frames and weights to bf16); the NPU
# forward adds int8 round-off on both sides of every GEMM.  On a TPU v5e,
# with these seeds, the errors read 3.9e-3 (ResNet-50) and 5.0e-3
# (SqueezeNet) for the edge, 1.9e-2 and 2.1e-2 for the NPU, and top-1
# agreement is 16 of 16 for both.  Each bound is about four times its
# reading.  fp8 activations (e4m3 keeps 4 significant bits to bf16's 8) or
# int4 weights round 16 times as coarsely and would exceed it, and so would
# a dropped or misordered layer.
EDGE_REL_L2_MAX = 2e-2
NPU_REL_L2_MAX = 8e-2
NPU_TOP1_MIN = 0.875

# Sweep comparison fields: ints exact, accuracy within AUDIT_TOL (the
# engines' contract); --four-chips compares every field bit for bit.
INT_FIELDS = ("frames_total", "frames_processed", "frames_missed_deadline",
              "frames_offloaded", "schedule_calls")
ALL_FIELDS = INT_FIELDS + ("accuracy_sum", "elapsed", "npu_busy_s")


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(count: int | None = None):
    import jax

    devices = jax.devices()
    d = devices[0]
    check(d.platform == "tpu", f"no TPU: JAX found platform {d.platform!r}")
    if count is not None:
        check(len(devices) == count, f"need {count} TPU devices, JAX found {len(devices)}")
    print(f"device: platform={d.platform} kind={d.device_kind} count={len(devices)}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------


def build_model(arch, seed: int):
    """Seeded random weights for ``arch``, its int8 NPU variant, and both
    deployment forwards (edge, bf16 compute; NPU, int8 on the kernel)."""
    import jax

    from repro import quant
    from repro.arch import abstract_params, classifier_forward
    from repro.models.common import init_tree

    specs, state_specs = abstract_params(arch)
    # one compiled init per tree: leaf by leaf, every shape is its own compile
    params = jax.jit(lambda k: init_tree(k, specs))(jax.random.key(seed))
    state = jax.jit(lambda k: init_tree(k, state_specs))(jax.random.key(seed + 1))

    def forward(p, x):
        return classifier_forward(arch, p, state, x, train=False)[0]

    qparams, _ = quant.npu_variant(params)
    # interpret=None: the platform rule picks Mosaic on the chip
    npu_forward = quant.npu_forward(forward, interpret=None)
    return {"arch": arch, "params": params, "state": state, "qparams": qparams,
            "forward": forward, "npu_forward": npu_forward}


def deploy(models, sample: np.ndarray, *, max_batch: int):
    """NPU ``ModelEndpoint``s and warmed edge ``BatchedEndpoint``s."""
    import jax.numpy as jnp

    from repro.core.profiles import PAPER_MODELS
    from repro.serving import BatchedEndpoint, ModelEndpoint

    npu, edge = {}, {}
    for j, m in enumerate(models):
        name = m["arch"].name
        npu[j] = ModelEndpoint(
            f"{name}-npu", lambda x, p=m["qparams"], f=m["npu_forward"]: f(p, x),
            profile_latency_s=PAPER_MODELS[j].t_npu,
        )
        npu[j].warmup(jnp.asarray(sample[None]))
        edge[j] = BatchedEndpoint(
            f"{name}-edge", lambda x, p=m["params"], f=m["forward"]: f(p, x),
            profile_latency_s=PAPER_MODELS[j].t_server, max_batch=max_batch,
        )
        edge[j].warmup(sample)
    return npu, edge


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def serve_phase(npu, edge, frames: np.ndarray, labels: np.ndarray) -> None:
    """Each policy over its own slice of the stream, through VideoServer."""
    from repro.core import BandwidthEstimator, OnlineController, PolicySpec, StreamSpec
    from repro.core.profiles import PAPER_MODELS
    from repro.core.simulator import Trace
    from repro.serving import BatchStats, EdgeBatchServer, VideoServer

    stream = StreamSpec(fps=30.0, deadline=0.200)
    trace = Trace.piecewise([(0.0, 8.0), (0.4, 2.0), (0.8, 8.0)], rtt_ms=50.0)
    n = len(frames) // len(SERVE_POLICIES)
    totals = {"npu_frames": 0, "edge_frames": 0}
    for i, policy in enumerate(SERVE_POLICIES):
        for ep in edge.values():
            ep.stats = BatchStats()
        net0 = trace.at(0.0)
        controller = OnlineController(
            models=PAPER_MODELS, stream=stream, policy=PolicySpec(policy),
            estimator=BandwidthEstimator(init_bps=net0.bandwidth_bps),
        )
        controller.estimator.observe_rtt(net0.rtt)
        server = VideoServer(controller=controller, npu_endpoints=npu, stream=stream,
                             trace=trace, edge_server=EdgeBatchServer(edge))
        sl = slice(i * n, (i + 1) * n)
        s = server.run(frames[sl], labels[sl])
        answered = sorted(r.frame for r in server.results)
        print(f"serve {policy}: frames={s['frames']} npu_frames={s['npu_frames']} "
              f"edge_frames={s['edge_frames']} "
              f"mean_batch={s.get('batch', {}).get('mean_batch', 0.0)} "
              f"deadline_met_frac={s['deadline_met_frac']} "
              f"smoke timing wall_s={s['wall_s']} (not a metric)", flush=True)
        check(answered == list(range(n)), f"serve {policy}: answered {len(answered)} of {n} frames")
        for k in totals:
            totals[k] += s[k]
    check(totals["npu_frames"] > 0, "serve: the NPU path served no frame")
    check(totals["edge_frames"] > 0, "serve: the batched edge path served no frame")


def rel_l2(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))


def correctness_phase(models, npu, edge, batch: np.ndarray) -> None:
    """Edge and NPU logits of one bucket against the float32 reference
    forward of the same parameters, computed on the same device."""
    import jax
    import jax.numpy as jnp

    from repro.models.reference import reference_logits

    for j, m in enumerate(models):
        name = m["arch"].name
        ref = jax.jit(lambda p, s, x, a=m["arch"]: reference_logits(a, p, s, x))(
            m["params"], m["state"], jnp.asarray(batch))
        check(ref.dtype == jnp.float32, f"{name}: reference logits are {ref.dtype}")
        ref = np.asarray(ref)
        check(bool(np.all(np.isfinite(ref))), f"{name}: reference logits not finite")
        e = np.asarray(edge[j](batch), np.float32)
        q = np.concatenate([npu[j](jnp.asarray(batch[i:i + 1])) for i in range(len(batch))])
        q = np.asarray(q, np.float32)
        check(e.shape == q.shape == ref.shape, f"{name}: logits shapes {e.shape} {q.shape} {ref.shape}")
        check(bool(np.all(np.isfinite(e)) and np.all(np.isfinite(q))), f"{name}: logits not finite")
        err_e, err_q = rel_l2(e, ref), rel_l2(q, ref)
        top1 = float(np.mean(np.argmax(q, -1) == np.argmax(ref, -1)))
        print(f"correctness {name}: logits {ref.shape} edge rel_l2={err_e} (max {EDGE_REL_L2_MAX}) "
              f"npu rel_l2={err_q} (max {NPU_REL_L2_MAX}) npu top1_agree={top1} "
              f"(min {NPU_TOP1_MIN})", flush=True)
        check(err_e <= EDGE_REL_L2_MAX, f"{name}: edge logits off the reference: {err_e}")
        check(err_q <= NPU_REL_L2_MAX, f"{name}: NPU logits off the reference: {err_q}")
        check(top1 >= NPU_TOP1_MIN, f"{name}: NPU top-1 agreement {top1}")


def mosaic_phase(models, npu, sample: np.ndarray) -> None:
    import jax.numpy as jnp

    from repro.kernels.platform import pallas_interpret_flags

    x = jnp.asarray(sample[None])
    for j, m in enumerate(models):
        name = m["arch"].name
        flags = pallas_interpret_flags(npu[j].forward, x)
        text = npu[j].forward.lower(x).compile().as_text()
        n_custom = text.count("tpu_custom_call")
        print(f"mosaic {name}: pallas_calls={len(flags)} interpreted={sum(flags)} "
              f"tpu_custom_call={n_custom}", flush=True)
        check(len(flags) > 0, f"{name}: the NPU forward traced no npu_matmul")
        check(not any(flags), f"{name}: an npu_matmul call resolved to interpret=True")
        check(n_custom > 0, f"{name}: no tpu_custom_call in the compiled NPU forward")


def planning_phase() -> None:
    """A small network-aware max_accuracy sweep: batched engine (x64 lane
    programs on the chip) against the reference loop, ints exact and
    accuracy within AUDIT_TOL."""
    from repro.core import PolicySpec
    from repro.core.audit import AUDIT_TOL
    from repro.session import ScenarioSpec, Session, SweepGrid, TraceSpec

    grid = SweepGrid(deadline_ms=(100.0, 150.0, 200.0, 350.0), fps=(15.0, 30.0))
    traces = {
        "constant": TraceSpec(mbps=2.5, rtt_ms=100.0),
        "piecewise": TraceSpec(kind="piecewise", points=((0.0, 3.0), (0.3, 0.8), (0.9, 6.0)),
                               rtt_ms=60.0),
    }
    n_points, max_acc_err, bad = 0, 0.0, []
    for kind, trace in traces.items():
        spec = ScenarioSpec(policy=PolicySpec("max_accuracy"), n_frames=60, trace=trace,
                            label=f"chip_smoke/{kind}")
        t0 = time.perf_counter()
        bat = Session(spec).run_sweep(grid, backend="batched")
        t_bat = time.perf_counter() - t0
        ref = Session(spec).run_sweep(grid, backend="reference")
        check(bat.backend == "batched", f"planning {kind}: ran on {bat.backend!r}, not batched")
        check(len(bat.points) == len(ref.points), f"planning {kind}: point counts differ")
        for pb, pr in zip(bat.points, ref.points):
            for sb, sr in zip(pb.streams, pr.streams):
                bad += [f"{kind} {pb.overrides}: {f} batched {getattr(sb, f)} != "
                        f"reference {getattr(sr, f)}"
                        for f in INT_FIELDS if getattr(sb, f) != getattr(sr, f)]
                err = abs(sb.accuracy_sum - sr.accuracy_sum)
                max_acc_err = max(max_acc_err, err)
                if err > AUDIT_TOL:
                    bad.append(f"{kind} {pb.overrides}: accuracy_sum off by {err}")
        n_points += len(bat.points)
        print(f"planning max_accuracy/{kind}: {len(bat.points)} points; smoke timing "
              f"batched_s={t_bat} (not a metric)", flush=True)
    for line in bad:
        print(f"planning mismatch: {line}", flush=True)
    check(not bad, f"planning: {len(bad)} mismatches between batched and reference")
    print(f"planning: {n_points} points batched == reference, ints exact, "
          f"max |accuracy_sum diff|={max_acc_err} (AUDIT_TOL {AUDIT_TOL})", flush=True)


def four_chip_phase() -> None:
    """One sweep grid through run_sharded on a four-device scenario mesh
    (5 lanes: padded to 8) against the same grid on one device."""
    from repro.core import PolicySpec
    from repro.core.sweep_shard import _sharded_jit, make_sweep_mesh
    from repro.session import ScenarioSpec, Session, SweepGrid, TraceSpec

    check(make_sweep_mesh().size == 4, f"sweep mesh has {make_sweep_mesh().size} devices")
    spec = ScenarioSpec(
        policy=PolicySpec("max_accuracy"), n_frames=60,
        trace=TraceSpec(kind="piecewise", points=((0.0, 3.0), (0.3, 0.8), (0.9, 6.0))),
        label="chip_smoke/four_chips",
    )
    grid = SweepGrid(rtt_ms=(20.0, 50.0, 80.0, 110.0, 140.0))
    t0 = time.perf_counter()
    sharded = Session(spec).run_sweep(grid, backend="batched")
    t_sharded = time.perf_counter() - t0
    check(_sharded_jit.cache_info().currsize > 0, "the sweep did not take the sharded path")
    os.environ["REPRO_SWEEP_SHARD"] = "0"
    plain = Session(spec).run_sweep(grid, backend="batched")
    check(sharded.backend == plain.backend == "batched", "sweep fell back off the batched engine")
    for pa, pb in zip(sharded.points, plain.points):
        for f in ALL_FIELDS:
            a, b = getattr(pa.stats, f), getattr(pb.stats, f)
            check(a == b, f"four chips {pa.overrides}: {f} sharded {a} != one device {b}")
    print(f"four chips: {len(sharded.points)} lanes sharded over 4 devices == one device, "
          f"bit for bit on {len(ALL_FIELDS)} fields; smoke timing sharded_s={t_sharded} "
          f"(not a metric)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep over four devices against one device")
    args = ap.parse_args(argv)
    if not args.four_chips:
        os.environ["REPRO_SWEEP_SHARD"] = "0"  # every phase on one device

    try:
        device = require_tpu(4 if args.four_chips else None)

        from repro.core.compile_cache import enable_compile_cache

        print(f"compile cache: {enable_compile_cache()}", flush=True)
        if args.four_chips:
            four_chip_phase()
        else:
            from repro import configs
            from repro.serving import make_synthetic_video

            t0 = time.perf_counter()
            models = [build_model(configs.get(name), SEED + 2 * j) for j, name in enumerate(MODELS)]
            frames, labels = make_synthetic_video(
                FRAMES_PER_POLICY * len(SERVE_POLICIES), res=RES, seed=SEED)
            t1 = time.perf_counter()
            npu, edge = deploy(models, frames[0], max_batch=MAX_BATCH)
            print(f"deploy: {', '.join(m['arch'].name for m in models)} at {RES}x{RES}, "
                  f"{models[0]['arch'].cfg.n_classes} classes; smoke timing "
                  f"build_s={t1 - t0} deploy_s={time.perf_counter() - t1} (not a metric)",
                  flush=True)
            serve_phase(npu, edge, frames, labels)
            correctness_phase(models, npu, edge, frames[:MAX_BATCH])
            mosaic_phase(models, npu, frames[0])
            planning_phase()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
