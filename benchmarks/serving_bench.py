"""SNVA-style end-to-end serving benchmark (emits ``BENCH_serving.json``).

Headline: sustained frames/sec through the real serving stack —
``serving.calibrate`` trains + measures both deployment variants (the NPU
variant's matmuls execute in ``kernels/npu_matmul``'s int8 Pallas kernel),
then ``VideoServer`` + ``EdgeBatchServer`` drive the FastVA controller over a
synthetic video with the *measured* profiles.  One calibration is shared
across every policy run, so the bench isolates scheduling differences.

Also asserted here (exit nonzero on failure): the bandwidth estimator,
started with a deliberately wrong prior, converges to the true trace
bandwidth during ``VideoServer.run`` — the regression gate for the
estimator-echo bug (the serving loop used to feed the estimator its own
prediction, so a wrong prior persisted forever).

    PYTHONPATH=src python benchmarks/serving_bench.py --smoke --out BENCH_serving.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

DEFAULT_OUT = "BENCH_serving.json"
ARTIFACT = Path(__file__).resolve().parent.parent / DEFAULT_OUT

SMOKE_FRAMES = 48
FULL_FRAMES = 300
POLICIES = ("max_accuracy", "offload", "local")
TRUE_MBPS = 8.0
# Estimator convergence gate: start the belief 10x HIGH on a constant-rate
# trace; after the run the EWMA must sit within this relative band of
# true_bps * pessimism (what .state() reports).  The optimistic direction is
# the one the policy can recover from: an over-pessimistic prior makes the
# Offload baseline skip every frame (nothing to measure — the paper's
# sub-1.5 Mbps collapse), while an optimistic prior keeps frames flowing so
# every transfer is a measured sample.  Before the estimator-echo fix this
# gate fails: the loop fed the estimator its own prediction, so a wrong
# prior persisted forever.
WRONG_PRIOR_FACTOR = 10.0
CONVERGENCE_RTOL = 0.25


def _build_stack(cal, *, policy, stream, trace, init_bps):
    from repro.core import BandwidthEstimator, OnlineController, PolicySpec
    from repro.serving import BatchedEndpoint, EdgeBatchServer, VideoServer
    from repro.session import _model_from_json

    models = [_model_from_json(cm.payload) for cm in cal.models]
    batched = {
        j: BatchedEndpoint(
            f"{cm.payload['name']}-edge-batch",
            lambda x, p=cm.params, f=cm.forward: f(p, x),
            max_batch=16,
        )
        for j, cm in enumerate(cal.models)
    }
    controller = OnlineController(
        models=models,
        stream=stream,
        policy=PolicySpec.coerce(policy),
        estimator=BandwidthEstimator(init_bps=init_bps),
    )
    controller.estimator.observe_rtt(trace.at(0.0).rtt)
    server = VideoServer(
        controller=controller,
        npu_endpoints={j: cm.npu_endpoint for j, cm in enumerate(cal.models)},
        stream=stream,
        trace=trace,
        edge_server=EdgeBatchServer(batched),
    )
    return server, controller, batched


def run_bench(*, smoke: bool = False, seed: int = 0) -> dict:
    import numpy as np

    from repro.core import StreamSpec
    from repro.serving import CalibrationConfig, calibrate, make_synthetic_video
    from repro.session import TraceSpec

    n_frames = SMOKE_FRAMES if smoke else FULL_FRAMES
    cfg = CalibrationConfig.smoke(seed=seed) if smoke else CalibrationConfig(seed=seed)

    t0 = time.perf_counter()
    cal = calibrate(cfg)
    calibration_s = time.perf_counter() - t0

    stream = StreamSpec()
    trace = TraceSpec(mbps=TRUE_MBPS).build()
    true_bps = trace.at(0.0).bandwidth_bps
    frames, labels = make_synthetic_video(n_frames, n_classes=cfg.n_classes, res=cfg.res, seed=seed)

    runs = []
    for policy in POLICIES:
        server, controller, batched = _build_stack(
            cal, policy=policy, stream=stream, trace=trace, init_bps=true_bps
        )
        for ep in batched.values():
            ep.warmup(frames[0])
        summary = server.run(frames, labels)
        runs.append(
            {
                "policy": policy,
                "frames": summary["frames"],
                "fps_sustained": summary["fps_sustained"],
                "wall_s": summary["wall_s"],
                "accuracy": summary["accuracy"],
                "deadline_met_frac": summary["deadline_met_frac"],
                "npu_frames": summary["npu_frames"],
                "edge_frames": summary["edge_frames"],
                "mean_latency_s": summary["mean_latency_s"],
                "batch": summary.get("batch"),
                "scheduler_rounds": controller.rounds,
            }
        )

    # Estimator convergence regression (the echo-bug gate): "offload" sends
    # every frame, so the estimator sees one measured transfer per frame.
    server, controller, batched = _build_stack(
        cal,
        policy="offload",
        stream=stream,
        trace=trace,
        init_bps=true_bps * WRONG_PRIOR_FACTOR,
    )
    for ep in batched.values():
        ep.warmup(frames[0])
    server.run(frames, labels)
    est = controller.estimator
    target = true_bps * est.pessimism
    rel_err = abs(est.state().bandwidth_bps - target) / target
    converged = bool(rel_err <= CONVERGENCE_RTOL) and est.samples >= 8
    convergence = {
        "init_bps": true_bps * WRONG_PRIOR_FACTOR,
        "true_bps": true_bps,
        "pessimism": est.pessimism,
        "final_estimate_bps": est.state().bandwidth_bps,
        "upload_samples": est.samples,
        "rel_err": rel_err,
        "rtol": CONVERGENCE_RTOL,
        "converged": converged,
    }

    headline = next(r for r in runs if r["policy"] == "max_accuracy")
    return {
        "bench": "serving",
        "smoke": smoke,
        "n_frames": n_frames,
        "true_mbps": TRUE_MBPS,
        "calibration_s": calibration_s,
        "calibration": cal.artifact,
        "runs": runs,
        "fps_sustained": headline["fps_sustained"],  # headline: max_accuracy
        "convergence": convergence,
        "ok": converged and all(np.isfinite(r["fps_sustained"]) for r in runs),
    }


# ---------------------------------------------------------------------------
# run.py auto-discovery: summarize the artifact (cheap; the measured run is
# the --smoke/full entry point below).
# ---------------------------------------------------------------------------

def serving_summary():
    if not ARTIFACT.exists():
        return [("serving/NO_ARTIFACT_run_serving_bench_first", 0.0, 0.0)]
    rec = json.loads(ARTIFACT.read_text())
    rows = []
    for r in rec.get("runs", []):
        base = f"serving/{r['policy']}"
        us = (r["wall_s"] / max(r["frames"], 1)) * 1e6
        rows.append((f"{base}/fps_sustained", us, r["fps_sustained"]))
        rows.append((f"{base}/accuracy", 0.0, r["accuracy"]))
        rows.append((f"{base}/deadline_met", 0.0, r["deadline_met_frac"]))
    conv = rec.get("convergence", {})
    if conv:
        rows.append(("serving/estimator_converged", 0.0, float(conv.get("converged", False))))
    for m in rec.get("calibration", {}).get("models", []):
        rows.append((f"serving/calibrated/{m['name']}/t_npu_ms", m["t_npu_ms"] * 1e3,
                     m["provenance"]["fp32_int8_agreement"]))
    return rows


ALL = [serving_summary]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized calibration budgets + short stream")
    ap.add_argument("--out", default=DEFAULT_OUT, help=f"output path (default {DEFAULT_OUT})")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    result = run_bench(smoke=args.smoke, seed=args.seed)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2)
        fh.write("\n")

    print(f"{'policy':>14} {'frames':>7} {'fps':>9} {'acc':>6} {'met':>6} "
          f"{'npu':>5} {'edge':>5} {'mean batch':>10}")
    for r in result["runs"]:
        b = r["batch"] or {}
        print(f"{r['policy']:>14} {r['frames']:>7} {r['fps_sustained']:>9.1f} "
              f"{r['accuracy']:>6.3f} {r['deadline_met_frac']:>6.2f} "
              f"{r['npu_frames']:>5} {r['edge_frames']:>5} {b.get('mean_batch', 0.0):>10.2f}")
    c = result["convergence"]
    print(f"\nestimator: init {c['init_bps']/1e6:.2f} Mbps -> "
          f"{c['final_estimate_bps']/1e6:.2f} Mbps (target {c['true_bps']*c['pessimism']/1e6:.2f}, "
          f"rel_err {c['rel_err']:.3f}, {c['upload_samples']} samples) "
          f"converged={c['converged']}")
    print(f"calibration took {result['calibration_s']:.1f}s; wrote {args.out}")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
