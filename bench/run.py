#!/usr/bin/env python3
"""Runs one benchmark cell once on the TPU this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name in ``BENCHMARK.json`` and the files under ``bench/``.  The run builds
the configuration's weights from the seed, warms every shape the traffic
reaches, drives ``VideoServer.run`` for the window, and then compares a
seeded sample of the window's answers with a float32 reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each compared number with its limit.
The same numbers end standard error.

It exits nonzero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(BENCH_DIR))

# the compile cache lives at a fixed path inside this checkout, whatever
# the environment says, so that two checkouts never share one
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def log(*parts) -> None:
    print(*parts, flush=True)


def finite(x):
    return x if x is None or math.isfinite(x) else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec

    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    cfg, ref = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    kind = "per_layer" if args.trace else "end_to_end"
    wanted = spec.metrics_for(bench, cell["name"], kind)

    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: needs {cell['chips']} TPU chip(s); JAX found {len(devices)} "
              f"{d.platform} device(s)", file=sys.stderr)
        return 2
    log(f"device: platform={d.platform} kind={d.device_kind} count={len(devices)}")
    peaks = spec.peaks(d.device_kind)

    from repro.core.compile_cache import enable_compile_cache

    from harness import check, serve

    log(f"compile cache: {enable_compile_cache()}")
    run, numbers, ok = serve.run_cell(cfg, ref, traffic, seed=args.seed, seconds=args.seconds,
                                      trace=bool(args.trace), t_process=T_PROCESS, log=log)
    run.peaks = peaks
    log(f"compiles in window: {run.compiles}")
    log(f"frames: scheduled {run.scheduled}, answered {run.answered}, npu {run.npu_frames}, "
        f"edge {run.edge_frames} in {run.edge_flushes} flushes; set-up {run.setup_seconds} s")

    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": finite(float(value)), "unit": m["unit"]}
    device = {"platform": d.platform, "kind": d.device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": ok, "attempted": run.scheduled, "failed": numbers["answered_once_misses"][0],
           "metrics": metrics, "device": device}
    tr = run.device_trace
    if tr is not None:
        device["busy_s"], device["window_s"] = tr.busy_s, tr.window_s
        out["breakdown"] = tr.breakdown()
        log(f"trace: clock offset {tr.clock_offset_s} s, busy {tr.busy_s} s of {tr.window_s} s, "
            f"{run.traced_npu_frames} NPU frames")
    out["checks"] = {k: {"value": finite(float(v["value"])), "limit": v["limit"]}
                     for k, v in check.report(numbers).items()}
    for k, v in numbers.items():
        print(f"check {k}: {v[0]} (limit {v[1]})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
