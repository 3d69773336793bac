#!/usr/bin/env python3
"""Readings from which a cell's limits are set: the program's compared
numbers and the lower-precision control's, seed by seed, in one process.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 [--out FILE]

Each seed builds its own weights and frames, runs a short window at the
cell's own load, and reads on the same sampled answers both what the
benchmark compares (``program``) and the same numbers with the control in
the program's place (``control``): the reference at the precision the
configuration's ``controls`` name for each path (int4 for the int8 NPU
path, float8_e4m3fn for the bf16 edge path).  A limit is sound when every
program reading is under it and every control fails it.
The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR.parent / "src"))
sys.path.insert(0, str(BENCH_DIR))
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(BENCH_DIR.parent / ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from harness import serve, spec
    from repro.core.compile_cache import enable_compile_cache

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"control: needs a TPU; JAX found {d.platform}", file=sys.stderr)
        return 2
    enable_compile_cache()
    bench = spec.benchmark()
    cell = spec.workload(bench, args.workload)
    cfg, ref = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        run, numbers, ok = serve.run_cell(cfg, ref, traffic, seed=seed, seconds=args.seconds,
                                          trace=False, t_process=time.perf_counter(),
                                          controls=True)
        program, control = run.readings
        program["answered_once_misses"] = numbers["answered_once_misses"][0]
        row = {"seed": seed, "correct": ok, "program": program, "control": control,
               "control_fails": any(v > cfg["limits"][k] for k, v in control.items()
                                    if k in cfg["limits"])}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"workload": args.workload, "device": d.device_kind,
                                              "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
