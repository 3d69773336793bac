"""Small copies of the benchmark's cells that the CPU can run in seconds:
the program's SMOKE architectures at 32x32, with the real traffic files."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from harness import spec  # noqa: E402

SMOKE = {
    "resnet50": {"depths": [1, 1], "width": 8},
    "squeezenet": {},
}


def config(name: str):
    cfg, ref = spec.config(name)
    cfg = dict(cfg, program_smoke=True, n_classes=10, input_res=32, **SMOKE[name])
    return cfg, ref


def traffic(name: str, **over):
    return dict(spec.traffic(name), **over)
