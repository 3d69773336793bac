"""Finds a cell's files by the names in ``BENCHMARK.json``.

    configs/<config>.json   sizes, program arch, planner profile, limits
    configs/<config>.py     the plain reference beside them
    traffic/<traffic>.json  the traffic mix's parameters
    metrics/<metric>.py     one reader per metric: ``read(run) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, prefix: str):
    name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(name: str) -> tuple[dict, object]:
    return (load_json(BENCH_DIR / "configs" / f"{name}.json"),
            load_module(BENCH_DIR / "configs" / f"{name}.py", "bench_config_"))


def traffic(name: str) -> dict:
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "harness" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the table has {sorted(table)}")
    return table[device_kind]


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that this cell reports."""
    return [m for m in bench[kind] if cell in m.get("workloads", [cell])]


def reader(metric: str):
    return load_module(BENCH_DIR / "metrics" / f"{metric}.py", "bench_metric_")
