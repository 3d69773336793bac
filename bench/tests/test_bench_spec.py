"""BENCHMARK.json against the files it names, and the rule that the
harness names no particular cell, configuration or metric."""
import json
import re

import pytest
import tinycells  # noqa: F401

from harness import spec

BENCH = spec.benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(entry):
    cfg, ref = spec.config(entry["name"])
    assert entry["file"] == f"bench/configs/{entry['name']}.json"
    assert cfg["name"] == entry["name"]
    for fn in ("param_shapes", "make_weights", "reference_logits", "gemms"):
        assert callable(getattr(ref, fn))
    assert cfg["limits"] and set(cfg["limits"]) <= {"npu_rel_l2", "edge_rel_l2"}
    assert "npu_rel_l2" in cfg["limits"]
    assert set(cfg["controls"]) == {"npu", "edge"}
    assert any(w["config"] == entry["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    traffic = spec.traffic(cell["traffic"])
    assert traffic["mode"] in ("live", "replay")
    assert cell["chips"] in (1, 4)
    e2e = spec.metrics_for(BENCH, cell["name"], "end_to_end")
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    layer = spec.metrics_for(BENCH, cell["name"], "per_layer")
    assert layer
    for m in layer:
        assert m["moves"] in names, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric["name"]).read)
    for w in metric.get("workloads", []):
        assert w in CELLS
    if "bound" in metric:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")


def test_harness_names_no_cell_config_or_metric():
    names = set(CELLS) | {c["name"] for c in BENCH["configs"]} | {w["traffic"] for w in CELLS.values()}
    names |= {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for path in list((spec.BENCH_DIR / "harness").glob("*.py")) + [spec.BENCH_DIR / "run.py",
                                                                   spec.BENCH_DIR / "control.py"]:
        text = path.read_text()
        for name in names:
            assert not re.search(rf"\b{re.escape(name)}\b", text), (path.name, name)


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_file_is_json_and_small():
    text = (spec.ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) < 64 * 1024
    json.loads(text)
