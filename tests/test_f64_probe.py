"""tools/f64_probe.py: on an IEEE float64 device (the CPU) it finds nothing."""
from __future__ import annotations

import importlib.util
from pathlib import Path

_PATH = Path(__file__).resolve().parents[1] / "tools" / "f64_probe.py"


def _load():
    spec = importlib.util.spec_from_file_location("f64_probe", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_probe_finds_no_difference_on_the_cpu():
    out = _load().probe(n=2000)
    assert out["device"] == "cpu"
    shares = {k: v[0] for k, v in out.items() if isinstance(v, list) and k != "range"}
    assert len(shares) == 4 * 2 + 2 + 3  # ops x samples, ceil_div x samples, gammas
    assert all(v == 0.0 for v in shares.values()), shares
    assert out["roundtrip"] == 0.0
    assert out["range"] == [1e-40, 1e-300, 1e39, 1e300]
