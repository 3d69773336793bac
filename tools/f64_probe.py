#!/usr/bin/env python3
"""Probe how the default JAX device computes float64, against numpy.

    python tools/f64_probe.py   # prints one F64_PROBE JSON line

The batched planning engines (``core/sim_batch``, ``sim_multi_batch``,
``sim_online_batch``) run under x64 and take integer decisions (segment
lookups, ceil/floor bin bounds, deadline compares) from float64
arithmetic.  A TPU emulates float64; this script measures how far the
device's results are from IEEE double, so the list in ROADMAP Design 5 of
decisions at risk rests on a number.  On the CPU every share is 0.

Keys of the printed object:

* ``<op>/<sample>``: ``[share of results that differ from numpy, largest
  relative difference]`` for add, sub, mul, div.  numpy computes on the
  values the device holds (read back from it), so this isolates the
  arithmetic from the transfer.  ``random`` draws magnitudes over twelve
  decades; ``nice`` uses round values of the kind configs hold
  (milliseconds, fps, Mbps, byte counts).
* ``ceil_div/<sample>``: ``[share of ceil(x / y) that differ from numpy,
  share of the quotients that numpy finds exactly integral]`` — the second
  number is where a flip is possible at all.
* ``head*gamma/<gamma>``: ``[share differing, largest relative
  difference]`` for frame times ``head * gamma``, heads 0..1999.
* ``roundtrip``: share of random doubles that change on a host -> device
  -> host copy.
* ``range``: ``[1e-40, 1e-300, 1e39, 1e300]`` copied to the device and
  back: f32's exponent range flushes or overflows them.
"""
from __future__ import annotations

import json
import operator

import numpy as np

N = 200_000
HEADS = 2000
GAMMAS = (1 / 30, 1 / 24, 1 / 15)
OPS = {"add": operator.add, "sub": operator.sub, "mul": operator.mul, "div": operator.truediv}


def _samples(rng: np.random.Generator, n: int) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    def wide(size):
        return rng.uniform(1.0, 10.0, size) * 10.0 ** rng.integers(-6, 6, size)

    base = np.array([0.005, 0.0333, 0.05, 0.1, 0.15, 0.2, 0.25, 0.3, 0.35, 1.0, 2.5,
                     8.0, 15.0, 24.0, 30.0, 45.0, 134.0, 224.0, 1e6, 2.5e6, 75264.0])
    nice_x, nice_y = np.meshgrid(base, base)
    return {"random": (wide(n), wide(n)), "nice": (nice_x.ravel(), nice_y.ravel())}


def _diff(dev: np.ndarray, ref: np.ndarray) -> list[float]:
    differ = dev != ref
    rel = np.abs(dev - ref) / np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
    return [float(differ.mean()), float(rel[np.isfinite(rel)].max(initial=0.0))]


def probe(n: int = N, seed: int = 0) -> dict:
    import jax
    import jax.numpy as jnp

    out: dict = {"device": jax.devices()[0].device_kind}
    with jax.enable_x64(True):
        rng = np.random.default_rng(seed)
        for sample, (x, y) in _samples(rng, n).items():
            xd, yd = jnp.asarray(x), jnp.asarray(y)
            xh, yh = np.asarray(xd), np.asarray(yd)  # what the device holds
            for name, op in OPS.items():
                dev = np.asarray(jax.jit(op)(xd, yd))
                out[f"{name}/{sample}"] = _diff(dev, op(xh, yh))
            dev = np.asarray(jax.jit(lambda a, b: jnp.ceil(a / b))(xd, yd))
            ref = np.ceil(xh / yh)
            exact = np.floor(xh / yh) == xh / yh
            out[f"ceil_div/{sample}"] = [float((dev != ref).mean()), float(exact.mean())]
        heads = np.arange(HEADS, dtype=np.int64)
        for g in GAMMAS:
            dev = np.asarray(jax.jit(lambda h, g: h * g)(jnp.asarray(heads), jnp.float64(g)))
            out[f"head*gamma/{g:.5f}"] = _diff(dev, heads * np.float64(g))
        x = rng.standard_normal(n) * 10.0 ** rng.integers(-6, 6, n)
        out["roundtrip"] = float((np.asarray(jnp.asarray(x)) != x).mean())
        edge = np.array([1e-40, 1e-300, 1e39, 1e300])
        out["range"] = [float(v) for v in np.asarray(jax.jit(lambda a: a * 1.0)(jnp.asarray(edge)))]
    return out


if __name__ == "__main__":
    print("F64_PROBE " + json.dumps(probe()), flush=True)
