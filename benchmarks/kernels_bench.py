"""Kernel micro-benchmarks.

The kernels run where :func:`repro.kernels.platform.interpret_mode` says:
Mosaic on a TPU, the Pallas interpreter elsewhere.  An interpreter wall time
is NOT a TPU performance signal, so every row names its mode, and
``derived`` reports the semantic quality metric (quantization relative
error / max deviation vs oracle).
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.kernels.platform import interpret_mode  # noqa: E402

# --smoke drops the larger shape per kernel (interpret mode is slow on CPU).
_SMOKE = False


def _mode() -> str:
    return "interpret" if interpret_mode() else "mosaic"


def kernel_npu_matmul():
    from repro.kernels.npu_matmul import ops, ref

    rows = []
    rng = np.random.default_rng(0)
    shapes = [(128, 512, 128)] if _SMOKE else [(128, 512, 128), (256, 2048, 256)]
    for m, k, n in shapes:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
        out = ops.npu_matmul(x, w).block_until_ready()
        t0 = time.perf_counter()
        out = ops.npu_matmul(x, w).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6
        exact = x @ w
        rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
        rows.append((f"kernel/npu_matmul_{m}x{k}x{n} ({_mode()})", us, rel))
    return rows


def kernel_flash_attention():
    from repro.kernels.flash_attention import kernel as fk
    from repro.kernels.flash_attention import ref as fr

    rows = []
    rng = np.random.default_rng(1)
    shapes = (
        [(1, 256, 8, 4, 64)] if _SMOKE else [(1, 256, 8, 4, 64), (1, 512, 8, 8, 128)]
    )
    for b, s, h, kh, hd in shapes:
        q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, kh, hd)), jnp.float32)
        out = fk.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                                 interpret=interpret_mode()).block_until_ready()
        t0 = time.perf_counter()
        out = fk.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128,
                                 interpret=interpret_mode()).block_until_ready()
        us = (time.perf_counter() - t0) * 1e6
        ref = fr.sdpa_ref(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out - ref)))
        rows.append((f"kernel/flash_attn_b{b}s{s}h{h} ({_mode()})", us, err))
    return rows


ALL = [kernel_npu_matmul, kernel_flash_attention]


def main(argv=None) -> int:
    global _SMOKE
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smallest shape per kernel (CI smoke)")
    args = ap.parse_args(argv)
    _SMOKE = args.smoke
    print("name,us_per_call,derived")
    for bench in ALL:
        for name, us, derived in bench():
            print(f"{name},{us:.2f},{derived:.6f}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
