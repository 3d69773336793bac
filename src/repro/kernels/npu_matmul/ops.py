"""Jit'd public wrapper for the NPU int8 matmul.

``npu_matmul(x, w)`` quantizes on the fly (per-row activations, per-channel
weights) and runs the Pallas kernel; ``npu_matmul_prequant`` takes already
quantized weights (the serving path: weights are quantized once at load).

``interpret=None`` follows :func:`repro.kernels.platform.interpret_mode`:
Mosaic on a TPU, the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..platform import interpret_mode
from . import kernel, ref


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pow2ceil(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


def npu_matmul(
    x: jax.Array, w: jax.Array, *, out_dtype=jnp.float32, interpret: bool | None = None
) -> jax.Array:
    """[..., K] x [K, N] -> [..., N] through int8 quantization (both sides)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xq, xs = ref.quantize_rowwise(x2)
    wq, ws = ref.quantize_colwise(w)
    out = npu_matmul_prequant(xq, xs, wq, ws, out_dtype=out_dtype, interpret=interpret)
    return out.reshape(*lead, w.shape[-1])


def npu_matmul_prequant(
    x_q: jax.Array,
    x_scale: jax.Array,
    w_q: jax.Array,
    w_scale: jax.Array,
    *,
    out_dtype=jnp.float32,
    interpret: bool | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
) -> jax.Array:
    interpret = interpret_mode(interpret)
    M, K = x_q.shape
    N = w_q.shape[1]
    # Adaptive block sizes: small matmuls (the serving single-frame case —
    # M=1 head GEMMs, narrow im2col convs) shrink each block to the next
    # power of two instead of padding every dim to the full 128/512/128
    # tile.  The Mosaic (TPU) path keeps the int8 tiling minima — 32
    # sublanes on the second-minor dim, 128 lanes on the minor dim.
    bm = min(block_m, _pow2ceil(M))
    bn = min(block_n, _pow2ceil(N))
    bk = min(block_k, _pow2ceil(K))
    if not interpret:
        bm, bn, bk = max(bm, 32), max(bn, 128), max(bk, 128)
    # Pad every dim to its block multiple; slice back after.
    xq = _pad_to(_pad_to(x_q, bm, 0), bk, 1)
    wq = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    xs = _pad_to(x_scale, bm, 0)
    ws = _pad_to(w_scale, bn, 0)
    out = kernel.int8_matmul(
        xq, wq, xs, ws,
        block_m=bm, block_n=bn, block_k=bk,
        out_dtype=out_dtype, interpret=interpret,
    )
    return out[:M, :N]
