"""Jit'd public wrapper for the NPU int8 matmul.

``npu_matmul(x, w)`` quantizes on the fly (per-row activations, per-channel
weights).  Where one grid step's full-K blocks fit the VMEM budget it is one
``kernel.quantized_matmul`` call that quantizes both operands in VMEM;
otherwise it quantizes in XLA and runs ``npu_matmul_prequant`` (two passes).
``npu_matmul_prequant`` takes operands already quantized.

``interpret=None`` follows :func:`repro.kernels.platform.interpret_mode`:
Mosaic on a TPU, the Pallas interpreter everywhere else.
"""
from __future__ import annotations

import collections

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


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# What one grid step of the fused kernel may hold in VMEM, by
# _fused_vmem_bytes; half of kernel.VMEM_LIMIT, for Mosaic's own temporaries.
VMEM_BUDGET = 24 << 20

# GEMM call sites lowered per path ("fused" or "two_pass"), counted when
# npu_matmul is traced: a jit traced once, or a scan body, counts once.
PATHS: collections.Counter = collections.Counter()


def _fused_vmem_bytes(bm: int, k: int, bn: int, itemsize: int) -> int:
    """VMEM of one grid step of the fused kernel, rows rounded up to the
    int8 tile's 32 and lanes to 128: each operand block double-buffered, its
    f32 widening and int8 copy; the output block double-buffered with its
    int32 accumulator and f32 epilogue."""
    rows, lanes_k, lanes_n = _round_up(bm, 32), _round_up(k, 128), _round_up(bn, 128)
    per_elem = 2 * itemsize + 4 + 1
    x = rows * lanes_k * per_elem
    w = _round_up(k, 32) * lanes_n * per_elem
    out = rows * lanes_n * 4 * 4
    return x + w + out


def fused_blocks(m: int, k: int, n: int, itemsize: int) -> tuple[int, int] | None:
    """``(bm, bn)`` for the fused kernel, or None where even the smallest
    full-K blocks exceed ``VMEM_BUDGET``.  A dim up to 1024 rows / 256
    columns is one whole block; a larger one splits into the largest
    power-of-two block that fits (a multiple of every dtype's tiling)."""
    bms = ([m] if m <= 1024 else []) + [b for b in (1024, 512, 256, 128, 64, 32) if b < m]
    bns = ([n] if n <= 256 else []) + [b for b in (256, 128) if b < n]
    for bn in bns:
        for bm in bms:
            if _fused_vmem_bytes(bm, k, bn, itemsize) <= VMEM_BUDGET:
                return bm, bn
    return None


def npu_matmul(
    x: jax.Array, w: jax.Array, *, out_dtype=jnp.float32, interpret: bool | None = None
) -> jax.Array:
    """[..., K] x [K, N] -> [..., N] through int8 quantization (both sides)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    (m, k), n = x2.shape, w.shape[1]
    blocks = fused_blocks(m, k, n, max(x2.dtype.itemsize, w.dtype.itemsize))
    if blocks is None:
        PATHS["two_pass"] += 1
        xq, xs = ref.quantize_rowwise(x2)
        wq, ws = ref.quantize_colwise(w)
        out = npu_matmul_prequant(xq, xs, wq, ws, out_dtype=out_dtype, interpret=interpret)
    else:
        PATHS["fused"] += 1
        out = kernel.quantized_matmul(
            x2, w, block_m=blocks[0], block_n=blocks[1], out_dtype=out_dtype,
            interpret=interpret_mode(interpret),
        )
    return out.reshape(*lead, n)


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
