"""Pallas TPU kernels: w8a8 int8 matmul with per-row/per-channel scales.

This is the "NPU path" of FastVA mapped to the TPU: the paper's phone NPU
runs CNNs in 8/16-bit — here the quantized variant of every model runs its
matmuls through these kernels.  TPU-native design (not a CUDA port).

``quantized_matmul`` takes float operands and quantizes both in VMEM:

  * grid (cdiv(M, bm), cdiv(N, bn)) with the whole K in every block, so the
    per-row and per-column abs-max see all of K and nothing is padded.  Rows
    and columns of a partial edge block hold stale data, but each row's and
    column's scale and output depend on that row or column alone, and the
    out-of-bounds part of the output block is never written back.
  * the activation block is quantized once per row block (at j == 0) into a
    VMEM int8 scratch with its [bm, 1] scales and reused across the column
    blocks, so j runs in order ("arbitrary"); the weight block is quantized
    at every step (its total work is cdiv(M, bm) * K * N elementwise).

``int8_matmul`` takes operands already quantized:

  * grid (M/bm, N/bn, K/bk); K innermost so each (i, j) tile accumulates in a
    VMEM int32 scratch across K steps — MXU-friendly int8 x int8 -> int32.
  * BlockSpecs tile x [bm, bk], w [bk, bn], out [bm, bn]; the scales ride
    as 2-D [M, 1] / [1, N] columns/rows blocked (bm, 1) / (1, bn) along the
    same grid axes, so the epilogue broadcasts them with no 1-D -> 2-D
    relayout (Mosaic refuses 1-D f32 blocks whose tiling differs from XLA's).
  * The f32 rescale happens ONCE, on the last K step, fused in-kernel
    (dequant epilogue) — no extra HBM round-trip for the int32 accumulator.

Block defaults (128, 128, 512) keep the working set
(bm*bk + bk*bn int8 + bm*bn i32) ~ 192 KB << 16 MB VMEM and all dims are
multiples of the 128-lane MXU tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _quantize(v: jax.Array, axis: int) -> tuple[jax.Array, jax.Array]:
    """``ref.quantize_rowwise`` (axis 1) / ``quantize_colwise`` (axis 0) on
    an f32 block, the scale kept 2-D for the epilogue's broadcast."""
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8), scale


def _fused_kernel(x_ref, w_ref, out_ref, xq_ref, xs_ref):
    @pl.when(pl.program_id(1) == 0)
    def _quantize_rows():
        xq_ref[...], xs_ref[...] = _quantize(x_ref[...].astype(jnp.float32), axis=1)

    wq, ws = _quantize(w_ref[...].astype(jnp.float32), axis=0)
    acc = jax.lax.dot_general(
        xq_ref[...], wq, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    out_ref[...] = (acc.astype(jnp.float32) * (xs_ref[...] * ws)).astype(out_ref.dtype)


# The scoped VMEM the fused kernel compiles under: v5e's default scoped limit
# is 16 MiB of its 128 MiB, below what ops.VMEM_BUDGET lets one step hold.
VMEM_LIMIT = 48 << 20


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "out_dtype", "interpret")
)
def quantized_matmul(
    x: jax.Array,  # [M, K] float
    w: jax.Array,  # [K, N] float
    *,
    block_m: int,
    block_n: int,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    bm, bn = block_m, block_n
    return pl.pallas_call(
        _fused_kernel,
        grid=(pl.cdiv(M, bm), pl.cdiv(N, bn)),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, K), jnp.int8), pltpu.VMEM((bm, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=VMEM_LIMIT
        ),
        interpret=interpret,
        # the same instruction name as int8_matmul: the trace reader and the
        # compile tests find every NPU GEMM by it
        name="int8_matmul",
    )(x, w)


def _kernel(x_ref, w_ref, xs_ref, ws_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 x int8 -> int32 on the MXU.
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = xs_ref[...] * ws_ref[...]  # [bm, 1] * [1, bn] -> [bm, bn]
        out_ref[...] = (acc_ref[...].astype(jnp.float32) * scale).astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "out_dtype", "interpret")
)
def int8_matmul(
    x_q: jax.Array,  # [M, K] int8
    w_q: jax.Array,  # [K, N] int8
    x_scale: jax.Array,  # [M] f32
    w_scale: jax.Array,  # [N] f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_q.shape
    K2, N = w_q.shape
    assert K == K2, (x_q.shape, w_q.shape)
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (
        f"shapes ({M},{K})x({K},{N}) must tile by ({bm},{bn},{bk}); pad upstream"
    )
    n_k = K // bk
    grid = (M // bm, N // bn, n_k)

    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        # the kernel's instruction name in a TPU trace, whatever jit wraps it
        name="int8_matmul",
    )(x_q, w_q, x_scale.reshape(M, 1), w_scale.reshape(1, N))
