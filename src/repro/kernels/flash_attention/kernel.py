"""Pallas TPU flash attention (forward), GQA-aware.

TPU-native design decisions (vs the CUDA flash-attention):
  * grid = (B * KH, n_q_blocks, n_kv_blocks) with the KV axis INNERMOST so the
    online-softmax accumulators (m, l, acc) live in VMEM scratch across the
    KV sweep — the TPU analogue of a CUDA thread-block's shared-memory state.
  * Q/K/V blocks are tiled (block_q x hd) / (block_kv x hd) in VMEM; hd is a
    full lane dimension (128 in the tests' widest cases), so MXU matmuls are
    (block_q x hd) @ (hd x block_kv) — both operands hardware-aligned.
  * GQA: the G query heads of one KV head are FOLDED into the q-block rows
    ((G*Sq) x hd), so grouped queries share the K/V block loads through VMEM
    instead of re-reading HBM per head — the MXU sees taller tiles, the HBM
    sees K/V once.
  * causal masking via block-index arithmetic; fully-masked blocks still run
    (Pallas TPU grids are dense) but their contribution is exactly zero.

Forward only: a backward pass would differentiate the jnp oracle
(``ref.blockwise_ref``).  No served model calls the kernel yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            n_kv: int, block_q: int, block_kv: int, causal: bool, sm_scale: float,
            g: int, seq_q: int, seq_kv: int):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # [g*block_q, hd]
    k = k_ref[...]  # [block_kv, hd]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale  # [g*block_q, block_kv]

    # Row/col absolute positions (rows are g query heads x block_q tokens).
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % block_q + q_i * block_q
    cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + kv_i * block_kv
    valid = cols < seq_kv
    if causal:
        valid &= cols <= rows + (seq_kv - seq_q)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kv_i == n_kv - 1)
    def _fin():
        out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(
            out_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_kv", "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, KH, hd]
    v: jax.Array,  # [B, T, KH, hd]
    *,
    causal: bool = True,
    block_q: int = 256,
    block_kv: int = 512,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    sm_scale = 1.0 / (hd**0.5)

    bq = min(block_q, S)
    bkv = min(block_kv, T)
    pad_q = (-S) % bq
    pad_kv = (-T) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    Sq, Tk = S + pad_q, T + pad_kv
    n_q, n_kv = Sq // bq, Tk // bkv

    # Layout: fold (B, KH) into the leading grid axis; queries grouped per KV
    # head as [B*KH, n_q, G*bq, hd] so one kernel invocation sees all G heads.
    qg = q.reshape(B, Sq, KH, G, hd).transpose(0, 2, 3, 1, 4).reshape(B * KH, G, Sq, hd)
    qg = qg.reshape(B * KH, G, n_q, bq, hd).transpose(0, 2, 1, 3, 4).reshape(
        B * KH, n_q, G * bq, hd
    )
    kg = k.transpose(0, 2, 1, 3).reshape(B * KH, Tk, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(B * KH, Tk, hd)

    grid = (B * KH, n_q, n_kv)
    out = pl.pallas_call(
        functools.partial(
            _kernel, n_kv=n_kv, block_q=bq, block_kv=bkv, causal=causal,
            sm_scale=sm_scale, g=G, seq_q=S, seq_kv=T,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, G * bq, hd), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, bkv, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bkv, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G * bq, hd), lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KH, n_q, G * bq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq,), jnp.float32),
            pltpu.VMEM((G * bq,), jnp.float32),
            pltpu.VMEM((G * bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qg, kg, vg)

    out = out.reshape(B * KH, n_q, G, bq, hd).transpose(0, 2, 1, 3, 4).reshape(
        B, KH, G, Sq, hd
    )[:, :, :, :S]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd)
