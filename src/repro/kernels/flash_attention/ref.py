"""Pure-jnp oracle for the flash attention kernel.

Two references:
  * ``sdpa_ref`` — naive O(S^2) softmax attention (ground truth).
  * ``blockwise_ref`` — the online-softmax blockwise algorithm in plain jnp;
    numerically equivalent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sdpa_ref(q, k, v, *, causal: bool) -> jnp.ndarray:
    """q: [B,S,H,hd]; k/v: [B,T,KH,hd] (GQA when H > KH)."""
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, hd)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) / jnp.sqrt(hd).astype(
        jnp.float32
    )
    if causal:
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", w.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


def blockwise_ref(q, k, v, *, causal: bool, q_block: int = 512, kv_block: int = 1024):
    """The kernel's online-softmax algorithm in plain jnp: a map over Q
    blocks, each scanning its KV blocks — O(S * kv_block) workspace.

    q: [B,S,H,hd]; k/v: [B,T,KH,hd].
    """
    B, S, H, hd = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    pad_q = (-S) % q_block
    pad_k = (-T) % kv_block
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    Sq, Tk = S + pad_q, T + pad_k
    nq, nk = Sq // q_block, Tk // kv_block

    qp = qp.reshape(B, nq, q_block, KH, G, hd)
    kp = kp.reshape(B, nk, kv_block, KH, hd)
    vp = vp.reshape(B, nk, kv_block, KH, hd)

    def q_block_fn(i):
        qi = qp[:, i]  # [B, qb, KH, G, hd]
        q_pos = i * q_block + jnp.arange(q_block)

        def kv_step(carry, j):
            acc, m, denom = carry
            kj = kp[:, j]
            vj = vp[:, j]
            logits = (
                jnp.einsum("bqkgd,btkd->bkgqt", qi, kj).astype(jnp.float32) * scale
            )  # [B,KH,G,qb,kvb]
            kv_pos = j * kv_block + jnp.arange(kv_block)
            valid = kv_pos[None, :] < T
            if causal:
                valid = valid & (kv_pos[None, :] <= q_pos[:, None])
            logits = jnp.where(valid[None, None, None], logits, -1e30)
            m_new = jnp.maximum(m, logits.max(-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(logits - m_new[..., None])
            denom = denom * alpha + pexp.sum(-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", pexp.astype(vj.dtype), vj
            ).astype(jnp.float32)
            return (acc, m_new, denom), None

        acc0 = jnp.zeros((B, KH, G, q_block, hd), jnp.float32)
        m0 = jnp.full((B, KH, G, q_block), -1e30, jnp.float32)
        d0 = jnp.zeros((B, KH, G, q_block), jnp.float32)
        (acc, m, denom), _ = jax.lax.scan(kv_step, (acc0, m0, d0), jnp.arange(nk))
        out = acc / jnp.maximum(denom[..., None], 1e-30)
        return out  # [B,KH,G,qb,hd]

    out = jax.lax.map(q_block_fn, jnp.arange(nq))  # [nq,B,KH,G,qb,hd]
    out = jnp.moveaxis(out, 0, 3).reshape(B, KH, G, Sq, hd)[:, :, :, :S]
    out = jnp.moveaxis(out, 3, 1).reshape(B, S, KH * G, hd).astype(q.dtype)
    return out
