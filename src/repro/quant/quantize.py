"""The "NPU variant" factory: int8 fake-quantization of model weights.

FastVA's phone NPU runs CNNs in 8/16-bit and loses accuracy in a
model-dependent way (paper §III.A: VGG barely, ResNet ~20%, YOLO badly).
Here every architecture gets a quantized variant whose error is REAL int8
round-off (symmetric per-output-channel, matching the Pallas kernel's
scheme), so the scheduler's accuracy/latency tradeoff is grounded in actual
arithmetic rather than assumed constants.  On TPU the quantized variant's
matmuls run through kernels/npu_matmul; fake-quant params make CPU tests and
profile calibration backend-independent.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class QuantStats:
    leaves_quantized: int = 0
    leaves_kept: int = 0
    mean_rel_err: float = 0.0
    max_rel_err: float = 0.0


def _fake_quant(w: jax.Array) -> jax.Array:
    """Symmetric per-output-channel (last dim) int8 quantize-dequantize."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127)
    return (q * scale).astype(w.dtype)


# Leaves added to a GEMM's output rather than multiplied in one: Swin's
# relative-position-bias table (``models/vision.py``), gathered into every
# window's attention scores.
ADDENDS = frozenset({"rel_bias"})


def fake_quant_tree(params: Any, *, min_ndim: int = 2) -> Any:
    """Quantize every floating leaf with ndim >= min_ndim (weights/embeddings);
    biases, norm scales and the ``ADDENDS`` stay exact, matching real NPU
    toolchains."""

    def q(path, x):
        if path and getattr(path[-1], "key", None) in ADDENDS:
            return x
        if jnp.issubdtype(x.dtype, jnp.floating) and x.ndim >= min_ndim:
            return _fake_quant(x)
        return x

    return jax.tree_util.tree_map_with_path(q, params)


@jax.jit
def _leaf_errors(params: Any, qparams: Any) -> list:
    """Per leaf: ``(changed, relative L2 error)`` for a floating leaf whose
    shape survived quantization, ``None`` for every other leaf — one
    compiled pass and one transfer, where a leaf-by-leaf loop pays a
    dispatch (and on an accelerator a compile) per small op."""
    out = []
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(qparams)):
        if not jnp.issubdtype(a.dtype, jnp.floating) or a.shape != b.shape:
            out.append(None)
            continue
        denom = jnp.linalg.norm(a.astype(jnp.float32))
        err = jnp.linalg.norm((a - b).astype(jnp.float32))
        out.append((jnp.any(a != b), err / jnp.where(denom > 0, denom, 1.0)))
    return out


def quant_error_stats(params: Any, qparams: Any) -> QuantStats:
    stats = QuantStats()
    rels = []
    # int/bool leaves, and leaves quantization left unchanged, count as kept
    for leaf in jax.device_get(_leaf_errors(params, qparams)):
        if leaf is None or not leaf[0]:
            stats.leaves_kept += 1
            continue
        rels.append(float(leaf[1]))
        stats.leaves_quantized += 1
    if rels:
        stats.mean_rel_err = sum(rels) / len(rels)
        stats.max_rel_err = max(rels)
    return stats


def npu_variant(params: Any) -> tuple[Any, QuantStats]:
    """The deployable NPU-path weights: int8 fake-quant + stats."""
    q = jax.jit(fake_quant_tree)(params)
    return q, quant_error_stats(params, q)


def agreement(
    forward: Callable[[Any, jax.Array], jax.Array],
    params_fp: Any,
    params_q: Any,
    inputs: jax.Array,
) -> float:
    """Top-1 agreement between full-precision and quantized variants — the
    measurable analogue of the paper's NPU accuracy drop (Fig. 1b)."""
    a = jnp.argmax(forward(params_fp, inputs), axis=-1)
    b = jnp.argmax(forward(params_q, inputs), axis=-1)
    return float(jnp.mean((a == b).astype(jnp.float32)))
