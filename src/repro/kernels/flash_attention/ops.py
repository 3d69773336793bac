"""Jit'd public wrapper for flash attention.

``attention(q, k, v, causal=...)`` runs the Pallas kernel: Mosaic on a TPU,
the Pallas interpreter elsewhere (:func:`repro.kernels.platform.interpret_mode`).
"""
from __future__ import annotations

from ..platform import interpret_mode
from . import kernel


def attention(q, k, v, *, causal: bool = True, block_q: int = 256, block_kv: int = 512):
    return kernel.flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv, interpret=interpret_mode()
    )
