"""Pallas TPU kernels for the perf-critical compute layers.

  npu_matmul       w8a8 int8 GEMM with fused dequant epilogue — the FastVA
                   "NPU path" (the paper's 8-bit phone NPU, TPU-native).
  flash_attention  online-softmax attention, GQA-folded tiles (no model
                   calls it yet; its tests hold it to its references).

Each package: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd wrapper
with backend dispatch), ref.py (pure-jnp oracle).  CPU validation runs the
kernel bodies in interpret mode; TPU compiles to Mosaic.
"""
from . import flash_attention, npu_matmul  # noqa: F401
