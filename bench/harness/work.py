"""Operations and bytes of the GEMMs a configuration's forward needs, and
the least time a chip could take for them.

A GEMM is ``(M, K, N)`` per frame at its unpadded shape: a convolution
lowered by im2col has ``M = H_out * W_out``, ``K = kh * kw * C_in`` and
``N = C_out``.  The counts are the algorithm's, whatever pads or tiles them.
"""
from __future__ import annotations


def ops(gemm) -> float:
    m, k, n = gemm
    return 2.0 * m * k * n


def int8_bytes(gemm) -> float:
    """int8 operands, f32 per-row and per-column scales, f32 output."""
    m, k, n = gemm
    return m * k + k * n + 4.0 * m + 4.0 * n + 4.0 * m * n


def least_s(gemm, ops_per_s: float, bytes_per_s: float, nbytes: float) -> float:
    """Roofline time of one GEMM: the larger of its compute and memory times."""
    return max(ops(gemm) / ops_per_s, nbytes / bytes_per_s)


def int8_least_s(gemms, peaks: dict) -> float:
    return sum(least_s(g, peaks["int8_ops_per_s"], peaks["hbm_bytes_per_s"], int8_bytes(g))
               for g in gemms)


def total_ops(gemms) -> float:
    return sum(ops(g) for g in gemms)
