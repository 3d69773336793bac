"""NPU execution: run the int8 variant's matmuls through the real kernel.

``quantize.py`` makes the *weights* real int8 (fake-quant round-off); this
module makes the *arithmetic* real: inside an :class:`npu_execution` context
every GEMM a model family lowers through ``models.common.matmul()`` —
classifier heads, and convolutions via im2col (``models/convnets.py``) —
executes as ``kernels/npu_matmul``'s w8a8 Pallas kernel (interpret mode on
CPU, Mosaic on TPU) instead of a float contraction.  Per-row activation and
per-output-channel weight scales match ``quantize._fake_quant``'s scheme, so
quantizing the already fake-quant weights is idempotent: the int8 values the
kernel multiplies are exactly the deployed NPU weights.

``serving/calibrate.py`` builds its measured t_npu/accuracy profiles on top
of this; nothing here is serving-specific.
"""
from __future__ import annotations

from typing import Any, Callable

import jax

from ..kernels.npu_matmul import ops as npu_ops
from ..models import common


def npu_dense(x2d: jax.Array, w2d: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """One NPU-path GEMM: both sides quantized to int8 and multiplied in one
    Pallas call (``npu_ops.npu_matmul`` picks the path from the shape)."""
    return npu_ops.npu_matmul(x2d, w2d, interpret=interpret)


class npu_execution(common.matmul_backend):
    """Context manager: every ``models.common.matmul()`` call (and every conv
    lowered through it) routes through ``kernels/npu_matmul`` while active.
    Active at trace time, so it composes with ``jax.jit``."""

    def __init__(self, *, interpret: bool | None = None):
        super().__init__(lambda x, w: npu_dense(x, w, interpret=interpret))


def npu_forward(forward: Callable[..., Any], *, interpret: bool | None = None) -> Callable[..., Any]:
    """Wrap a classifier forward so its matmuls execute on the NPU path.

    The wrapper installs the backend around every invocation (including the
    jit trace), so ``jax.jit(npu_forward(f))`` compiles the kernel-routed
    graph while ``f`` itself stays the full-precision edge variant.
    """

    def fwd(*args, **kwargs):
        with npu_execution(interpret=interpret):
            return forward(*args, **kwargs)

    return fwd
