"""Model foundations: abstract parameter specs, their materialization, and
the matmul seam — the contract each of the three served classifier families
(``convnets``: ResNet-50, SqueezeNet; ``vision``: Swin-B) implements.

A model family provides:
  abstract_params(cfg) -> dict[str, ParamSpec]      (nested dicts allowed)
  forward(cfg, params, ..., images) -> logits       (pure function)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterable

import jax
import jax.numpy as jnp

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple[int, ...]
    dtype: Any = jnp.float32
    init: str = "normal"  # normal | zeros | ones | conv
    scale: float | None = None  # override fan-in scaling


def spec(shape: Iterable[int], **kw) -> ParamSpec:
    return ParamSpec(tuple(int(s) for s in shape), **kw)


def stack(specs: Pytree, n: int) -> Pytree:
    """Add a leading dim of ``n`` to every spec in the tree: the parameters
    of ``n`` identical blocks that a ``lax.scan`` runs in turn."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, shape=(n, *s.shape)),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


def _fan_in(shape: tuple[int, ...], init: str) -> float:
    if len(shape) == 1:
        return 1.0
    if init == "conv":  # HWIO
        rf = math.prod(shape[:-2]) if len(shape) > 2 else 1
        return float(rf * shape[-2])
    return float(shape[-2]) if len(shape) >= 2 else float(shape[0])


def init_param(key: jax.Array, s: ParamSpec) -> jax.Array:
    if s.init == "zeros":
        return jnp.zeros(s.shape, s.dtype)
    if s.init == "ones":
        return jnp.ones(s.shape, s.dtype)
    scale = s.scale if s.scale is not None else 1.0 / math.sqrt(max(_fan_in(s.shape, s.init), 1.0))
    return (jax.random.normal(key, s.shape, jnp.float32) * scale).astype(s.dtype)


def init_tree(key: jax.Array, specs: Pytree) -> Pytree:
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [init_param(k, s) for k, s in zip(keys, leaves)])


def abstract_tree(specs: Pytree) -> Pytree:
    """ShapeDtypeStructs for .lower() without allocating anything."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, ParamSpec),
    )


# ---------------------------------------------------------------------------
# Matmul backend hook — the "NPU execution" seam.  Model families lower their
# GEMMs (classifier heads, and convolutions via im2col) through matmul(); an
# installed backend replaces the plain jnp contraction — quant/npu_exec uses
# this to route every matmul of the int8 variant through the Pallas
# kernels/npu_matmul kernel (interpret mode on CPU, Mosaic on TPU).  Outside
# a backend context matmul() is exactly ``x @ w``, so training and the fp32
# "edge" path are untouched.
# ---------------------------------------------------------------------------

_ACTIVE_MATMUL: list[Any] = []


class matmul_backend:
    """Context manager installing fn(x2d [M, K], w2d [K, N]) -> [M, N] for
    every matmul() call (active at trace time, so it composes with jit)."""

    def __init__(self, fn: Any):
        self.fn = fn

    def __enter__(self):
        _ACTIVE_MATMUL.append(self.fn)
        return self.fn

    def __exit__(self, *exc):
        _ACTIVE_MATMUL.pop()


def current_matmul():
    return _ACTIVE_MATMUL[-1] if _ACTIVE_MATMUL else None


def matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """[..., K] x [K, N] through the active backend (plain ``@`` if none)."""
    if not _ACTIVE_MATMUL:
        return x @ w
    fn = _ACTIVE_MATMUL[-1]
    lead = x.shape[:-1]
    out = fn(x.reshape(-1, x.shape[-1]), w)
    return out.reshape(*lead, w.shape[-1]).astype(x.dtype)
