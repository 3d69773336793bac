"""AdamW + schedules, from scratch (no optax in this environment).

Optimizer state is a pytree mirroring params: {"m": ..., "v": ...} in f32,
plus a scalar step.  ``adamw_update`` is pure and jit-friendly.
``serving/calibrate.py`` trains its classifiers with it.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def lr_at(c: AdamWConfig, step: jax.Array) -> jax.Array:
    """Linear warmup then cosine decay to min_lr_ratio * lr."""
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(c.warmup_steps, 1)
    prog = jnp.clip(
        (step - c.warmup_steps) / jnp.maximum(c.total_steps - c.warmup_steps, 1), 0.0, 1.0
    )
    cos = c.min_lr_ratio + (1 - c.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return c.lr * jnp.where(step < c.warmup_steps, warm, cos)


def init_opt_state(params: Any) -> dict:
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "m": jax.tree.map(zeros, params),
        "v": jax.tree.map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Any) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_update(c: AdamWConfig, params: Any, grads: Any, opt: dict) -> tuple[Any, dict, dict]:
    """Returns (new_params, new_opt_state, metrics)."""
    step = opt["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, c.grad_clip / jnp.maximum(gnorm, 1e-9)) if c.grad_clip else 1.0
    lr = lr_at(c, step)
    b1t = 1 - c.b1 ** step.astype(jnp.float32)
    b2t = 1 - c.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m2 = c.b1 * m + (1 - c.b1) * g
        v2 = c.b2 * v + (1 - c.b2) * g * g
        mhat = m2 / b1t
        vhat = v2 / b2t
        p32 = p.astype(jnp.float32)
        p2 = p32 - lr * (mhat / (jnp.sqrt(vhat) + c.eps) + c.weight_decay * p32)
        return p2.astype(p.dtype), m2, v2

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt["m"])
    flat_v = jax.tree.leaves(opt["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(treedef, [o[0] for o in out])
    new_m = jax.tree.unflatten(treedef, [o[1] for o in out])
    new_v = jax.tree.unflatten(treedef, [o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, {"m": new_m, "v": new_v, "step": step}, metrics
