"""SqueezeNet v1.1 (the authors' SqueezeNet_v1.1 release of arXiv:1602.07360)
as the benchmark knows it, apart from the program: parameter shapes in the
program's layout, seeded weights, a float32 reference forward and the GEMMs
of one frame.

The reference is a copy of the program's plain forward (float32,
``Precision.HIGHEST``).  ``lower`` rounds weights (per output channel)
and activations (per pixel) to a lower precision before every convolution:
the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def is_shape(x) -> bool:
    """A leaf of the shape trees: a tuple of ints."""
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def param_shapes(cfg):
    c = cfg["stem_channels"]
    params = {"stem": {"w": (3, 3, 3, c), "b": (c,)}}
    cin = c
    for gi, group in enumerate(cfg["fires"]):
        for fi, (sq, ex) in enumerate(group):
            params[f"fire{gi}_{fi}"] = {
                "squeeze": {"w": (1, 1, cin, sq), "b": (sq,)},
                "e1": {"w": (1, 1, sq, ex), "b": (ex,)},
                "e3": {"w": (3, 3, sq, ex), "b": (ex,)},
            }
            cin = 2 * ex
    params["classifier"] = {"w": (1, 1, cin, cfg["n_classes"]), "b": (cfg["n_classes"],)}
    return params, {}


def make_weights(key, cfg):
    """Seeded float32 weights: He-normal convolutions, small random biases."""
    params, state = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(params, is_leaf=is_shape)
    out = []
    for k, shape in zip(jax.random.split(key, len(leaves)), leaves):
        z = jax.random.normal(k, shape, jnp.float32)
        if len(shape) == 1:
            out.append(0.1 * z)
        else:
            out.append(z * math.sqrt(2.0 / (shape[0] * shape[1] * shape[2])))
    return jax.tree.unflatten(tree, out), state


def gemms(cfg):
    """``(M, K, N)`` of every GEMM of one frame, in forward order."""
    h = math.ceil(cfg["input_res"] / 2)
    out = [(h * h, 3 * 3 * 3, cfg["stem_channels"])]
    cin = cfg["stem_channels"]
    for group in cfg["fires"]:
        h = math.ceil(h / 2)  # max-pool
        for sq, ex in group:
            out += [(h * h, cin, sq), (h * h, sq, ex), (h * h, 9 * sq, ex)]
            cin = 2 * ex
    out.append((h * h, cin, cfg["n_classes"]))
    return out


def _lower(x, lower, axes):
    """``x`` rounded to ``lower`` with one scale per slice along ``axes``:
    ``lower`` bits of a symmetric integer, or a floating type by name (the
    slice's largest magnitude goes to the type's largest finite value)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    if isinstance(lower, int):
        top = 2.0 ** (lower - 1) - 1
        scale = jnp.where(amax > 0, amax / top, 1.0)
        return jnp.clip(jnp.round(x / scale), -top, top) * scale
    dtype = jnp.dtype(lower)
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _conv(p, x, stride=1, lower=None):
    w = p["w"]
    if lower is not None:
        w, x = _lower(w, lower, (0, 1, 2)), _lower(x, lower, (3,))
    y = lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )
    return y + p["b"]


def _maxpool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def reference_logits(cfg, params, state, images, *, lower=None):
    """Logits ``[B, n_classes]`` in float32, every layer at HIGHEST."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jax.nn.relu(_conv(params["stem"], jnp.asarray(images, jnp.float32), 2, lower))
    for gi, group in enumerate(cfg["fires"]):
        x = _maxpool(x)
        for fi in range(len(group)):
            p = params[f"fire{gi}_{fi}"]
            s = jax.nn.relu(_conv(p["squeeze"], x, 1, lower))
            x = jax.nn.relu(jnp.concatenate(
                [_conv(p["e1"], s, 1, lower), _conv(p["e3"], s, 1, lower)], axis=-1))
    x = _conv(params["classifier"], x, 1, lower)
    return jax.nn.relu(x).mean(axis=(1, 2))
