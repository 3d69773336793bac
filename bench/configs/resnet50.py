"""ResNet-50 v1.5 (arXiv:1512.03385, with the stride of each stage's first
block on its 3x3 convolution) as the benchmark knows it, apart from the
program: the shapes of its parameters in the program's layout, seeded
weights, a float32 reference forward, and the GEMMs of one frame.

The reference is a copy of the program's plain forward (every convolution
and matmul in float32 at ``Precision.HIGHEST``), kept here so that no
change to the program moves the yardstick.  ``lower`` rounds weights (per
output channel) and activations (per pixel) to a lower precision before
every convolution and matmul: the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5


def is_shape(x) -> bool:
    """A leaf of the shape trees: a tuple of ints."""
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _stage_dims(cfg):
    cin = cfg["width"]
    for i, depth in enumerate(cfg["depths"]):
        cmid = cfg["width"] * 2 ** i
        cout = cmid * cfg["expansion"]
        yield i, depth, cin, cmid, cout, (1 if i == 0 else 2)
        cin = cout


def param_shapes(cfg):
    """``(params, state)``: nested dicts of shapes, stacked blocks leading."""
    bn = lambda c: {"scale": (c,), "bias": (c,)}  # noqa: E731
    bn_state = lambda c: {"mean": (c,), "var": (c,)}  # noqa: E731

    def block(cin, cmid, cout, proj, lead=()):
        p = {"conv1": (1, 1, cin, cmid), "bn1": bn(cmid), "conv2": (3, 3, cmid, cmid),
             "bn2": bn(cmid), "conv3": (1, 1, cmid, cout), "bn3": bn(cout)}
        s = {"bn1": bn_state(cmid), "bn2": bn_state(cmid), "bn3": bn_state(cout)}
        if proj:
            p["proj"], p["bn_proj"], s["bn_proj"] = (1, 1, cin, cout), bn(cout), bn_state(cout)
        stack = lambda t: jax.tree.map(lambda sh: lead + sh, t, is_leaf=is_shape)  # noqa: E731
        return stack(p), stack(s)

    w = cfg["width"]
    params = {"stem": {"conv": (7, 7, 3, w), "bn": bn(w)}}
    state = {"stem": {"bn": bn_state(w)}}
    for i, depth, cin, cmid, cout, stride in _stage_dims(cfg):
        params[f"stage{i}_first"], state[f"stage{i}_first"] = block(
            cin, cmid, cout, stride != 1 or cin != cout)
        if depth > 1:
            params[f"stage{i}_rest"], state[f"stage{i}_rest"] = block(
                cout, cmid, cout, False, (depth - 1,))
        last = cout
    params["head"] = {"w": (last, cfg["n_classes"]), "b": (cfg["n_classes"],)}
    return params, state


def make_weights(key, cfg):
    """Seeded weights in float32, the type the program serves them in:
    He-normal convolutions, a 1/sqrt(fan-in) head, and BatchNorm statistics
    and affine parameters drawn near the identity so that every term of
    the forward carries weight."""
    shapes = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, path, shape in zip(keys, paths, leaves):
        z = jax.random.normal(k, shape, jnp.float32)
        if "'var'" in path:
            out.append(1.0 + 0.2 * jnp.abs(z))
        elif "'scale'" in path:
            out.append(1.0 + 0.1 * z)
        elif "'mean'" in path or "'bias'" in path or "'b'" in path:
            out.append(0.1 * z)
        elif "'head'" in path:
            out.append(z / math.sqrt(shape[-2]))
        else:  # a convolution, HWIO (stacked blocks lead)
            fan_in = shape[-4] * shape[-3] * shape[-2]
            out.append(z * math.sqrt(2.0 / fan_in))
    return jax.tree.unflatten(tree, out)


def gemms(cfg):
    """``(M, K, N)`` of every GEMM of one frame, in forward order."""
    out = []
    h = math.ceil(cfg["input_res"] / 2)
    out.append((h * h, 7 * 7 * 3, cfg["width"]))
    h = math.ceil(h / 2)  # max-pool
    for _, depth, cin, cmid, cout, stride in _stage_dims(cfg):
        for r in range(depth):
            s = stride if r == 0 else 1
            c_in = cin if r == 0 else cout
            ho = math.ceil(h / s)
            out += [(h * h, c_in, cmid), (ho * ho, 9 * cmid, cmid), (ho * ho, cmid, cout)]
            if r == 0 and (s != 1 or c_in != cout):
                out.append((ho * ho, c_in, cout))
            h = ho
    out.append((1, cout, cfg["n_classes"]))
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


def _conv(w, x, stride=1, lower=None):
    if lower is not None:
        w, x = _lower(w, lower, (0, 1, 2)), _lower(x, lower, (3,))
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _bn(p, s, x):
    return (x - s["mean"]) / jnp.sqrt(s["var"] + BN_EPS) * p["scale"] + p["bias"]


def _maxpool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def _bottleneck(p, s, x, stride, lower):
    h = jax.nn.relu(_bn(p["bn1"], s["bn1"], _conv(p["conv1"], x, 1, lower)))
    h = jax.nn.relu(_bn(p["bn2"], s["bn2"], _conv(p["conv2"], h, stride, lower)))
    h = _bn(p["bn3"], s["bn3"], _conv(p["conv3"], h, 1, lower))
    if "proj" in p:
        x = _bn(p["bn_proj"], s["bn_proj"], _conv(p["proj"], x, stride, lower))
    return jax.nn.relu(h + x)


def reference_logits(cfg, params, state, images, *, lower=None):
    """Logits ``[B, n_classes]`` in float32, every layer at HIGHEST."""
    f32 = lambda t: jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), t)  # noqa: E731
    params, state, x = f32(params), f32(state), jnp.asarray(images, jnp.float32)
    x = _conv(params["stem"]["conv"], x, 2, lower)
    x = _maxpool(jax.nn.relu(_bn(params["stem"]["bn"], state["stem"]["bn"], x)))
    for i, depth in enumerate(cfg["depths"]):
        stride = 1 if i == 0 else 2
        x = _bottleneck(params[f"stage{i}_first"], state[f"stage{i}_first"], x, stride, lower)
        for r in range(depth - 1):  # the stacked blocks, one at a time
            p, s = jax.tree.map(lambda t, r=r: t[r], (params[f"stage{i}_rest"], state[f"stage{i}_rest"]))
            x = _bottleneck(p, s, x, 1, lower)
    h = x.mean(axis=(1, 2))
    w = params["head"]["w"]
    if lower is not None:
        h, w = _lower(h, lower, (1,)), _lower(w, lower, (0,))
    return jnp.dot(h, w, precision=HIGHEST) + params["head"]["b"]
