"""Swin-B (arXiv:2103.14030; microsoft/Swin-Transformer,
``configs/swin/swin_base_patch4_window7_224.yaml``) as the benchmark knows
it, apart from the program: parameter shapes in the program's layout,
seeded weights, a float32 reference forward and the GEMMs of one frame.

The reference is written from the source's ``models/swin_transformer.py``:
patch embedding, then per stage its blocks (window attention, every odd
block on a map larger than the window shifted by half a window under the
source's region mask, additive -100), patch merging in the source's
x0, x1, x2, x3 order, and a mean-pooled head.  Every contraction is float32
at ``Precision.HIGHEST``; there is no scan, no cond and no matmul backend.
``lower`` rounds the operands of every Linear layer (weights per output
column, activations per row) and of both attention products to a lower
precision before the product: the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
LN_EPS = 1e-5
MASKED = -100.0  # what the source adds to a score across regions of a shifted window


def is_shape(x) -> bool:
    """A leaf of the shape trees: a tuple of ints."""
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _stages(cfg):
    """``(stage, depth, dim, heads)``; each stage's width doubles."""
    for i, (depth, heads) in enumerate(zip(cfg["depths"], cfg["heads"])):
        yield i, depth, cfg["embed_dim"] * 2 ** i, heads


def param_shapes(cfg):
    """``(params, state)``: nested dicts of shapes; Swin keeps no state."""
    ln = lambda c: {"scale": (c,), "bias": (c,)}  # noqa: E731
    lin = lambda a, b: {"w": (a, b), "b": (b,)}  # noqa: E731
    p, c0, w = cfg["patch"], cfg["embed_dim"], cfg["window"]
    params = {"patch_embed": {"proj": {"w": (p, p, 3, c0), "b": (c0,)}, "norm": ln(c0)}}
    n = len(cfg["depths"])
    for i, depth, dim, heads in _stages(cfg):
        hidden = dim * cfg["mlp_ratio"]
        block = {"norm1": ln(dim),
                 "attn": {"qkv": lin(dim, 3 * dim), "rel_bias": ((2 * w - 1) ** 2, heads),
                          "proj": lin(dim, dim)},
                 "norm2": ln(dim),
                 "mlp": {"fc1": lin(dim, hidden), "fc2": lin(hidden, dim)}}
        stage = {"blocks": [block] * depth}
        if i < n - 1:
            stage["merge"] = {"norm": ln(4 * dim), "reduction": {"w": (4 * dim, 2 * dim)}}
        params[f"stage{i}"] = stage
    last = cfg["embed_dim"] * 2 ** (n - 1)
    params["norm"] = ln(last)
    params["head"] = lin(last, cfg["n_classes"])
    return params, {}


def make_weights(key, cfg):
    """Seeded float32 weights, every term large enough to matter in the
    comparison: Linear weights normal, truncated at two standard deviations,
    at std 1/sqrt(fan-in), so that each branch's output is of the residual
    stream's size (at the source's initial std 0.02 every attention branch
    adds about 2% to the stream, and a forward that dropped the bias table,
    the shift or the mask agreed with the reference to 1e-3); the patch
    embedding's convolution at PyTorch's default scale (std 1/sqrt(3
    fan-in)); LayerNorm affine near the identity (1 + 0.1 z, 0.1 z); biases
    0.1 z; the relative-position-bias table at unit scale."""
    shapes, state = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=is_shape)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes, is_leaf=is_shape)[0]]
    out = []
    for k, path, shape in zip(jax.random.split(key, len(leaves)), paths, leaves):
        if "'rel_bias'" in path:
            out.append(jax.random.normal(k, shape, jnp.float32))
        elif "'scale'" in path:
            out.append(1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32))
        elif len(shape) == 1:
            out.append(0.1 * jax.random.normal(k, shape, jnp.float32))
        elif len(shape) == 4:  # the patch embedding's convolution, HWIO
            fan_in = shape[0] * shape[1] * shape[2]
            out.append(jax.random.normal(k, shape, jnp.float32) / math.sqrt(3.0 * fan_in))
        else:
            out.append(jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
                       / math.sqrt(shape[0]))
    return jax.tree.unflatten(tree, out), state


def gemms(cfg):
    """``(M, K, N)`` of every Linear layer of one frame, in forward order:
    the patch embedding, per block ``qkv``, ``proj``, ``fc1`` and ``fc2``,
    each patch merge's reduction, the head."""
    p = cfg["patch"]
    h = cfg["input_res"] // p
    out = [(h * h, p * p * 3, cfg["embed_dim"])]
    n = len(cfg["depths"])
    for i, depth, dim, _ in _stages(cfg):
        hidden = dim * cfg["mlp_ratio"]
        out += [(h * h, dim, 3 * dim), (h * h, dim, dim), (h * h, dim, hidden),
                (h * h, hidden, dim)] * depth
        if i < n - 1:
            h //= 2
            out.append((h * h, 4 * dim, 2 * dim))
    out.append((1, dim, cfg["n_classes"]))
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


def _matmul(a, b, lower):
    """``a @ b`` over the last axis of ``a`` and the second-last of ``b``;
    with ``lower`` each row of ``a`` and each column of ``b`` rounded first."""
    if lower is not None:
        a, b = _lower(a, lower, (-1,)), _lower(b, lower, (-2,))
    return jnp.matmul(a, b, precision=HIGHEST)


def _linear(p, x, lower):
    y = _matmul(x, p["w"], lower)
    return y + p["b"] if "b" in p else y


def _layer_norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _window_partition(x, w):
    B, H, W, C = x.shape
    x = x.reshape(B, H // w, w, W // w, w, C).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, w, w, C)


def _window_reverse(windows, w, H, W):
    B = windows.shape[0] // (H * W // w // w)
    x = windows.reshape(B, H // w, W // w, w, w, -1).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H, W, -1)


def _relative_position_index(w):
    coords = np.stack(np.meshgrid(np.arange(w), np.arange(w), indexing="ij"))  # [2, w, w]
    flat = coords.reshape(2, -1)
    rel = (flat[:, :, None] - flat[:, None, :]).transpose(1, 2, 0)  # [S, S, 2]
    rel = rel + (w - 1)
    rel[:, :, 0] *= 2 * w - 1
    return rel.sum(-1)


def _attn_mask(H, W, w, shift):
    """``[nW, S, S]``: 0 within a region of the rolled map, ``MASKED`` across."""
    img = np.zeros((1, H, W, 1), np.float32)
    cnt = 0
    for hs in (slice(0, -w), slice(-w, -shift), slice(-shift, None)):
        for ws in (slice(0, -w), slice(-w, -shift), slice(-shift, None)):
            img[:, hs, ws, :] = cnt
            cnt += 1
    mw = _window_partition(img, w).reshape(-1, w * w)
    diff = mw[:, None, :] - mw[:, :, None]
    return np.where(diff != 0, MASKED, 0.0).astype(np.float32)


def _window_attention(p, x, heads, w, mask, lower):
    """x: ``[B_, N, C]`` windows -> same."""
    B_, N, C = x.shape
    hd = C // heads
    qkv = _linear(p["qkv"], x, lower).reshape(B_, N, 3, heads, hd).transpose(2, 0, 3, 1, 4)
    q, k, v = qkv[0] * hd ** -0.5, qkv[1], qkv[2]
    attn = _matmul(q, k.swapaxes(-2, -1), lower)  # [B_, heads, N, N]
    table = p["rel_bias"][_relative_position_index(w).reshape(-1)]
    attn = attn + table.reshape(N, N, heads).transpose(2, 0, 1)[None]
    if mask is not None:
        nw = mask.shape[0]
        attn = attn.reshape(B_ // nw, nw, heads, N, N) + mask[None, :, None]
        attn = attn.reshape(B_, heads, N, N)
    attn = jax.nn.softmax(attn, axis=-1)
    out = _matmul(attn, v, lower).transpose(0, 2, 1, 3).reshape(B_, N, C)
    return _linear(p["proj"], out, lower)


def _block(p, x, H, W, heads, w, shift, lower):
    B, L, C = x.shape
    shortcut = x
    x = _layer_norm(p["norm1"], x).reshape(B, H, W, C)
    if shift:
        x = jnp.roll(x, shift=(-shift, -shift), axis=(1, 2))
    windows = _window_partition(x, w).reshape(-1, w * w, C)
    mask = jnp.asarray(_attn_mask(H, W, w, shift)) if shift else None
    windows = _window_attention(p["attn"], windows, heads, w, mask, lower)
    x = _window_reverse(windows.reshape(-1, w, w, C), w, H, W)
    if shift:
        x = jnp.roll(x, shift=(shift, shift), axis=(1, 2))
    x = shortcut + x.reshape(B, L, C)
    h = _gelu(_linear(p["mlp"]["fc1"], _layer_norm(p["norm2"], x), lower))
    return x + _linear(p["mlp"]["fc2"], h, lower)


def _patch_merge(p, x, H, W, lower):
    B, _, C = x.shape
    x = x.reshape(B, H, W, C)
    x0 = x[:, 0::2, 0::2, :]
    x1 = x[:, 1::2, 0::2, :]
    x2 = x[:, 0::2, 1::2, :]
    x3 = x[:, 1::2, 1::2, :]
    x = jnp.concatenate([x0, x1, x2, x3], -1).reshape(B, -1, 4 * C)
    return _linear(p["reduction"], _layer_norm(p["norm"], x), lower)


def reference_logits(cfg, params, state, images, *, lower=None):
    """Logits ``[B, n_classes]`` in float32, every contraction at HIGHEST."""
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    x = jnp.asarray(images, jnp.float32)
    B, Hi, Wi, cin = x.shape
    ps, w = cfg["patch"], cfg["window"]
    H, W = Hi // ps, Wi // ps
    # a ps x ps convolution of stride ps: each patch's pixels (row, column,
    # channel) against the HWIO kernel flattened the same way
    patches = x.reshape(B, H, ps, W, ps, cin).transpose(0, 1, 3, 2, 4, 5)
    patches = patches.reshape(B, H * W, ps * ps * cin)
    pe = p["patch_embed"]
    x = _linear({"w": pe["proj"]["w"].reshape(ps * ps * cin, -1), "b": pe["proj"]["b"]},
                patches, lower)
    x = _layer_norm(pe["norm"], x)
    n = len(cfg["depths"])
    for i, depth, _, heads in _stages(cfg):
        stage = p[f"stage{i}"]
        for j in range(depth):
            # the source: no shift where one window covers the map
            shift = w // 2 if j % 2 == 1 and min(H, W) > w else 0
            x = _block(stage["blocks"][j], x, H, W, heads, w, shift, lower)
        if i < n - 1:
            x = _patch_merge(stage["merge"], x, H, W, lower)
            H, W = H // 2, W // 2
    h = _layer_norm(p["norm"], x).mean(1)
    return _linear(p["head"], h, lower)
