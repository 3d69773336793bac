"""Swin Transformer (windowed, shifted): an encoder-only classifier,
``swin_forward(cfg, params, images) -> logits``, its patch embedding part
of the model.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .common import matmul, spec
from .convnets import conv

SWIN_LN_EPS = 1e-5  # torch.nn.LayerNorm's default, as the source builds every norm


@dataclasses.dataclass(frozen=True)
class SwinConfig:
    name: str
    img_res: int
    patch: int = 4
    window: int = 7
    depths: tuple[int, ...] = (2, 2, 18, 2)
    dims: tuple[int, ...] = (128, 256, 512, 1024)
    n_heads: tuple[int, ...] = (4, 8, 16, 32)
    mlp_ratio: int = 4
    n_classes: int = 1000


def layernorm_specs(dim: int) -> dict:
    return {"scale": spec((dim,), init="ones"), "bias": spec((dim,), init="zeros")}


def layernorm(params, x, eps: float):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)).astype(dt)


def _linear_specs(din: int, dout: int, bias: bool = True) -> dict:
    p = {"w": spec((din, dout))}
    if bias:
        p["b"] = spec((dout,), init="zeros")
    return p


def _swin_block_specs(c: SwinConfig, dim: int, heads: int) -> dict:
    w = c.window
    hidden = dim * c.mlp_ratio
    return {
        "norm1": layernorm_specs(dim),
        "attn": {
            "qkv": _linear_specs(dim, 3 * dim),
            "rel_bias": spec(((2 * w - 1) * (2 * w - 1), heads), scale=0.02),
            "proj": _linear_specs(dim, dim),
        },
        "norm2": layernorm_specs(dim),
        "mlp": {
            "fc1": _linear_specs(dim, hidden),
            "fc2": _linear_specs(hidden, dim),
        },
    }


def swin_abstract_params(c: SwinConfig) -> dict:
    """The source's layout (``models/swin_transformer.py``): one ``qkv``
    Linear per block, every block's parameters of its own (the forward runs
    each block with its own shift, so nothing is stacked)."""
    p: dict = {
        "patch_embed": {
            "proj": {
                "w": spec((c.patch, c.patch, 3, c.dims[0]), init="conv"),
                "b": spec((c.dims[0],), init="zeros"),
            },
            "norm": layernorm_specs(c.dims[0]),
        }
    }
    for i, (depth, dim, heads) in enumerate(zip(c.depths, c.dims, c.n_heads)):
        stage: dict = {"blocks": [_swin_block_specs(c, dim, heads) for _ in range(depth)]}
        if i < len(c.depths) - 1:
            stage["merge"] = {
                "norm": layernorm_specs(4 * dim),
                "reduction": _linear_specs(4 * dim, c.dims[i + 1], bias=False),
            }
        p[f"stage{i}"] = stage
    p["norm"] = layernorm_specs(c.dims[-1])
    p["head"] = _linear_specs(c.dims[-1], c.n_classes)
    return p


def _linear(p, x):
    """A Linear layer: its GEMM through ``common.matmul`` (the int8 kernel
    under ``npu_execution``), its bias added in the activations' dtype."""
    y = matmul(x, p["w"].astype(x.dtype))
    return y + p["b"].astype(x.dtype) if "b" in p else y


def _norm(p, x):
    return layernorm(p, x, eps=SWIN_LN_EPS)


def _rel_bias_index(w: int) -> np.ndarray:
    coords = np.stack(np.meshgrid(np.arange(w), np.arange(w), indexing="ij"), 0).reshape(2, -1)
    rel = coords[:, :, None] - coords[:, None, :]
    rel = rel.transpose(1, 2, 0) + (w - 1)
    return (rel[..., 0] * (2 * w - 1) + rel[..., 1]).astype(np.int32)  # [w*w, w*w]


def _rel_bias(table, w: int):
    """``[heads, S, S]``: the table's entry for each query/key offset in a
    window, picked by a one-hot product at f32 precision (exact: one term
    per sum) rather than a gather, whose TPU lowering pads its index vector
    inside the forward."""
    idx = jnp.asarray(_rel_bias_index(w).reshape(-1))
    onehot = (idx[:, None] == jnp.arange(table.shape[0])[None, :]).astype(jnp.float32)
    bias = jnp.dot(onehot, table.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
    return bias.reshape(w * w, w * w, -1).transpose(2, 0, 1)


def _shift_mask(H: int, W: int, w: int, shift: int) -> np.ndarray:
    """``[nW, S, S]``: True where two tokens of a shifted window came from
    the same region of the map before the cyclic roll."""
    img = np.zeros((H, W), np.int32)
    cnt = 0
    for hs in (slice(0, -w), slice(-w, -shift), slice(-shift, None)):
        for ws in (slice(0, -w), slice(-w, -shift), slice(-shift, None)):
            img[hs, ws] = cnt
            cnt += 1
    mw = img.reshape(H // w, w, W // w, w).transpose(0, 2, 1, 3).reshape(-1, w * w)
    return mw[:, None, :] == mw[:, :, None]


def _window_attention(p, x, H: int, W: int, w: int, heads: int, shift: int):
    """x: [B, H*W, C] -> same: multi-head self-attention inside each w x w
    window, after a cyclic roll by ``shift`` (with the region mask) where
    it is nonzero.  ``qkv`` and ``proj`` go through ``common.matmul``; the
    score and value products stay in the activations' dtype (bf16) on every
    path."""
    B, _, C = x.shape
    hd = C // heads
    xs = x.reshape(B, H, W, C)
    if shift:
        xs = jnp.roll(xs, shift=(-shift, -shift), axis=(1, 2))
    nh, nw = H // w, W // w
    S = w * w
    xw = xs.reshape(B, nh, w, nw, w, C).transpose(0, 1, 3, 2, 4, 5).reshape(B * nh * nw, S, C)

    qkv = _linear(p["qkv"], xw).reshape(B * nh * nw, S, 3, heads, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = jnp.einsum("bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32) * hd ** -0.5
    logits = logits + _rel_bias(p["rel_bias"], w)
    if shift:
        # -1e30 where the source adds -100: exp(-100) < 4e-44, below f32's
        # resolution of any softmax row that holds its own token
        mask = jnp.asarray(_shift_mask(H, W, w, shift))[:, None]  # [nW, 1, S, S]
        logits = jnp.where(mask, logits.reshape(B, nh * nw, heads, S, S), -1e30)
    attn = jax.nn.softmax(logits, axis=-1).astype(v.dtype).reshape(B * nh * nw, heads, S, S)
    out = jnp.einsum("bhst,bthd->bshd", attn, v).reshape(B * nh * nw, S, C)
    y = _linear(p["proj"], out)

    ys = y.reshape(B, nh, nw, w, w, C).transpose(0, 1, 3, 2, 4, 5).reshape(B, H, W, C)
    if shift:
        ys = jnp.roll(ys, shift=(shift, shift), axis=(1, 2))
    return ys.reshape(B, H * W, C)


def _swin_block(p, x, H: int, W: int, w: int, heads: int, shift: int):
    with jax.named_scope("swin.window_attention"):
        x = x + _window_attention(p["attn"], _norm(p["norm1"], x), H, W, w, heads, shift)
    with jax.named_scope("swin.mlp"):
        h = jax.nn.gelu(_linear(p["mlp"]["fc1"], _norm(p["norm2"], x)), approximate=False)
        return x + _linear(p["mlp"]["fc2"], h)


def _patch_merge(p, x, H: int, W: int):
    """2x2 neighbours concatenated in the source's order x0, x1, x2, x3 =
    (0,0), (1,0), (0,1), (1,1) (row offset fastest), normed, reduced."""
    B, _, C = x.shape
    with jax.named_scope("swin.patch_merge"):
        xs = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 4, 2, 5)
        xs = xs.reshape(B, (H // 2) * (W // 2), 4 * C)
        return _linear(p["reduction"], _norm(p["norm"], xs))


def swin_forward(c: SwinConfig, params, images):
    """images: [B, H, W, 3] -> logits [B, n_classes]; the token grid is the
    frame's, H / patch x W / patch.  Every block runs with its own static
    shift (window // 2 on odd blocks, where the map is larger than the
    window), so each of its Linear layers is one ``common.matmul`` call."""
    B, Hi, Wi, _ = images.shape
    H, W = Hi // c.patch, Wi // c.patch
    pe = params["patch_embed"]
    x = conv(pe["proj"]["w"], images.astype(jnp.bfloat16), stride=c.patch, padding="VALID")
    x = x.reshape(B, H * W, c.dims[0]) + pe["proj"]["b"].astype(jnp.bfloat16)
    x = _norm(pe["norm"], x)

    w = c.window
    for i, heads in enumerate(c.n_heads):
        stage = params[f"stage{i}"]
        if H % w or W % w:
            raise ValueError(f"{c.name}: stage {i}'s {H}x{W} token grid is not whole {w}x{w} windows")
        for j, blk in enumerate(stage["blocks"]):
            shift = w // 2 if j % 2 == 1 and min(H, W) > w else 0
            x = _swin_block(blk, x, H, W, w, heads, shift)
        if "merge" in stage:
            x = _patch_merge(stage["merge"], x, H, W)
            H, W = H // 2, W // 2

    h = _norm(params["norm"], x).mean(axis=1)
    return _linear(params["head"], h).astype(jnp.float32)
