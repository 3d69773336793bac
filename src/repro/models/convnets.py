"""Convolutional classifiers: ResNet-50, EfficientNet-B7, SqueezeNet.

BatchNorm models carry a separate mutable ``state`` tree (running mean/var);
``forward(..., train=True)`` returns (logits, new_state).  Stages scan their
repeated identical blocks (stacked params) so the 512-device SPMD compile of
EfficientNet-B7's 55 blocks stays tractable.
"""
from __future__ import annotations

import collections
import dataclasses
import math


import jax
import jax.numpy as jnp

from .common import current_matmul, matmul, shard, spec
from .lm import _stack

BN_MOMENTUM = 0.9


def conv_spec(kh, kw, cin, cout, name_in="conv_in", name_out="conv_out"):
    return spec((kh, kw, cin, cout), (None, None, name_in, name_out), init="conv")


def conv(p, x, stride=1, padding="SAME", groups=1):
    if current_matmul() is not None and groups == 1:
        return _conv_via_matmul(p, x, stride, padding)
    return jax.lax.conv_general_dilated(
        x,
        p.astype(x.dtype),
        window_strides=(stride, stride),
        padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )


def _conv_via_matmul(p, x, stride, padding):
    """im2col lowering: the conv as ONE [B*H'*W', KH*KW*Cin] x [., Cout] GEMM
    through the active matmul backend — how NPUs (and the int8 Pallas path)
    actually execute convolutions.  The patches are in (kh, kw, cin) order,
    so the weight is its HWIO array reshaped, with no transpose.  Depthwise
    convs (groups > 1) stay on lax.conv: they are channel-parallel scalar
    products, not GEMMs."""
    kh, kw, cin, cout = p.shape
    w2d = p.reshape(kh * kw * cin, cout).astype(x.dtype)
    return matmul(im2col(x, kh, kw, stride, padding), w2d)


# A patch row is built by the one-hot convolution where the KH*KW views
# would outnumber its 128-lane tiles more than this many times over: each
# view of a narrow input is written padded to whole tiles.  Device time
# outside the int8 kernel on one v5e: the convolution took ResNet-50's
# 7x7/2 stem from 0.367 to 0.232 ms (24.5 views per tile), while the views
# took SqueezeNet's 3x3/2 stem from 0.111 to 0.094 ms (9 per tile) and a
# 3x3 conv of 16 channels at 56 x 56 from 0.019 to 0.008 ms (4.5 per tile).
VIEWS_PER_TILE = 16
LANES = 128

# Conv call sites lowered per patch builder ("pointwise",
# "strided_pointwise", "nonoverlap", "patch_op", "slices"), counted when
# im2col is traced: a jit traced once, or a scan body, counts once.
CONV_PATHS: collections.Counter = collections.Counter()


def im2col(x, kh, kw, stride, padding):
    """[B, H, W, Cin] -> [B, H', W', KH*KW*Cin] patches, channel fastest,
    then kw, then kh: the rows of an HWIO weight reshaped to 2-D.

    The builder follows from the shapes alone:
    - a 1x1 kernel with no border is the input, subsampled by the stride;
    - a kernel equal to its stride with no border tiles the input, so the
      patches are one reshape and one transpose (Swin's 4x4/4 patch
      embedding: 0.026 ms outside the kernel on a v5e, against 0.093 ms
      through the one-hot convolution);
    - where the KH*KW views would be more than ``VIEWS_PER_TILE`` times
      the 128-lane tiles of a patch row (a narrow input under a wide
      window: ResNet-50's stem), one convolution against a one-hot filter;
    - anything else concatenates the KH*KW strided views of the input, its
      border built from zero rows and columns so that XLA fuses it into the
      concatenation.
    """
    b, h, w, cin = x.shape
    if isinstance(padding, str):
        padding = jax.lax.padtype_to_pads((h, w), (kh, kw), (stride, stride), padding)
    (top, bottom), (left, right) = padding
    ho = (h + top + bottom - kh) // stride + 1
    wo = (w + left + right - kw) // stride + 1
    border = top or bottom or left or right
    if (kh, kw) == (1, 1) and not border:
        CONV_PATHS["pointwise" if stride == 1 else "strided_pointwise"] += 1
        return jax.lax.slice(x, (0, 0, 0, 0), x.shape, (1, stride, stride, 1))
    if (kh, kw) == (stride, stride) and not border:
        CONV_PATHS["nonoverlap"] += 1
        tiles = x[:, : ho * kh, : wo * kw, :].reshape(b, ho, kh, wo, kw, cin)
        return tiles.transpose(0, 1, 3, 2, 4, 5).reshape(b, ho, wo, kh * kw * cin)
    k = kh * kw * cin
    if kh * kw > VIEWS_PER_TILE * -(-k // LANES):
        CONV_PATHS["patch_op"] += 1
        one_hot = jnp.eye(k, dtype=x.dtype).reshape(kh, kw, cin, k)
        return jax.lax.conv_general_dilated(
            x, one_hot, (stride, stride), padding, dimension_numbers=("NHWC", "HWIO", "NHWC")
        )
    CONV_PATHS["slices"] += 1
    # At least one zero row and column on every side: XLA turns a one-sided
    # concatenation with zeros into a pad of its own, which it does not fuse.
    t, l = max(top, 1), max(left, 1)
    rows, cols = t + h + max(bottom, 1), l + w + max(right, 1)
    dtype = x.dtype

    def zeros(*shape):
        return jnp.zeros(shape, dtype)

    x = jnp.concatenate([zeros(b, t, w, cin), x, zeros(b, rows - t - h, w, cin)], axis=1)
    x = jnp.concatenate([zeros(b, rows, l, cin), x, zeros(b, rows, cols - l - w, cin)], axis=2)
    views = [
        jax.lax.slice(
            x,
            (0, t - top + i, l - left + j, 0),
            (b, t - top + i + stride * (ho - 1) + 1, l - left + j + stride * (wo - 1) + 1, cin),
            (1, stride, stride, 1),
        )
        for i in range(kh)
        for j in range(kw)
    ]
    return jnp.concatenate(views, axis=-1)


def bn_specs(ch):
    return {
        "scale": spec((ch,), ("channels",), init="ones"),
        "bias": spec((ch,), ("channels",), init="zeros"),
    }


def bn_state_specs(ch):
    return {
        "mean": spec((ch,), ("channels",), init="zeros"),
        "var": spec((ch,), ("channels",), init="ones"),
    }


def batchnorm(p, s, x, train: bool, eps=1e-5):
    """Returns (y, new_state).  In SPMD training the jnp.mean over the global
    batch/space dims is what produces the cross-device all-reduce (sync-BN)."""
    if train:
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=(0, 1, 2))
        var = jnp.var(x32, axis=(0, 1, 2))
        new_s = {
            "mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
            "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var,
        }
    else:
        mean, var = s["mean"], s["var"]
        new_s = s
    inv = jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    y = (x.astype(jnp.float32) - mean) * inv + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype), new_s


def maxpool(x, window=3, stride=2, padding="SAME"):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1), (1, stride, stride, 1), padding
    )


# ---------------------------------------------------------------------------
# ResNet-50
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    name: str
    depths: tuple[int, ...] = (3, 4, 6, 3)
    width: int = 64
    n_classes: int = 1000
    expansion: int = 4


def _bottleneck_specs(cin, cmid, cout, stride):
    s = {
        "conv1": conv_spec(1, 1, cin, cmid),
        "bn1": bn_specs(cmid),
        "conv2": conv_spec(3, 3, cmid, cmid),
        "bn2": bn_specs(cmid),
        "conv3": conv_spec(1, 1, cmid, cout),
        "bn3": bn_specs(cout),
    }
    if stride != 1 or cin != cout:
        s["proj"] = conv_spec(1, 1, cin, cout)
        s["bn_proj"] = bn_specs(cout)
    return s


def _bottleneck_state(cin, cmid, cout, stride):
    s = {"bn1": bn_state_specs(cmid), "bn2": bn_state_specs(cmid), "bn3": bn_state_specs(cout)}
    if stride != 1 or cin != cout:
        s["bn_proj"] = bn_state_specs(cout)
    return s


def resnet_abstract(c: ResNetConfig) -> tuple[dict, dict]:
    params: dict = {"stem": {"conv": conv_spec(7, 7, 3, c.width), "bn": bn_specs(c.width)}}
    state: dict = {"stem": {"bn": bn_state_specs(c.width)}}
    cin = c.width
    for i, depth in enumerate(c.depths):
        cmid = c.width * (2**i)
        cout = cmid * c.expansion
        stride = 1 if i == 0 else 2
        params[f"stage{i}_first"] = _bottleneck_specs(cin, cmid, cout, stride)
        state[f"stage{i}_first"] = _bottleneck_state(cin, cmid, cout, stride)
        if depth > 1:
            params[f"stage{i}_rest"] = _stack(_bottleneck_specs(cout, cmid, cout, 1), depth - 1)
            state[f"stage{i}_rest"] = _stack(_bottleneck_state(cout, cmid, cout, 1), depth - 1)
        cin = cout
    params["head"] = {
        "w": spec((cin, c.n_classes), ("embed", "vocab")),
        "b": spec((c.n_classes,), ("vocab",), init="zeros"),
    }
    return params, state


def _bottleneck(p, s, x, stride, train):
    ns = {}
    h, ns["bn1"] = batchnorm(p["bn1"], s["bn1"], conv(p["conv1"], x), train)
    h = jax.nn.relu(h)
    h, ns["bn2"] = batchnorm(p["bn2"], s["bn2"], conv(p["conv2"], h, stride=stride), train)
    h = jax.nn.relu(h)
    h, ns["bn3"] = batchnorm(p["bn3"], s["bn3"], conv(p["conv3"], h), train)
    if "proj" in p:
        sc, ns["bn_proj"] = batchnorm(p["bn_proj"], s["bn_proj"], conv(p["proj"], x, stride=stride), train)
    else:
        sc = x
    return jax.nn.relu(h + sc), ns


def resnet_forward(c: ResNetConfig, params, state, images, *, train: bool = False):
    x = images.astype(jnp.bfloat16)
    ns: dict = {"stem": {}}
    x = conv(params["stem"]["conv"], x, stride=2)
    x, ns["stem"]["bn"] = batchnorm(params["stem"]["bn"], state["stem"]["bn"], x, train)
    x = maxpool(jax.nn.relu(x))
    for i, depth in enumerate(c.depths):
        stride = 1 if i == 0 else 2
        x, ns[f"stage{i}_first"] = _bottleneck(
            params[f"stage{i}_first"], state[f"stage{i}_first"], x, stride, train
        )
        if depth > 1:

            def body(x, ps):
                p, s = ps
                y, s2 = _bottleneck(p, s, x, 1, train)
                return y, s2

            x, ns[f"stage{i}_rest"] = jax.lax.scan(
                body, x, (params[f"stage{i}_rest"], state[f"stage{i}_rest"])
            )
        x = shard(x, "batch", None, None, None)
    h = x.mean(axis=(1, 2))
    logits = matmul(h, params["head"]["w"].astype(h.dtype)) + params["head"]["b"].astype(h.dtype)
    return logits.astype(jnp.float32), ns


# ---------------------------------------------------------------------------
# EfficientNet (B0 base scaled by width/depth multipliers; B7 = 2.0 / 3.1)
# ---------------------------------------------------------------------------

EFFNET_B0_BLOCKS = (  # (expand, channels, repeats, stride, kernel)
    (1, 16, 1, 1, 3),
    (6, 24, 2, 2, 3),
    (6, 40, 2, 2, 5),
    (6, 80, 3, 2, 3),
    (6, 112, 3, 1, 5),
    (6, 192, 4, 2, 5),
    (6, 320, 1, 1, 3),
)


def _round_filters(ch: float, mult: float, divisor: int = 8) -> int:
    ch *= mult
    new = max(divisor, int(ch + divisor / 2) // divisor * divisor)
    if new < 0.9 * ch:
        new += divisor
    return int(new)


@dataclasses.dataclass(frozen=True)
class EfficientNetConfig:
    name: str
    width_mult: float = 1.0
    depth_mult: float = 1.0
    n_classes: int = 1000
    se_ratio: float = 0.25

    def stages(self):
        out = []
        for expand, ch, reps, stride, k in EFFNET_B0_BLOCKS:
            out.append(
                (
                    expand,
                    _round_filters(ch, self.width_mult),
                    int(math.ceil(reps * self.depth_mult)),
                    stride,
                    k,
                )
            )
        return out

    @property
    def stem_ch(self) -> int:
        return _round_filters(32, self.width_mult)

    @property
    def head_ch(self) -> int:
        return _round_filters(1280, self.width_mult)


def _mbconv_specs(cin, cout, expand, k, se_ratio):
    cmid = cin * expand
    s: dict = {}
    if expand != 1:
        s["expand"] = conv_spec(1, 1, cin, cmid)
        s["bn_e"] = bn_specs(cmid)
    s["dw"] = spec((k, k, 1, cmid), (None, None, None, "conv_out"), init="conv")
    s["bn_d"] = bn_specs(cmid)
    cse = max(1, int(cin * se_ratio))
    s["se_r"] = {"w": conv_spec(1, 1, cmid, cse), "b": spec((cse,), (None,), init="zeros")}
    s["se_e"] = {"w": conv_spec(1, 1, cse, cmid), "b": spec((cmid,), (None,), init="zeros")}
    s["project"] = conv_spec(1, 1, cmid, cout)
    s["bn_p"] = bn_specs(cout)
    return s


def _mbconv_state(cin, cout, expand):
    cmid = cin * expand
    s: dict = {"bn_d": bn_state_specs(cmid), "bn_p": bn_state_specs(cout)}
    if expand != 1:
        s["bn_e"] = bn_state_specs(cmid)
    return s


def effnet_abstract(c: EfficientNetConfig) -> tuple[dict, dict]:
    params: dict = {"stem": {"conv": conv_spec(3, 3, 3, c.stem_ch), "bn": bn_specs(c.stem_ch)}}
    state: dict = {"stem": {"bn": bn_state_specs(c.stem_ch)}}
    cin = c.stem_ch
    for i, (expand, cout, reps, stride, k) in enumerate(c.stages()):
        params[f"stage{i}_first"] = _mbconv_specs(cin, cout, expand, k, c.se_ratio)
        state[f"stage{i}_first"] = _mbconv_state(cin, cout, expand)
        if reps > 1:
            params[f"stage{i}_rest"] = _stack(_mbconv_specs(cout, cout, expand, k, c.se_ratio), reps - 1)
            state[f"stage{i}_rest"] = _stack(_mbconv_state(cout, cout, expand), reps - 1)
        cin = cout
    params["head_conv"] = {"conv": conv_spec(1, 1, cin, c.head_ch), "bn": bn_specs(c.head_ch)}
    state["head_conv"] = {"bn": bn_state_specs(c.head_ch)}
    params["head"] = {
        "w": spec((c.head_ch, c.n_classes), ("embed", "vocab")),
        "b": spec((c.n_classes,), ("vocab",), init="zeros"),
    }
    return params, state


def _mbconv(p, s, x, stride, k, train):
    ns: dict = {}
    h = x
    if "expand" in p:
        h, ns["bn_e"] = batchnorm(p["bn_e"], s["bn_e"], conv(p["expand"], h), train)
        h = jax.nn.silu(h)
    cmid = h.shape[-1]
    h2 = conv(p["dw"], h, stride=stride, groups=cmid)
    h, ns["bn_d"] = batchnorm(p["bn_d"], s["bn_d"], h2, train)
    h = jax.nn.silu(h)
    # Squeeze-and-excitation.
    z = h.mean(axis=(1, 2), keepdims=True)
    z = jax.nn.silu(conv(p["se_r"]["w"], z) + p["se_r"]["b"].astype(z.dtype))
    z = jax.nn.sigmoid(conv(p["se_e"]["w"], z) + p["se_e"]["b"].astype(z.dtype))
    h = h * z
    h, ns["bn_p"] = batchnorm(p["bn_p"], s["bn_p"], conv(p["project"], h), train)
    if stride == 1 and x.shape[-1] == h.shape[-1]:
        h = h + x
    return h, ns


def effnet_forward(c: EfficientNetConfig, params, state, images, *, train: bool = False):
    x = images.astype(jnp.bfloat16)
    ns: dict = {"stem": {}, "head_conv": {}}
    x = conv(params["stem"]["conv"], x, stride=2)
    x, ns["stem"]["bn"] = batchnorm(params["stem"]["bn"], state["stem"]["bn"], x, train)
    x = jax.nn.silu(x)
    for i, (expand, cout, reps, stride, k) in enumerate(c.stages()):
        x, ns[f"stage{i}_first"] = _mbconv(
            params[f"stage{i}_first"], state[f"stage{i}_first"], x, stride, k, train
        )
        if reps > 1:

            def body(x, ps, k=k):
                p, s = ps
                y, s2 = _mbconv(p, s, x, 1, k, train)
                return y, s2

            x, ns[f"stage{i}_rest"] = jax.lax.scan(
                body, x, (params[f"stage{i}_rest"], state[f"stage{i}_rest"])
            )
        x = shard(x, "batch", None, None, None)
    x = conv(params["head_conv"]["conv"], x)
    x, ns["head_conv"]["bn"] = batchnorm(params["head_conv"]["bn"], state["head_conv"]["bn"], x, train)
    h = jax.nn.silu(x).mean(axis=(1, 2))
    logits = matmul(h, params["head"]["w"].astype(h.dtype)) + params["head"]["b"].astype(h.dtype)
    return logits.astype(jnp.float32), ns


# ---------------------------------------------------------------------------
# SqueezeNet v1.1 (the paper's compact model)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SqueezeNetConfig:
    name: str = "squeezenet"
    n_classes: int = 1000


FIRE_CFG = (  # (squeeze, expand) after each pool stage
    ((16, 64), (16, 64)),
    ((32, 128), (32, 128)),
    ((48, 192), (48, 192), (64, 256), (64, 256)),
)


def _fire_specs(cin, sq, ex):
    return {
        "squeeze": {"w": conv_spec(1, 1, cin, sq), "b": spec((sq,), (None,), init="zeros")},
        "e1": {"w": conv_spec(1, 1, sq, ex), "b": spec((ex,), (None,), init="zeros")},
        "e3": {"w": conv_spec(3, 3, sq, ex), "b": spec((ex,), (None,), init="zeros")},
    }


def squeezenet_abstract(c: SqueezeNetConfig) -> tuple[dict, dict]:
    params: dict = {
        "stem": {"w": conv_spec(3, 3, 3, 64), "b": spec((64,), (None,), init="zeros")}
    }
    cin = 64
    for gi, group in enumerate(FIRE_CFG):
        for fi, (sq, ex) in enumerate(group):
            params[f"fire{gi}_{fi}"] = _fire_specs(cin, sq, ex)
            cin = 2 * ex
    params["classifier"] = {
        "w": conv_spec(1, 1, cin, c.n_classes),
        "b": spec((c.n_classes,), (None,), init="zeros"),
    }
    return params, {}


def _fire(p, x):
    s = jax.nn.relu(conv(p["squeeze"]["w"], x) + p["squeeze"]["b"].astype(x.dtype))
    e1 = conv(p["e1"]["w"], s) + p["e1"]["b"].astype(x.dtype)
    e3 = conv(p["e3"]["w"], s) + p["e3"]["b"].astype(x.dtype)
    return jax.nn.relu(jnp.concatenate([e1, e3], axis=-1))


def squeezenet_forward(c: SqueezeNetConfig, params, state, images, *, train: bool = False):
    x = images.astype(jnp.bfloat16)
    x = jax.nn.relu(conv(params["stem"]["w"], x, stride=2) + params["stem"]["b"].astype(x.dtype))
    for gi, group in enumerate(FIRE_CFG):
        x = maxpool(x)
        for fi, _ in enumerate(group):
            x = _fire(params[f"fire{gi}_{fi}"], x)
    x = conv(params["classifier"]["w"], x) + params["classifier"]["b"].astype(x.dtype)
    logits = jax.nn.relu(x).mean(axis=(1, 2))
    return logits.astype(jnp.float32), {}
