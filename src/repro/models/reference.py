"""Plain float32 forwards of ResNet and SqueezeNet, to check served logits.

The served forwards (:mod:`.convnets`) compute in bfloat16: they cast the
frames to bf16 and every weight to the activations' dtype.  These forwards
take the same parameter trees and compute every layer in ``dtype``
(float32 by default) with each convolution and matmul at
``Precision.HIGHEST``.  They share no code with ``convnets`` — no dtype
policy, no matmul backend, no scan over stacked blocks — so a fault in the
served program cannot cancel against the same fault here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BN_EPS = 1e-5


def _conv(w, x, stride=1):
    return lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST,
    )


def _bn(p, s, x):
    return (x - s["mean"]) / jnp.sqrt(s["var"] + BN_EPS) * p["scale"] + p["bias"]


def _maxpool(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME")


def _bottleneck(p, s, x, stride):
    h = jax.nn.relu(_bn(p["bn1"], s["bn1"], _conv(p["conv1"], x)))
    h = jax.nn.relu(_bn(p["bn2"], s["bn2"], _conv(p["conv2"], h, stride)))
    h = _bn(p["bn3"], s["bn3"], _conv(p["conv3"], h))
    if "proj" in p:
        x = _bn(p["bn_proj"], s["bn_proj"], _conv(p["proj"], x, stride))
    return jax.nn.relu(h + x)


def resnet_logits(cfg, params, state, images):
    x = _conv(params["stem"]["conv"], images, 2)
    x = _maxpool(jax.nn.relu(_bn(params["stem"]["bn"], state["stem"]["bn"], x)))
    for i, depth in enumerate(cfg.depths):
        stride = 1 if i == 0 else 2
        x = _bottleneck(params[f"stage{i}_first"], state[f"stage{i}_first"], x, stride)
        for r in range(depth - 1):  # the stacked blocks, one at a time
            p, s = jax.tree.map(lambda t, r=r: t[r], (params[f"stage{i}_rest"], state[f"stage{i}_rest"]))
            x = _bottleneck(p, s, x, 1)
    h = x.mean(axis=(1, 2))
    return jnp.dot(h, params["head"]["w"], precision=HIGHEST) + params["head"]["b"]


# SqueezeNet v1.1: fire modules after each of its three max-pools
_FIRES_PER_POOL = (2, 2, 4)


def squeezenet_logits(cfg, params, state, images):
    x = jax.nn.relu(_conv(params["stem"]["w"], images, 2) + params["stem"]["b"])
    for gi, n_fires in enumerate(_FIRES_PER_POOL):
        x = _maxpool(x)
        for fi in range(n_fires):
            p = params[f"fire{gi}_{fi}"]
            s = jax.nn.relu(_conv(p["squeeze"]["w"], x) + p["squeeze"]["b"])
            x = jax.nn.relu(jnp.concatenate(
                [_conv(p["e1"]["w"], s) + p["e1"]["b"], _conv(p["e3"]["w"], s) + p["e3"]["b"]],
                axis=-1,
            ))
    x = _conv(params["classifier"]["w"], x) + params["classifier"]["b"]
    return jax.nn.relu(x).mean(axis=(1, 2))


_FORWARDS = {"resnet": resnet_logits, "squeezenet": squeezenet_logits}


def reference_logits(arch, params, state, images, *, dtype=jnp.float32):
    """Logits of ``arch`` (a ResNet or SqueezeNet ``Arch``) with parameters,
    state and images cast to ``dtype`` and every layer computed in it."""
    if arch.family not in _FORWARDS:
        raise ValueError(f"no reference forward for family {arch.family!r}")
    cast = lambda t: jax.tree.map(lambda a: jnp.asarray(a, dtype), t)  # noqa: E731
    return _FORWARDS[arch.family](arch.cfg, cast(params), cast(state), jnp.asarray(images, dtype))
