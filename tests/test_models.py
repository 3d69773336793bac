"""Per-architecture smoke tests: each served classifier (reduced config)
runs an eval forward and a training gradient step on CPU with the right
output shapes and finite values, plus the registry's error path."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.arch import abstract_params, classifier_forward
from repro.models.common import init_tree


def _all_finite(tree) -> bool:
    return all(
        bool(jnp.all(jnp.isfinite(x)))
        for x in jax.tree.leaves(tree)
        if jnp.issubdtype(x.dtype, jnp.floating)
    )


@pytest.mark.parametrize("arch_name", configs.ALL)
def test_arch_smoke(arch_name):
    """An eval forward, then one ``jax.grad`` step of the training-mode
    cross-entropy through ``classifier_forward``."""
    a = configs.get(arch_name, smoke=True)
    specs, state_specs = abstract_params(a)
    params, state = jax.jit(lambda k0, k1: (init_tree(k0, specs), init_tree(k1, state_specs)))(
        jax.random.key(0), jax.random.key(1)
    )
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3))
    y = jnp.asarray([1, 3])

    logits, same = jax.jit(lambda p, s, x: classifier_forward(a, p, s, x, train=False))(
        params, state, x
    )
    assert logits.shape == (2, a.cfg.n_classes) and logits.dtype == jnp.float32
    assert _all_finite(logits), f"{arch_name}: non-finite eval logits"
    assert jax.tree.structure(same) == jax.tree.structure(state)

    def loss(p, s):
        out, new_state = classifier_forward(a, p, s, x, train=True)
        logp = jax.nn.log_softmax(out, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)), new_state

    (value, new_state), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params, state)
    assert float(value) > 0
    assert jax.tree.structure(grads) == jax.tree.structure(params)
    assert jax.tree.structure(new_state) == jax.tree.structure(state)
    assert _all_finite((grads, new_state)), f"{arch_name}: non-finite training step"
    assert any(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))


def test_configs_get_unknown_name_lists_the_served_configs():
    with pytest.raises(KeyError) as err:
        configs.get("qwen3-0.6b")
    for name in ("resnet-50", "squeezenet", "swin-b"):
        assert name in str(err.value)


@pytest.mark.slow
def test_quant_variant_close_but_not_equal():
    from repro import quant

    a = configs.get("resnet-50", smoke=True)
    specs, st_specs = abstract_params(a)
    params = init_tree(jax.random.key(0), specs)
    state = init_tree(jax.random.key(1), st_specs)
    qparams, stats = quant.npu_variant(params)
    assert stats.leaves_quantized > 0
    assert 0 < stats.mean_rel_err < 0.05  # real but small int8 error
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3))
    lo_fp, _ = classifier_forward(a, params, state, x, train=False)
    lo_q, _ = classifier_forward(a, qparams, state, x, train=False)
    assert not bool(jnp.allclose(lo_fp, lo_q))  # quantization does something
    rel = float(jnp.linalg.norm(lo_fp - lo_q) / jnp.maximum(jnp.linalg.norm(lo_fp), 1e-9))
    assert rel < 0.5
