"""Swin-B's program against the benchmark's plain reference
(``configs/swin_b.py``) on seeded weights at the SMOKE size (32x32 frames,
window 4, depths 2-2, widths 32-64, heads 2-4, 10 classes: stage 0's 8x8
token map shifts on its odd block, stage 1's one window does not), with a
fault planted in the program one at a time; and the GEMMs and parameters of
program and reference at the published widths."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tinycells import config

from harness import check, spec, work

# Worst relative L2 of a frame's logits against the reference, 8 frames on
# each of 3 seeds.  The bf16 edge forward reads 0.008-0.012 here: bf16
# activations and weights through 4 blocks and 2 norms a block.  The
# float8_e4m3fn control (the reference with every product's operands in
# fp8) reads 0.067-0.10.
EDGE_TOL = 0.03
# The int8 NPU forward reads 0.014-0.022: the reference with int8 operands
# in every product reads 0.016-0.027, and bf16 around the kernel adds
# little.  The int4 control reads 0.25-0.38.
NPU_TOL = 0.06
SEEDS = (0, 1, 2)

# Swin SMOKE at 32x32: patch embedding 8x8 tokens of 4*4*3; stage 0 (32
# wide) at 64 tokens, merged to 16 tokens of 4*32 -> 64; stage 1 (64 wide);
# the head on the pooled 64 features.
SWIN_SMOKE_32 = (
    [(64, 48, 32)]
    + [(64, 32, 96), (64, 32, 32), (64, 32, 128), (64, 128, 32)] * 2
    + [(16, 128, 64)]
    + [(16, 64, 192), (16, 64, 64), (16, 64, 256), (16, 256, 64)] * 2
    + [(1, 64, 10)]
)


def _forward(arch):
    from repro.arch import classifier_forward

    return lambda p, x: classifier_forward(arch, p, {}, x, train=False)[0]


@pytest.fixture(scope="module")
def smoke():
    """The SMOKE configuration, its program architecture, and per seed the
    weights, 8 frames and the reference's logits."""
    from repro import configs

    cfg, ref = config("swin_b")
    arch = configs.get(cfg["program_arch"], smoke=True)
    cases = []
    for seed in SEEDS:
        params, state = ref.make_weights(jax.random.key(seed), cfg)
        x = np.random.default_rng(seed).standard_normal((8, 32, 32, 3), dtype=np.float32)
        cases.append((params, x, np.asarray(ref.reference_logits(cfg, params, state, x))))
    return cfg, ref, arch, cases


def _readings(arch, cases, *, path):
    """Worst relative L2 of each case on one path, traced anew (so that a
    fault planted in the program is in the trace)."""
    from repro import quant

    fwd = _forward(arch)
    if path == "npu":
        prog = jax.jit(quant.npu_forward(fwd))
        prep = jax.jit(quant.fake_quant_tree)
    else:
        prog, prep = jax.jit(lambda p, x: fwd(p, x)), (lambda p: p)
    return [check.worst_rel_l2(np.asarray(prog(prep(p), x)), want) for p, x, want in cases]


@pytest.mark.parametrize("path,tol", [("edge", EDGE_TOL), ("npu", NPU_TOL)])
def test_program_matches_reference(smoke, path, tol):
    _, _, arch, cases = smoke
    readings = _readings(arch, cases, path=path)
    assert max(readings) < tol, readings


@pytest.mark.parametrize("path,tol", [("edge", EDGE_TOL), ("npu", NPU_TOL)])
def test_lower_precision_control_fails(smoke, path, tol):
    """The reference one precision step below the path fails its tolerance
    on every seed: the tolerance is tight enough to see the precision."""
    cfg, ref, _, cases = smoke
    lower = cfg["controls"][path]
    readings = [check.worst_rel_l2(np.asarray(ref.reference_logits(cfg, p, {}, x, lower=lower)),
                                   want) for p, x, want in cases]
    assert min(readings) > tol, readings


def _swapped_merge(p, x, H, W):
    """Patch merging with the 2x2 neighbours in (0,0), (0,1), (1,0), (1,1)
    order, not the source's."""
    from repro.models import vision

    B, _, C = x.shape
    xs = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 2, 4, 5)
    return vision._linear(p["reduction"], vision._norm(p["norm"], xs.reshape(B, -1, 4 * C)))


def _plant(monkeypatch, fault):
    from repro.models import vision

    if fault == "no_shift":
        attend = vision._window_attention
        monkeypatch.setattr(vision, "_window_attention",
                            lambda p, x, H, W, w, heads, shift: attend(p, x, H, W, w, heads, 0))
    elif fault == "no_mask":
        mask = vision._shift_mask
        monkeypatch.setattr(vision, "_shift_mask",
                            lambda H, W, w, shift: np.ones_like(mask(H, W, w, shift)))
    elif fault == "merge_order":
        monkeypatch.setattr(vision, "_patch_merge", _swapped_merge)


@pytest.mark.parametrize("fault", ["no_bias_table", "no_shift", "no_mask", "merge_order"])
@pytest.mark.parametrize("path,tol", [("edge", EDGE_TOL), ("npu", NPU_TOL)])
def test_planted_fault_fails(smoke, monkeypatch, fault, path, tol):
    """Each fault moves every seed's reading over the path's tolerance: the
    weights make every term of the block matter (``make_weights``)."""
    _, _, arch, cases = smoke
    _plant(monkeypatch, fault)
    if fault == "no_bias_table":
        drop = lambda k, a: jnp.zeros_like(a) if "rel_bias" in jax.tree_util.keystr(k) else a  # noqa: E731
        cases = [(jax.tree_util.tree_map_with_path(drop, p), x, want) for p, x, want in cases]
    readings = _readings(arch, cases, path=path)
    assert min(readings) > tol, readings


def test_npu_variant_keeps_the_bias_table_exact(smoke):
    """The NPU variant quantizes every Linear weight per output column and
    leaves the relative-position-bias table, an addend of the scores, as it
    was."""
    from repro import quant

    _, _, _, cases = smoke
    params = cases[0][0]
    q = quant.fake_quant_tree(params)
    attn, qattn = params["stage0"]["blocks"][1]["attn"], q["stage0"]["blocks"][1]["attn"]
    np.testing.assert_array_equal(qattn["rel_bias"], attn["rel_bias"])
    w, qw = np.asarray(attn["qkv"]["w"]), np.asarray(qattn["qkv"]["w"])
    assert not np.array_equal(qw, w)
    steps = np.abs(w).max(axis=0) / 127
    np.testing.assert_allclose(qw / steps, np.round(qw / steps), atol=1e-3)


def test_smoke_gemms_by_hand():
    cfg, ref = config("swin_b")
    assert ref.gemms(cfg) == SWIN_SMOKE_32


def _program_gemms(cfg, ref, smoke):
    """``(M, K, N)`` of each ``common.matmul`` call of the program's forward
    on one frame, traced abstractly (nothing is computed)."""
    from repro import configs
    from repro.models.common import matmul_backend

    arch = configs.get(cfg["program_arch"], smoke=smoke)
    params, _ = jax.eval_shape(lambda k: ref.make_weights(k, cfg), jax.random.key(0))
    seen = []

    def record(x, w):
        seen.append((x.shape[0], x.shape[1], w.shape[1]))
        return x @ w

    res = cfg["input_res"]
    with matmul_backend(record):
        jax.eval_shape(_forward(arch), params, jax.ShapeDtypeStruct((1, res, res, 3), jnp.float32))
    return seen


def test_gemms_match_the_programs_matmuls_smoke():
    cfg, ref = config("swin_b")
    assert _program_gemms(cfg, ref, smoke=True) == ref.gemms(cfg)


def test_gemms_match_the_programs_matmuls_published():
    """101 Linear layers a frame at 224x224, 30.25 G int8 operations, every
    one a ``common.matmul`` call in the reference's order."""
    cfg, ref = spec.config("swin_b")
    gemms = ref.gemms(cfg)
    assert len(gemms) == 101
    assert work.total_ops(gemms) == 30_252_154_880
    assert _program_gemms(cfg, ref, smoke=False) == gemms


def test_parameter_shapes_published():
    """The program's parameters are the reference's, leaf for leaf, and
    count the source's 87,768,224."""
    from repro import configs
    from repro.arch import abstract_params
    from repro.models.common import ParamSpec

    cfg, ref = spec.config("swin_b")
    params, state = abstract_params(configs.get(cfg["program_arch"]))
    ours = [s.shape for s in jax.tree.leaves(params, is_leaf=lambda x: isinstance(x, ParamSpec))]
    theirs = jax.tree.leaves(ref.param_shapes(cfg), is_leaf=ref.is_shape)
    assert ours == theirs
    assert sum(math.prod(s) for s in theirs) == 87_768_224
    assert state == {}
