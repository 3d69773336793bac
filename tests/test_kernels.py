"""Per-kernel validation: Pallas kernel (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests on the
quantization scheme."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.kernels.flash_attention import kernel as fk
from repro.kernels.flash_attention import ref as fr
from repro.kernels.npu_matmul import ops as nops
from repro.kernels.npu_matmul import ref as nref

# Example counts come from the shared profiles in conftest.py
# (HYPOTHESIS_PROFILE=ci|nightly); settings() snapshots the active profile.
SETTINGS = settings()


@pytest.mark.parametrize(
    "m,k,n",
    [(128, 512, 128), (256, 1024, 384), (64, 300, 100), (8, 128, 128), (1, 64, 1), (130, 70, 9)],
)
def test_int8_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m * 1000 + n)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    ref = nref.npu_matmul_ref(x, w)
    out = nops.npu_matmul(x, w, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_dtypes(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 256)), dtype)
    w = jnp.asarray(rng.normal(size=(256, 64)), dtype)
    out = nops.npu_matmul(x, w, interpret=True)
    # under jit, as the kernel's body is: XLA turns ``amax / 127`` into a
    # multiply by the reciprocal, and bf16 inputs put values on exact
    # rounding ties, where that last bit of the scale decides the int8 value
    ref = jax.jit(nref.npu_matmul_ref)(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=1e-4, atol=1e-3
    )


def _quantized_ref(x, w):
    """The int8 operands and scales ``ref`` gives under jit, their exact
    int32 product, and the kernel's epilogue on it."""
    (xq, xs), (wq, ws) = jax.jit(
        lambda x, w: (nref.quantize_rowwise(x), nref.quantize_colwise(w))
    )(x, w)
    acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32))
    return xq, wq, acc.astype(jnp.float32) * (xs[:, None] * ws[None, :])


# K x N of the configs' GEMMs (the ResNet-50 stem, a stage-1 and a stage-4
# 3x3 conv, the SqueezeNet fire squeeze, the head) at one frame, and M x N
# off every block multiple (two row and two column blocks, both partial).
FUSED_SHAPES = [(1, 147, 64), (1, 576, 64), (1, 4608, 512), (1, 16, 64), (1, 2048, 1000),
                (1300, 70, 300)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n", FUSED_SHAPES)
def test_fused_quantize_matches_ref(m, k, n, dtype):
    """One ``quantized_matmul`` call quantizes both operands in VMEM: the
    output is bit for bit ``ref``'s int8 operands through an exact int32
    GEMM and the f32 epilogue, and within the two-pass path's tolerances of
    ``npu_matmul_ref``."""
    assert nops.fused_blocks(m, k, n, jnp.dtype(dtype).itemsize) is not None
    rng = np.random.default_rng(m * 31 + k + n)
    x = jnp.asarray(rng.normal(size=(m, k)), dtype)
    w = jnp.asarray(rng.normal(size=(k, n)), dtype)
    nops.PATHS.clear()
    out = nops.npu_matmul(x, w, interpret=True)
    assert nops.PATHS == {"fused": 1}
    assert out.shape == (m, n) and out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(_quantized_ref(x, w)[2]))
    tol = dict(rtol=1e-5, atol=1e-4) if dtype == jnp.float32 else dict(rtol=1e-4, atol=1e-3)
    ref = jax.jit(nref.npu_matmul_ref)(x, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **tol)


@pytest.mark.parametrize("axis", [0, 1])
def test_fused_quantize_int8_values_exact(axis):
    """The kernel's in-VMEM quantization gives ``ref``'s int8 values and
    scales, on bf16 inputs whose ratios land on rounding ties."""
    from jax.experimental import pallas as pl

    from repro.kernels.npu_matmul import kernel as nk

    rng = np.random.default_rng(axis)
    v = jnp.asarray(rng.integers(-254, 255, size=(48, 200)) / 8.0, jnp.bfloat16)

    def body(v_ref, q_ref, s_ref):
        q_ref[...], s_ref[...] = nk._quantize(v_ref[...].astype(jnp.float32), axis)

    s_shape = (48, 1) if axis == 1 else (1, 200)
    q, s = pl.pallas_call(
        body, interpret=True,
        out_shape=[jax.ShapeDtypeStruct(v.shape, jnp.int8),
                   jax.ShapeDtypeStruct(s_shape, jnp.float32)],
    )(v)
    quantize = nref.quantize_rowwise if axis == 1 else nref.quantize_colwise
    rq, rs = jax.jit(quantize)(v)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(rq))
    np.testing.assert_array_equal(np.asarray(s).reshape(-1), np.asarray(rs))


def test_fused_zero_row_and_column():
    """An all-zero activation row and weight column take scale 1 and give
    exact zeros, in partial edge blocks and beside finite neighbours."""
    rng = np.random.default_rng(3)
    x = np.asarray(rng.normal(size=(1100, 96)), np.float32)
    w = np.asarray(rng.normal(size=(96, 270)), np.float32)
    x[1090] = 0.0
    w[:, 265] = 0.0
    out = np.asarray(nops.npu_matmul(jnp.asarray(x), jnp.asarray(w), interpret=True))
    assert np.all(out[1090] == 0.0) and np.all(out[:, 265] == 0.0)
    assert np.all(np.isfinite(out))
    xq, wq, expect = _quantized_ref(jnp.asarray(x), jnp.asarray(w))
    assert not np.any(np.asarray(xq)[1090]) and not np.any(np.asarray(wq)[:, 265])
    np.testing.assert_array_equal(out, np.asarray(expect))


def test_fused_falls_back_when_blocks_exceed_budget():
    """A K whose smallest full-K blocks exceed the VMEM budget keeps the
    two-pass path (XLA quantizes, ``npu_matmul_prequant`` multiplies)."""
    k = 1 << 20
    assert nops._fused_vmem_bytes(1, k, 128, 2) > nops.VMEM_BUDGET
    assert nops.fused_blocks(1, k, 128, 2) is None
    assert nops.fused_blocks(1, 4608, 512, 2) is not None
    nops.PATHS.clear()
    out = jax.eval_shape(
        lambda x, w: nops.npu_matmul(x, w, interpret=True),
        jax.ShapeDtypeStruct((1, k), jnp.bfloat16), jax.ShapeDtypeStruct((k, 128), jnp.bfloat16),
    )
    assert out.shape == (1, 128)
    assert nops.PATHS == {"two_pass": 1}


@pytest.mark.parametrize(
    "m,k,n",
    [
        # Shapes where M, K, N are each NOT multiples of the default
        # (128, 512, 128) blocks — exercises the adaptive block sizing +
        # padding path in npu_matmul_prequant end to end.
        (130, 700, 129),
        (3, 33, 65),
        (257, 513, 127),
    ],
)
def test_int8_prequant_non_block_multiple_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    xq, xs = nref.quantize_rowwise(x)
    wq, ws = nref.quantize_colwise(w)
    ref = nref.int8_matmul_ref(xq, wq, xs, ws)
    out = nops.npu_matmul_prequant(xq, xs, wq, ws, interpret=True)
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-4)


def test_int8_prequant_single_row_golden():
    """M=1 — the serving loop's per-frame head GEMM.  The adaptive block
    size (bm=1 instead of padding M to 128) must not change the numbers:
    golden-compared against the pure-jnp int8 reference."""
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.normal(size=(1, 96)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(96, 10)), jnp.float32)
    xq, xs = nref.quantize_rowwise(x)
    wq, ws = nref.quantize_colwise(w)
    ref = nref.int8_matmul_ref(xq, wq, xs, ws)
    out = nops.npu_matmul_prequant(xq, xs, wq, ws, interpret=True)
    assert out.shape == (1, 10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("blocks", ["adaptive", "mosaic_minima"])
@pytest.mark.parametrize("k,n", [(147, 64), (300, 200)])
@pytest.mark.parametrize("m", [1, 33, 128 + 5])
def test_int8_scale_layout_golden(m, k, n, blocks):
    """The scales ride as [M, 1] / [1, N] blocks.  Every row and column gets
    its own scale, so a transposed or misaligned scale block shows; K and N
    are on no block multiple.  ``mosaic_minima`` runs the tiles the TPU path
    uses (bm >= 32, bk >= 128, bn >= 128) through the interpreter."""
    from repro.kernels.npu_matmul import kernel as nk

    rng = np.random.default_rng(m * 7 + k + n)
    xq = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    xs = jnp.asarray(rng.uniform(0.5, 2.0, size=m), jnp.float32)
    ws = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    ref = nref.int8_matmul_ref(xq, wq, xs, ws)
    if blocks == "adaptive":
        out = nops.npu_matmul_prequant(xq, xs, wq, ws, interpret=True)
    else:
        bm, bk, bn = 32, 128, 128
        out = nk.int8_matmul(
            nops._pad_to(nops._pad_to(xq, bm, 0), bk, 1),
            nops._pad_to(nops._pad_to(wq, bk, 0), bn, 1),
            nops._pad_to(xs, bm, 0), nops._pad_to(ws, bn, 0),
            block_m=bm, block_n=bn, block_k=bk, interpret=True,
        )[:m, :n]
    assert out.shape == (m, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_interpret_rule_shared_by_both_kernels():
    """One rule for both kernels: interpret exactly off a TPU — so on the
    CPU both run their kernel body in the interpreter (flash attention no
    longer falls back to the blockwise jnp path)."""
    from repro.kernels.flash_attention import ops as fops
    from repro.kernels.platform import interpret_mode, pallas_interpret_flags

    assert jax.default_backend() != "tpu"
    assert interpret_mode() is True
    assert interpret_mode(False) is False
    x, w = jnp.ones((4, 16)), jnp.ones((16, 8))
    assert pallas_interpret_flags(nops.npu_matmul, x, w) == [True]
    q = jnp.ones((1, 64, 4, 32))
    kv = jnp.ones((1, 64, 2, 32))
    attn = lambda q, k, v: fops.attention(q, k, v, block_q=32, block_kv=32)  # noqa: E731
    flags = pallas_interpret_flags(attn, q, kv, kv)
    assert flags and all(flags)


def test_quant_error_stats_counts_mixed_tree():
    """Non-float leaves (step counters, bool masks) must count as kept, so
    leaves_quantized + leaves_kept == total leaves on any params tree."""
    from repro.quant import fake_quant_tree, quant_error_stats

    rng = np.random.default_rng(5)
    params = {
        "w": jnp.asarray(rng.normal(size=(16, 8)), jnp.float32),  # quantized
        "b": jnp.asarray(rng.normal(size=(8,)), jnp.float32),  # kept (ndim < 2)
        "step": jnp.asarray(3, jnp.int32),  # kept (int)
        "mask": jnp.ones((4, 4), jnp.bool_),  # kept (bool)
    }
    q = fake_quant_tree(params)
    stats = quant_error_stats(params, q)
    total = len(jax.tree.leaves(params))
    assert stats.leaves_quantized == 1
    assert stats.leaves_kept == total - 1 == 3
    assert stats.mean_rel_err > 0


def test_int8_quant_error_bounded():
    """int8 symmetric quantization keeps the GEMM within ~2% relative error
    on well-conditioned inputs — the 'NPU is less accurate' premise, bounded."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
    out = nops.npu_matmul(x, w, interpret=True)
    exact = x @ w
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02


@given(
    st.integers(1, 6).map(lambda i: 2**i),
    st.integers(4, 9).map(lambda i: 2**i),
    st.floats(0.1, 100.0),
)
@SETTINGS
def test_quantize_roundtrip_property(m, k, scale):
    rng = np.random.default_rng(m * k)
    x = jnp.asarray(rng.normal(size=(m, k)) * scale, jnp.float32)
    q, s = nref.quantize_rowwise(x)
    deq = q.astype(jnp.float32) * s[:, None]
    # max round-off is half a quantization step per element
    step = jnp.abs(x).max(axis=1) / 127.0
    assert bool(jnp.all(jnp.abs(deq - x) <= step[:, None] * 0.5 + 1e-7))
    assert int(jnp.max(jnp.abs(q))) <= 127


@pytest.mark.parametrize(
    "b,s,t,h,kh,hd,causal",
    [
        (2, 128, 128, 8, 4, 64, True),
        (1, 100, 200, 4, 4, 32, False),
        (2, 257, 257, 8, 2, 64, True),
        (1, 64, 512, 16, 8, 128, True),
        (1, 33, 65, 2, 1, 16, False),
    ],
)
def test_flash_attention_matches_ref(b, s, t, h, kh, hd, causal):
    rng = np.random.default_rng(s * t)
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, t, kh, hd)), jnp.float32)
    ref = fr.sdpa_ref(q, k, v, causal=causal)
    out = fk.flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(2, 128, 8, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 128, 4, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 128, 4, 64)), jnp.bfloat16)
    ref = fr.sdpa_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32), causal=True
    )
    out = fk.flash_attention(q, k, v, causal=True, block_q=64, block_kv=64, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=0.05, atol=0.02
    )


def test_blockwise_oracle_matches_dense():
    """The jnp blockwise path (what models use off-TPU) == dense attention."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 100, 8, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 100, 4, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 100, 4, 32)), jnp.float32)
    ref = fr.sdpa_ref(q, k, v, causal=True)
    out = fr.blockwise_ref(q, k, v, causal=True, q_block=32, kv_block=48)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=2e-6)


@given(st.integers(1, 4), st.integers(1, 5), st.booleans())
@SETTINGS
def test_flash_attention_property(b, blocks, causal):
    """Random (ragged vs block) sizes: kernel == oracle."""
    s = 17 * blocks + 3
    rng = np.random.default_rng(b * blocks)
    q = jnp.asarray(rng.normal(size=(b, s, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, 2, 32)), jnp.float32)
    ref = fr.sdpa_ref(q, k, v, causal=causal)
    out = fk.flash_attention(q, k, v, causal=causal, block_q=32, block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=2e-5)
