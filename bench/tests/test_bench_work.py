"""GEMM work counts against a hand count and against the program's own
matmul calls."""
import jax
import jax.numpy as jnp
import pytest
from tinycells import config

from harness import work

# ResNet SMOKE (depths 1-1, width 8, expansion 4, 10 classes) at 32x32:
# stem 7x7/2 -> 16x16, max-pool -> 8x8; stage 0 at 8x8 (8 -> 8 -> 32, with
# a projection); stage 1 strided to 4x4 (32 -> 16 -> 64, with a
# projection); the head on the pooled 64 features.
RESNET_SMOKE_32 = [
    (256, 147, 8),
    (64, 8, 8), (64, 72, 8), (64, 8, 32), (64, 8, 32),
    (64, 32, 16), (16, 144, 16), (16, 16, 64), (16, 32, 64),
    (1, 64, 10),
]


def test_resnet_smoke_gemms_by_hand():
    cfg, ref = config("resnet50")
    assert ref.gemms(cfg) == RESNET_SMOKE_32
    ops = sum(2 * m * k * n for m, k, n in RESNET_SMOKE_32)
    assert work.total_ops(ref.gemms(cfg)) == ops == 988_416


def test_resnet50_published_ops_per_frame():
    """About 4.1 G multiply-adds a frame at 224x224, as the paper family reports."""
    from harness import spec

    cfg, ref = spec.config("resnet50")
    gemms = ref.gemms(cfg)
    assert len(gemms) == 54
    assert 8.0e9 < work.total_ops(gemms) < 8.4e9


def test_int8_bytes_and_least_time():
    g = (64, 128, 256)
    assert work.int8_bytes(g) == 64 * 128 + 128 * 256 + 4 * 64 + 4 * 256 + 4 * 64 * 256
    peaks = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # memory-bound at these peaks: bytes / bandwidth exceeds ops / peak
    assert work.int8_least_s([g], peaks) == pytest.approx(work.int8_bytes(g) / 1e9)


@pytest.mark.parametrize("name", ["resnet50", "squeezenet"])
def test_gemms_match_the_programs_matmuls(name):
    """Every GEMM the program's int8 forward issues for one frame, in order."""
    from repro import configs
    from repro.arch import classifier_forward
    from repro.models.common import matmul_backend

    cfg, ref = config(name)
    arch = configs.get(cfg["program_arch"], smoke=True)
    params, state = jax.eval_shape(lambda k: ref.make_weights(k, cfg), jax.random.key(0))
    seen = []

    def record(x, w):
        seen.append((x.shape[0], x.shape[1], w.shape[1]))
        return x @ w

    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    with matmul_backend(record):
        jax.eval_shape(lambda p, s, x: classifier_forward(arch, p, s, x, train=False)[0],
                       params, state, x)
    assert seen == ref.gemms(cfg)
