"""Compile the main path's device programs for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a chip described by its topology.  That catches what interpret mode
cannot — Mosaic refusing a block layout, a tile off the int8 tiling, f64
ops the chip's compiler rejects — at no chip time.  Nothing here runs.

The topology is described only inside the module-scoped ``topo`` fixture
(never at import): only one process at a time may load the TPU library, so
describing it while the module is imported would break collection under
several test workers.
"""
from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.npu_matmul import ops

# (M, K, N) of the int8 GEMMs ResNet-50 runs at its published width: the
# head at one frame, the stage-1 3x3 im2col conv and the 7x7 stem at batch
# 16, a stage-4 3x3 conv at one frame, and a shape on no block multiple.
NPU_SHAPES = [
    (1, 2048, 1000),
    (50176, 576, 64),
    (200704, 147, 64),
    (4, 4608, 512),
    (33, 300, 200),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("m,k,n", NPU_SHAPES)
def test_npu_matmul_compiles_to_mosaic(one_chip, m, k, n):
    """Each shape compiles as the one quantize-in-kernel call."""
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda x, w: ops.npu_matmul(x, w, interpret=False))
    ops.PATHS.clear()
    compiled = fn.lower(x, w).compile()
    assert ops.PATHS == {"fused": 1}
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 1


# how bench/metrics/npu_matmul_roofline.py finds the kernel in a TPU trace
KERNEL_NAME = re.compile(r"^int8_matmul(\.\d+)?$")
CUSTOM_CALL = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*custom_call_target=\"tpu_custom_call\"")


@pytest.mark.parametrize("arch_name", ["resnet-50", "squeezenet", "swin-b"])
def test_int8_forward_names_its_kernel(one_chip, arch_name):
    """The int8 forward, built as the serving path builds it, compiles each
    GEMM to a ``tpu_custom_call`` whose instruction the trace reader can
    find by name."""
    from repro import configs, quant
    from repro.arch import abstract_params, classifier_forward
    from repro.models.common import ParamSpec

    arch = configs.get(arch_name, smoke=True)

    def forward(p, s, x):
        return classifier_forward(arch, p, s, x, train=False)[0]

    def struct(p):
        return jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip)

    params, state = jax.tree.map(struct, abstract_params(arch),
                                 is_leaf=lambda p: isinstance(p, ParamSpec))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32, sharding=one_chip)
    fwd = jax.jit(quant.npu_forward(forward, interpret=False))
    text = fwd.lower(params, state, x).compile().as_text()
    names = [m.group(1) for m in map(CUSTOM_CALL.match, text.splitlines()) if m]
    assert names
    assert all(KERNEL_NAME.match(n) for n in names), names


def _int8_forward_compiled(one_chip, arch, res):
    """``arch``'s int8 forward compiled at one ``res`` x ``res`` frame,
    weights as arguments (as the serving benchmark deploys it), the
    ``pallas_call`` sites its jaxpr holds (a scan body once), and the path
    tally of that trace."""
    from repro import quant
    from repro.arch import abstract_params, classifier_forward
    from repro.kernels.platform import pallas_interpret_flags
    from repro.models.common import ParamSpec

    def forward(p, s, x):
        return classifier_forward(arch, p, s, x, train=False)[0]

    def struct(p):
        return jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip)

    params, state = jax.tree.map(struct, abstract_params(arch),
                                 is_leaf=lambda p: isinstance(p, ParamSpec))
    x = jax.ShapeDtypeStruct((1, res, res, 3), jnp.float32, sharding=one_chip)
    fwd = quant.npu_forward(forward, interpret=False)
    ops.PATHS.clear()
    sites = len(pallas_interpret_flags(fwd, params, state, x))
    paths = dict(ops.PATHS)
    return jax.jit(fwd).lower(params, state, x).compile(), sites, paths


def _int8_forward_hlo(one_chip, arch, res):
    """The optimized HLO of ``_int8_forward_compiled``, its sites and tally."""
    compiled, sites, paths = _int8_forward_compiled(one_chip, arch, res)
    return compiled.as_text(), sites, paths


def _top_level_and_fused_ops(text):
    """``(opcode, op_name)`` of each HLO instruction, split into those of
    the computations a fusion calls and all others (entry, loop bodies)."""
    fused_names = set(re.findall(r" fusion\(.*?calls=%([\w.-]+)", text))
    top, fused, into = [], [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            into = fused if head.group(1) in fused_names else top
            continue
        op = re.match(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9-]*)\(", line)
        if op and into is not None:
            name = re.search(r'op_name="([^"]*)"', line)
            into.append((op.group(1), name.group(1) if name else ""))
    return top, fused


@pytest.mark.parametrize("arch_name", ["resnet-50", "squeezenet", "swin-b"])
def test_int8_forward_fused_at_published_width(one_chip, arch_name):
    """At published width (224 x 224, one frame) every GEMM call site of the
    int8 forward is one fused ``int8_matmul`` call: no two-pass site, no
    quantize round and no pad left in XLA around the kernels (the only pads
    are SqueezeNet's fire concatenates, inside fusions)."""
    from repro import configs

    text, sites, paths = _int8_forward_hlo(one_chip, configs.get(arch_name), 224)
    names = [m.group(1) for m in map(CUSTOM_CALL.match, text.splitlines()) if m]
    assert names and all(KERNEL_NAME.match(n) for n in names), names
    assert len(names) == sites
    assert paths == {"fused": sites}
    assert "round-nearest-even" not in text
    top, fused = _top_level_and_fused_ops(text)
    assert not [op for op in top if op[0] == "pad"]
    assert all(n.endswith("/concatenate") for op, n in fused if op == "pad")


# XLA's bytes accessed by ResNet-50's int8 forward at 224 x 224 (weights as
# arguments) with the patches built by a one-hot convolution in (cin, kh,
# kw) order and every conv weight transposed to match, before the patch
# builder of convnets.im2col
LAX_PATCH_RESNET50_BYTES = 1_028_341_248
SHAPE = re.compile(r"^\s*(?:ROOT )?%\S+ = [a-z0-9]+\[([\d,]*)\]")


def test_resnet50_int8_im2col_builds_patches_without_convolution(one_chip):
    """ResNet-50's int8 forward at published width: the only convolution
    left is the stem's one-hot patch convolution (the one conv whose views
    would outnumber the lane tiles of its patch row more than
    ``convnets.VIEWS_PER_TILE`` times), no instruction outside a fusion
    transposes a conv weight (per-frame weight transposes), and XLA's bytes
    accessed fall by at least 15% from the lax-patch lowering."""
    from repro import configs
    from repro.arch import abstract_params
    from repro.models import convnets
    from repro.models.common import ParamSpec

    arch = configs.get("resnet-50")
    convnets.CONV_PATHS.clear()
    compiled, _, _ = _int8_forward_compiled(one_chip, arch, 224)
    assert convnets.CONV_PATHS["patch_op"] == 1
    text = compiled.as_text()
    convs = [SHAPE.match(line).group(1) for line in text.splitlines()
             if re.match(r"^\s*(?:ROOT )?%\S+ = \S+ convolution\(", line)]
    assert convs == ["1,112,112,147"]  # 7 x 7 x 3 patches of the 224 x 224 frame

    weights = {
        s.shape[-4:] for s in jax.tree.leaves(abstract_params(arch)[0],
                                              is_leaf=lambda p: isinstance(p, ParamSpec))
        if s.init == "conv" and s.shape[-4] * s.shape[-3] > 1
    }
    fused_names = set(re.findall(r" fusion\(.*?calls=%([\w.-]+)", text))
    transposed, top = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            top = head.group(1) not in fused_names
            continue
        m = SHAPE.match(line)
        if not (top and m and m.group(1)):
            continue
        assert " transpose(" not in line, line
        dims = tuple(int(d) for d in m.group(1).split(",") if d != "1")
        if any(sorted(dims) == sorted(w) and dims != w for w in weights):
            transposed.append(line.strip()[:120])
    assert top is not None and not transposed, transposed

    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 0.85 * LAX_PATCH_RESNET50_BYTES, cost["bytes accessed"]


def test_max_accuracy_lane_program_compiles_x64(one_chip, monkeypatch):
    """One x64 lane program of the batched planner (``max_accuracy``, one
    small shape group), with the arguments the engine really passes."""
    from repro.core import PolicySpec, sim_batch
    from repro.core.profiles import PAPER_MODELS

    captured = []
    real = sim_batch._max_accuracy_program

    def capture(*key):
        prog = real(*key)

        def call(*args):
            captured.append((prog, args))
            return prog(*args)

        return call

    monkeypatch.setattr(sim_batch, "_max_accuracy_program", capture)
    scenarios = [
        sim_batch.BatchScenario(n_frames=8, params=PolicySpec("max_accuracy").resolved,
                                bw_segments=((0.0, 3e6), (0.3, 0.8e6)))
        for _ in range(3)
    ]
    sim_batch.simulate_batch("max_accuracy", PAPER_MODELS, scenarios)
    assert len(captured) == 1
    prog, args = captured[0]
    with jax.enable_x64(True):
        structs = [
            jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype, sharding=one_chip)
            for a in args
        ]
        assert any(s.dtype == np.float64 for s in structs)
        compiled = prog.jit.lower(*structs).compile()
    assert compiled.as_text()
