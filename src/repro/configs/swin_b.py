"""swin-b [arXiv:2103.14030]: the source's
``configs/swin/swin_base_patch4_window7_224.yaml`` (microsoft/Swin-Transformer):
224px, patch 4, window 7, embed 128, depths 2-2-18-2, heads 4-8-16-32, MLP
ratio 4, qkv bias, no absolute position embedding, 1000 classes; 87,768,224
parameters in the layout of the source's ``models/swin_transformer.py``.

Departures from the source, in ``models/vision.py``:

* The served forward computes in bf16.  On the NPU path every Linear layer
  (patch embedding, ``qkv``, ``proj``, ``fc1``, ``fc2``, each patch-merge
  reduction, the head) is an int8 GEMM on ``kernels/npu_matmul``; the two
  attention products of each window (scores and values) stay bf16 on every
  path.
* A shifted window's region mask sets a score to -1e30 where the source
  adds -100: exp(-100) < 4e-44, so no softmax row moves by f32 resolution.
"""
from ..arch import Arch
from ..models import vision

CONFIG = Arch(
    name="swin-b",
    family="swin",
    cfg=vision.SwinConfig(name="swin-b", img_res=224),
)

SMOKE = Arch(
    name="swin-b-smoke",
    family="swin",
    cfg=vision.SwinConfig(
        name="swin-smoke",
        img_res=32,
        patch=4,
        window=4,
        depths=(2, 2),
        dims=(32, 64),
        n_heads=(2, 4),
        n_classes=10,
    ),
)
