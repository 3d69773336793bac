"""Uniform architecture surface: each served classifier (ResNet-50,
SqueezeNet, Swin-B) is an ``Arch`` whose family names its adapter, the
abstract params and the forward.  ``configs/<id>.py`` files instantiate
these; serving, calibration, tests and the benchmark consume only this API.
"""
from __future__ import annotations

import dataclasses
from typing import Any

from .models import convnets, vision


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    family: str  # resnet | squeezenet | swin
    cfg: Any
    notes: str = ""


def _swin_forward(cfg, params, state, images, *, train: bool):
    return vision.swin_forward(cfg, params, images), state


# family -> (abstract (params, state) specs, forward(cfg, params, state,
# images, *, train) -> (logits, new_state))
_FAMILIES = {
    "resnet": (convnets.resnet_abstract, convnets.resnet_forward),
    "squeezenet": (convnets.squeezenet_abstract, convnets.squeezenet_forward),
    "swin": (lambda cfg: (vision.swin_abstract_params(cfg), {}), _swin_forward),
}


def abstract_params(arch: Arch):
    return _FAMILIES[arch.family][0](arch.cfg)


def classifier_forward(arch: Arch, params, state, images, *, train: bool):
    return _FAMILIES[arch.family][1](arch.cfg, params, state, images, train=train)
