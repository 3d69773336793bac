"""Production meshes.  A FUNCTION (not a module constant) so importing never
touches jax device state — required because smoke tests must see 1 device
while the dry-run sees 512 (XLA_FLAGS set by dryrun.py before any import).
"""
from __future__ import annotations

from functools import lru_cache

import jax
from jax.sharding import Mesh


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: int = 0) -> Mesh:
    """Small mesh over however many (fake) host devices exist — for tests."""
    if pod:
        return _mesh((pod, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))


@lru_cache(maxsize=None)
def make_sweep_mesh() -> Mesh:
    """1-D mesh over every local device, for scenario-parallel sweep groups.

    The sweep engines shard only the scenario (lane) axis — planner programs
    are embarrassingly parallel across lanes, so a flat mesh uses every
    device with zero cross-device traffic.  Cached: the device topology is
    fixed for the life of the process, and callers key compiled sharded
    programs on this mesh object.
    """
    return _mesh((jax.device_count(),), ("scenario",))
