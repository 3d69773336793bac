"""Mesh scale-out for the batched sweep engines.

Every planner program in :mod:`sim_batch` / :mod:`sim_multi_batch` has the
same calling convention: ``n_lane`` leading arguments carry the scenario
(lane) batch on axis 0 and the trailing arguments are shared tables
(``in_axes = (0,) * n_lane + (None,) * k``).  :class:`LaneProgram` wraps
``jit(vmap(one))`` once per shape bucket and routes calls through
:func:`run_sharded`:

* **single device** (or ``REPRO_SWEEP_SHARD=0``): the plain jitted program
  runs exactly as before — bit-identical to the pre-sharding engine, so
  every golden-lattice and hypothesis equivalence contract keeps holding
  without a mesh in the loop;
* **multi device**: lane args are padded on axis 0 to a multiple of the
  sweep mesh (by repeating the final lane — planner lanes are independent,
  so a duplicated lane computes a result we slice off, the same inert-
  padding argument as the W/NBINS shape buckets), the program runs under
  ``shard_map`` over the mesh's ``scenario`` axis with shared tables
  replicated, and outputs are sliced back to the true lane count.

The mesh comes from :func:`repro.launch.mesh.make_sweep_mesh` and the
partition specs from :func:`repro.sharding.rules.sweep_rules` — the rules'
divisibility guard is what certifies the padded lane count actually
shards.

Scenario lane buffers are deliberately **not** donated.  A planner never
reads a lane argument after the call, so donation looks free — but on
jax 0.4.37 on the CPU an executable compiled with ``donate_argnums`` and
*reloaded from the persistent compilation cache* returns corrupted stats
for a nondeterministic subset of lanes (reproduced and bisected to
donation by the scale bench: clean with donation off, hundreds of zeroed
lanes with it on).  The inputs are host-built numpy chunks anyway, so
donation never had an allocation to reuse here — correctness wins.
"""
from __future__ import annotations

import os
from functools import lru_cache

import jax
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..launch.mesh import make_sweep_mesh
from ..sharding.rules import MeshRules, sweep_rules

def _shard_enabled() -> bool:
    return os.environ.get("REPRO_SWEEP_SHARD", "1") != "0"


class LaneProgram:
    """One compiled planner program: ``jit(vmap(one, in_axes))`` plus the
    lane metadata :func:`run_sharded` needs to scale it across a mesh.

    ``in_axes`` must be ``(0,) * n_lane + (None,) * n_shared`` — lane args
    lead, shared tables trail.  Calling the instance dispatches through
    :func:`run_sharded`; the raw single-device executable stays reachable
    as ``.jit`` (tests use its ``_cache_size`` for compile counting).
    """

    def __init__(self, one, in_axes: tuple):
        n_lane = 0
        for ax in in_axes:
            if ax != 0:
                break
            n_lane += 1
        if any(ax is not None for ax in in_axes[n_lane:]):
            raise ValueError(
                f"lane args must lead: in_axes must be (0,)*n + (None,)*k, got {in_axes}"
            )
        self.n_lane = n_lane
        self.n_args = len(in_axes)
        self._vmapped = jax.vmap(one, in_axes=in_axes)
        # no donate_argnums: see the module docstring's persistent-cache hazard
        self.jit = jax.jit(self._vmapped)

    def __call__(self, *args):
        return run_sharded(self, *args)


@lru_cache(maxsize=None)
def _sharded_jit(prog: LaneProgram, mesh: Mesh):
    """jit(shard_map(program)) over the sweep mesh, one per (program, mesh)."""
    rules = MeshRules(mesh, sweep_rules(mesh))
    # Resolved at the mesh extent itself: padding guarantees divisibility,
    # and the rules' guard would replicate (never mis-shard) anything else.
    lane = rules._resolve((mesh.size,), ("scenario",))
    assert lane != P(), "sweep mesh must expose a scenario/batch axis"
    in_specs = tuple(lane if i < prog.n_lane else P() for i in range(prog.n_args))
    sm = jax.shard_map(
        prog._vmapped, mesh=mesh, in_specs=in_specs,
        out_specs=lane, check_vma=False,
    )
    return jax.jit(sm)


def run_sharded(prog: LaneProgram, *args):
    """Run ``prog`` over its lane batch, sharded across the sweep mesh.

    Single-device meshes (and ``REPRO_SWEEP_SHARD=0``) take the plain
    jitted path — bit-identical to the unsharded engine.  Multi-device
    meshes pad lanes to the mesh extent by repeating the last lane, shard,
    and slice outputs back to the true batch.
    """
    mesh = make_sweep_mesh()
    if mesh.size == 1 or not _shard_enabled():
        return prog.jit(*args)
    B = int(np.shape(args[0])[0])
    pad = (-B) % mesh.size
    if pad:
        args = tuple(
            np.concatenate([np.asarray(a), np.repeat(np.asarray(a)[-1:], pad, axis=0)])
            if i < prog.n_lane else a
            for i, a in enumerate(args)
        )
    out = _sharded_jit(prog, mesh)(*args)
    return tuple(np.asarray(o)[:B] for o in out)
