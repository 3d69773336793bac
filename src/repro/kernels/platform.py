"""Where a Pallas kernel runs: the one rule both kernels share.

A kernel compiles to Mosaic on a TPU and runs in the Pallas interpreter on
every other platform (the CPU tests validate the real kernel body that
way).  There is no switch and no silent jnp fallback: on a TPU the kernel
is the kernel.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` when given, else True exactly when the platform is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def pallas_interpret_flags(fn, *args) -> list[bool]:
    """The ``interpret`` flag of every ``pallas_call`` that ``fn(*args)``
    traces to, nested jaxprs (jit, scan, cond) included: what ran, as
    opposed to what a caller asked for."""
    flags: list[bool] = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                flags.append(bool(eqn.params["interpret"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return flags
