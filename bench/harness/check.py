"""The comparison that decides ``correct``.

For each path (``npu``: the int8 forward; ``edge``: the bf16 forward) the
window keeps a seeded sample of ``(input, served logits)`` pairs as the
endpoints received and answered them.  After the window the configuration's
float32 reference runs over the same inputs, in blocks, and the worst
frame's relative L2 error ``|served - ref| / |ref|`` is read as
``<path>_rel_l2``.  A reading is compared where the configuration's
``limits`` give it a limit.

The control is the reference itself in the program's place, at the lower
precision that the configuration's ``controls`` name for each path (the
step below what the path serves in): its readings come from the same
function on the same inputs, so the program and the control are measured
alike.
"""
from __future__ import annotations

import numpy as np

BLOCK = 16


def reference(cfg: dict, ref, params, state, inputs: np.ndarray, *, lower=None) -> np.ndarray:
    """The reference's logits over ``inputs`` ``[B, H, W, 3]``, computed in
    fixed blocks of ``BLOCK`` rows so that one program serves any count;
    with ``lower`` (bits, or a floating type's name) every convolution's
    and matmul's operands are first rounded to that precision."""
    import jax

    fwd = jax.jit(lambda p, s, x: ref.reference_logits(cfg, p, s, x, lower=lower))
    out = []
    for lo in range(0, len(inputs), BLOCK):
        block = inputs[lo:lo + BLOCK]
        pad = BLOCK - len(block)
        if pad:
            block = np.concatenate([block, np.zeros((pad, *block.shape[1:]), block.dtype)])
        out.append(np.asarray(fwd(params, state, block))[:BLOCK - pad])
    return np.concatenate(out)


def stack(items) -> tuple[np.ndarray, np.ndarray]:
    """``(inputs [B, H, W, 3], served [B, classes])`` from sampled pairs; an
    input may be one frame or a batch of one."""
    xs = [np.asarray(x, np.float32).reshape(-1, *np.shape(x)[-3:]) for x, _ in items]
    ys = [np.asarray(y, np.float32).reshape(len(x), -1) for x, (_, y) in zip(xs, items)]
    return np.concatenate(xs), np.concatenate(ys)


def worst_rel_l2(served: np.ndarray, want: np.ndarray) -> float:
    """The largest relative L2 error of a row; a row that is not finite
    reads as infinite."""
    rows = np.linalg.norm(served - want, axis=-1) / np.linalg.norm(want, axis=-1)
    return float(np.max(np.where(np.isfinite(rows), rows, np.inf)))


def readings(cfg: dict, ref, params, state, samples: dict, *, control: bool = False) -> dict:
    """``{<path>_rel_l2: value}`` for every path that served a sampled
    frame: of the served answers, or with ``control`` of the reference at
    the configuration's lower precision for that path, on the same inputs.
    A sampled answer that is missing reads as infinite."""
    out = {}
    for path, items in samples.items():
        if not items:
            continue
        name = f"{path}_rel_l2"
        if not control and any(y is None for _, y in items):
            out[name] = float("inf")
            continue
        inputs, served = stack(items)
        if control:
            served = reference(cfg, ref, params, state, inputs, lower=cfg["controls"][path])
        out[name] = worst_rel_l2(served, reference(cfg, ref, params, state, inputs))
    return out


def compare(cfg: dict, values: dict) -> dict:
    """``{name: (value, limit)}`` for every reading the configuration
    limits."""
    return {k: (v, cfg["limits"][k]) for k, v in values.items() if k in cfg["limits"]}


def passes(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())


def report(numbers: dict) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
