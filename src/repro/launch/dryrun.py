import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("REPRO_XLA_EXTRA", "") + " --xla_force_host_platform_device_count=512"
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract the roofline inputs.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun

The XLA_FLAGS line above MUST run before any other import (jax locks the
device count at first init) — hence the unusual module layout.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from .. import configs  # noqa: E402
from ..arch import n_params  # noqa: E402
from ..sharding.rules import MeshRules, serve_rules, train_rules  # noqa: E402
from ..train.optim import AdamWConfig  # noqa: E402
from . import analysis  # noqa: E402
from .mesh import make_production_mesh  # noqa: E402
from .steps import build_cell  # noqa: E402


def _tokens_of(arch, shape) -> float:
    """Work units (tokens / patches / pixels-equivalents) for MODEL_FLOPS."""
    if arch.family == "lm":
        if shape.kind == "train":
            return shape.batch * shape.seq
        if shape.kind == "prefill":
            return shape.batch * shape.seq
        return shape.batch * 1.0  # decode: one token per sequence
    if arch.family in ("dit", "flux"):
        lat = shape.img // 8
        return shape.batch * (lat // arch.cfg.patch) ** 2
    return shape.batch * (shape.img // 16) ** 2  # vision: ~patch16 equivalents


def _active_params(arch) -> int:
    if arch.family == "lm" and arch.cfg.moe is not None:
        m = arch.cfg.moe
        full = n_params(arch)
        expert_p = 3 * m.d_model * m.d_ff_expert
        inactive = (m.n_experts - m.top_k) * expert_p * arch.cfg.n_layers
        return full - inactive
    return n_params(arch)


def run_cell(
    arch_name: str,
    shape_name: str,
    *,
    multi_pod: bool,
    out_dir: Path | None,
    submesh: tuple[int, int] | None = None,
    kv_quant: bool = False,
) -> dict:
    """submesh=(data, model): serve on an N-chip replica instead of the full
    pod — the deployment lever for small-batch serving cells (per-replica
    collective cost is ~mesh-size-invariant, so K replicas = K x throughput).
    kv_quant: int8 KV cache for LM serve cells (halves the decode memory term)."""
    import dataclasses as _dc

    arch = configs.get(arch_name)
    shape = arch.shape(shape_name)
    if kv_quant and arch.family == "lm":
        arch = _dc.replace(arch, cfg=_dc.replace(arch.cfg, kv_quant=True))
    if submesh is not None:
        import jax as _jax

        mesh = _jax.make_mesh(
            submesh, ("data", "model"),
            axis_types=(_jax.sharding.AxisType.Auto,) * 2,
        )
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    is_train = "train" in shape.kind
    table = train_rules(mesh) if is_train else serve_rules(mesh)
    if arch.sharding_overrides:
        table.update(arch.sharding_overrides)
    rules = MeshRules(mesh, table)
    prog = build_cell(arch, shape_name, rules=rules, adamw=AdamWConfig())

    from ..models.layers import flash_accounting

    t0 = time.time()
    with jax.set_mesh(mesh):
        jitted = prog.jit()
        abstract = prog.abstract_args()
        lowered = jitted.lower(*abstract)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        # Flash-kernel variant: the attention inner body is one Pallas call
        # on TPU; XLA sees exactly the stubbed program around it.  Collectives
        # and memory for the kernel-enabled system come from THIS compile;
        # flops always from the real trace.
        with flash_accounting():
            compiled_flash = prog.jit(fresh=True).lower(*abstract).compile()

    mem = compiled.memory_analysis()
    mem_flash = compiled_flash.memory_analysis()
    hlo = compiled.as_text()
    coll = analysis.parse_collectives(hlo)
    coll_flash = analysis.parse_collectives(compiled_flash.as_text())
    jc = analysis.traced_costs(prog.fn, *abstract)
    with flash_accounting():
        jc_flash = analysis.traced_costs(prog.fn, *abstract)
    ca = compiled.cost_analysis() or {}
    # The flash kernel still needs full K/V per device when activations are
    # seq-sharded and the model is in the K/V-gather regime (2*KH*hd < D —
    # see models.lm._unshard_seq).  The stub's tiny K/V dependency lets DCE
    # drop that gather, so add it analytically (per-device result bytes).
    kv_gather_s = 0.0
    if arch.family == "lm" and shape.kind in ("prefill", "train"):
        cfg = arch.cfg
        if 2 * cfg.n_kv_heads * cfg.hd < cfg.d_model:
            traversals = 3.0 if shape.kind == "train" else 1.0
            kv_bytes = 2 * shape.seq * cfg.n_kv_heads * cfg.hd * 2 * cfg.n_layers * traversals
            kv_gather_s = kv_bytes / analysis.LINK_BW
            coll_flash = dict(coll_flash)
            coll_flash["est_seconds"] = coll_flash["est_seconds"] + kv_gather_s
            coll_flash["kv_gather_s_analytic"] = kv_gather_s
    rf_noflash = analysis.roofline(jc.flops, jc.bytes, coll, chips)
    rf = analysis.roofline(jc.flops, jc_flash.bytes, coll_flash, chips)
    mf = analysis.model_flops(shape.kind, n_params(arch), _active_params(arch), _tokens_of(arch, shape))

    rec = {
        "arch": arch_name,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": f"{submesh[0]}x{submesh[1]}" if submesh else ("2x16x16" if multi_pod else "16x16"),
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gb": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
                 - mem.alias_size_in_bytes) / 1e9, 3),
            **analysis.analytic_memory_gb(
                mem.argument_size_in_bytes, mem.output_size_in_bytes,
                mem.alias_size_in_bytes, shape.kind, mem.temp_size_in_bytes),
            "flash_peak_per_device_gb": round(
                (mem_flash.argument_size_in_bytes + mem_flash.output_size_in_bytes
                 + mem_flash.temp_size_in_bytes - mem_flash.alias_size_in_bytes) / 1e9, 3),
        },
        "flops_jaxpr": jc.flops,
        "bytes_jaxpr": jc.bytes,
        "bytes_jaxpr_flash": jc_flash.bytes,
        "xla_cost_flops": ca.get("flops", 0.0),
        "collectives": coll,
        "collectives_flash": coll_flash,
        "top_collectives": analysis.top_collective_sites(hlo),
        "top_collectives_flash": analysis.top_collective_sites(compiled_flash.as_text()),
        "top_cost_sites": analysis.top_cost_sites(prog.fn, *abstract),
        "roofline": rf,
        "roofline_no_flash_kernel": rf_noflash,
        "model_flops": mf,
        "useful_compute_ratio": mf / jc.flops if jc.flops else 0.0,
        "n_params": n_params(arch),
        "hlo_bytes": len(hlo),
    }
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        path = out_dir / f"{rec['mesh']}__{arch_name}__{shape_name}.json"
        path.write_text(json.dumps(rec, indent=1))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--submesh", default=None, help="DATAxMODEL serving replica, e.g. 4x4")
    ap.add_argument("--kv-quant", action="store_true", help="int8 KV cache for LM serve cells")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    submesh = None
    if args.submesh:
        d, m = args.submesh.lower().split("x")
        submesh = (int(d), int(m))

    out = Path(args.out)
    cells = configs.cells() if args.all else [(args.arch, args.shape)]
    if args.arch and not args.shape:
        cells = [(args.arch, s.name) for s in configs.get(args.arch).shapes]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    ok, failed = 0, []
    for arch_name, shape_name in cells:
        for mp in meshes:
            tag = f"{arch_name}/{shape_name}@{args.submesh or ('2x16x16' if mp else '16x16')}"
            try:
                rec = run_cell(
                    arch_name, shape_name, multi_pod=mp, out_dir=out, submesh=submesh,
                    kv_quant=args.kv_quant,
                )
                r = rec["roofline"]
                print(
                    f"OK  {tag:55s} compile={rec['compile_s']:7.1f}s "
                    f"mem/dev={rec['memory']['peak_per_device_gb']:7.3f}GB "
                    f"compute={r['compute_s']:.3e}s mem={r['memory_s']:.3e}s "
                    f"coll={r['collective_s']:.3e}s -> {r['bottleneck']}",
                    flush=True,
                )
                ok += 1
            except Exception as e:  # noqa: BLE001
                failed.append(tag)
                print(f"FAIL {tag}: {type(e).__name__}: {e}", flush=True)
                traceback.print_exc()
    print(f"\n{ok} cells OK, {len(failed)} failed")
    for f in failed:
        print("  FAILED:", f)
    raise SystemExit(1 if failed else 0)


if __name__ == "__main__":
    main()
