"""Fleet-sweep engine benchmark: reference loop vs batched jit/vmap backend.

Two ladders run the same grids through both ``Session.run_sweep`` backends
at {10, 100, 1000} points and report wall-clock plus the equivalence bit
(integer stats exact; accuracy sums within ``AUDIT_TOL`` — the speedup is
worthless otherwise):

  * the **jax ladder** (``jax_accuracy``/``jax_utility``): network-aware
    (bandwidth × deadline × fps × rtt) grids — the axes parameterize the
    scenario; these local-only policies ignore the network, and their
    per-round reference pays a jitted-kernel dispatch, which is what the
    vectorized engine amortizes.  **Acceptance bar: >= 10x warm at the
    1000-point grid** (tracked since PR 3, now on a network-aware grid).
  * the **network ladder** (``max_accuracy``/``max_utility``): the paper's
    offload-capable planners on network-aware grids — piecewise traces with
    an rtt axis at 10/100 points, a low-bandwidth (bandwidth × deadline ×
    fps × rtt) grid at 1000.  Their *reference* is plain numpy/Python (no
    per-round jit dispatch), so on a small-CPU host the batched engine
    roughly breaks even — the recorded ``speedup_warm`` is the honest
    number, gated on equivalence only (the row exists to track the perf
    trajectory on parallel hardware, where the lanes are free).

Every cell also reports **compile counts** (via jax monitoring — real XLA
builds vs persistent-cache loads) and **peak host RSS** (a sampler thread
over ``/proc/self/statm``), so the caching and streaming wins are measured,
not inferred.

The **scale cell** is the headline: a >= 100k-point network-aware
``max_utility`` grid streamed through ``run_sweep(chunk_size=...,
keep_points=False)`` with the persistent compilation cache enabled — run
cold (compiles), warm (all caches hot), then again "cold" after dropping
every in-process executable (fresh-process simulation: compiled programs
reload from the disk cache).  The acceptance gate is that this cache-warm
cold path lands within 2x of the warm run, i.e. compilation is amortized
away.  A 100-point corner of the same grid is spot-checked exactly against
the reference loop (full-grid equivalence is impossible at 10^5 but chunk
invariance is golden-tested in tests/test_sweep_scale.py).

Results land in ``BENCH_sweep.json`` so CI can track the trajectory:

    PYTHONPATH=src python benchmarks/sweep_bench.py            # full ladders + 100k scale cell
    PYTHONPATH=src python benchmarks/sweep_bench.py --smoke    # 10-point grids + 10k scale cell
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax  # noqa: E402

from repro.core import PolicySpec  # noqa: E402
from repro.core import sim_batch, sim_multi_batch, sweep_shard  # noqa: E402
from repro.core.audit import AUDIT_TOL  # noqa: E402
from repro.core.compile_cache import CompileCounter, enable_compile_cache  # noqa: E402
from repro.session import ScenarioSpec, Session, SweepGrid, TraceSpec  # noqa: E402

N_FRAMES = 120
POLICIES = (("jax_accuracy", {}), ("jax_utility", {"alpha": 200.0}))
NET_POLICIES = (("max_accuracy", {}), ("max_utility", {"alpha": 200.0}))
SIZES = (10, 100, 1000)
DEFAULT_OUT = "BENCH_sweep.json"

# The scale cell: the paper's offload-capable utility planner on a short
# clip, streamed.  2.0 ms/point warm on a 1-core host — 100k points is a
# ~3.5 min warm pass, and nothing but one 2500-point chunk plus the running
# summary ever lives on the host.
SCALE_POLICY = ("max_utility", {"alpha": 200.0})
SCALE_N_FRAMES = 24
SCALE_CHUNK = 2500

_PAGE_BYTES = os.sysconf("SC_PAGE_SIZE")


def _rss_bytes() -> int:
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * _PAGE_BYTES
    except (OSError, IndexError, ValueError):  # non-procfs host
        return 0


class _RssSampler:
    """Peak host RSS over a measured region, polled from /proc/self/statm.

    A daemon thread samples at ~20 Hz — cheap enough to leave running for a
    multi-minute sweep, and it catches transient peaks (a chunk's worth of
    lane arrays materializing) that an end-of-run snapshot would miss.
    """

    def __init__(self, interval_s: float = 0.05):
        self.interval_s = interval_s
        self.peak_bytes = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._poll, daemon=True)

    def _poll(self):
        while not self._stop.is_set():
            self.peak_bytes = max(self.peak_bytes, _rss_bytes())
            self._stop.wait(self.interval_s)

    def __enter__(self):
        self.peak_bytes = _rss_bytes()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak_bytes = max(self.peak_bytes, _rss_bytes())
        return False

    @property
    def peak_mib(self) -> float:
        return self.peak_bytes / (1024 * 1024)


def _clear_compiled() -> None:
    """Drop every in-process executable: the engines' jitted-program
    factories, the shard_map wrapper cache, and jax's trace/compile caches.
    The next sweep then behaves like a fresh process — programs re-trace,
    and XLA binaries come from the persistent compilation cache (when
    enabled) instead of a full recompile."""
    for mod in (sim_batch, sim_multi_batch):
        for name in dir(mod):
            obj = getattr(mod, name)
            if callable(getattr(obj, "cache_clear", None)):
                obj.cache_clear()
    sweep_shard._sharded_jit.cache_clear()
    jax.clear_caches()

PIECEWISE = TraceSpec(
    kind="piecewise", points=((0.0, 3.0), (0.3, 0.8), (0.9, 6.0)), rtt_ms=60.0
)


def make_grid(size: int) -> SweepGrid:
    """A network-aware grid with exactly ``size`` points (jax ladder)."""
    if size == 10:
        return SweepGrid(deadline_ms=(150.0, 200.0, 250.0, 300.0, 350.0), fps=(20.0, 40.0))
    if size == 100:
        return SweepGrid(
            deadline_ms=tuple(150.0 + 20.0 * i for i in range(10)),
            fps=(10.0, 20.0, 30.0, 40.0, 50.0),
            bandwidth_mbps=(1.0, 2.5),
        )
    if size == 1000:
        return SweepGrid(
            deadline_ms=tuple(120.0 + 20.0 * i for i in range(10)),
            fps=(10.0, 20.0, 30.0, 40.0, 50.0),
            bandwidth_mbps=(0.5, 1.0, 2.0, 4.0, 8.0),
            rtt_ms=(40.0, 70.0, 100.0, 130.0),
        )
    raise ValueError(f"no predefined grid of size {size}")


def make_net_grid(size: int) -> tuple[SweepGrid, TraceSpec]:
    """Network ladder: grid + base trace for the paper's planners.

    10/100-point grids replay a *piecewise* trace on device (deadline ×
    fps × rtt axes preserve it); the 1000-point grid sweeps a constant
    low-bandwidth regime where offload/local candidate selection really
    flips per point.
    """
    if size == 10:
        return SweepGrid(
            deadline_ms=(150.0, 200.0, 250.0, 300.0, 350.0), rtt_ms=(50.0, 100.0)
        ), PIECEWISE
    if size == 100:
        return SweepGrid(
            deadline_ms=tuple(150.0 + 20.0 * i for i in range(10)),
            fps=(10.0, 20.0, 30.0, 40.0, 50.0),
            rtt_ms=(50.0, 100.0),
        ), PIECEWISE
    if size == 1000:
        return SweepGrid(
            deadline_ms=tuple(240.0 + 16.0 * i for i in range(10)),
            fps=(30.0, 48.0, 50.0, 56.0, 60.0),
            bandwidth_mbps=(0.3, 0.5, 0.8, 1.1, 1.4),
            rtt_ms=(40.0, 70.0, 100.0, 130.0),
        ), TraceSpec(mbps=1.0)
    raise ValueError(f"no predefined network grid of size {size}")


def _stats_equiv(a, b) -> bool:
    """The certified cross-backend contract: ints exact, floats in tol."""
    return (
        abs(a.accuracy_sum - b.accuracy_sum) <= AUDIT_TOL
        and a.frames_processed == b.frames_processed
        and a.frames_missed_deadline == b.frames_missed_deadline
        and a.frames_offloaded == b.frames_offloaded
        and a.frames_total == b.frames_total
    )


def bench_cell(policy: str, params: dict, size: int, *, net: bool = False) -> dict:
    if net:
        grid, trace = make_net_grid(size)
    else:
        grid, trace = make_grid(size), TraceSpec(mbps=2.5)
    session = Session(
        ScenarioSpec(policy=PolicySpec(policy, params), n_frames=N_FRAMES,
                     trace=trace, label=f"sweep_bench/{policy}/{size}")
    )
    with _RssSampler() as rss:
        t0 = time.perf_counter()
        ref = session.run_sweep(grid, backend="reference")
        reference_s = time.perf_counter() - t0
        with CompileCounter() as cc:
            t0 = time.perf_counter()
            session.run_sweep(grid, backend="batched")
            batched_cold_s = time.perf_counter() - t0
        with CompileCounter() as cw:
            t0 = time.perf_counter()
            bat = session.run_sweep(grid, backend="batched")
            batched_warm_s = time.perf_counter() - t0
    assert bat.backend == "batched", bat.meta
    exact = all(
        _stats_equiv(pr.stats, pb.stats) for pr, pb in zip(ref.points, bat.points)
    )
    return {
        "policy": policy,
        "ladder": "network" if net else "jax",
        "trace": trace.kind,
        "grid_points": len(grid),
        "n_frames": N_FRAMES,
        "reference_s": reference_s,
        "batched_cold_s": batched_cold_s,
        "batched_warm_s": batched_warm_s,
        "speedup_cold": reference_s / batched_cold_s if batched_cold_s > 0 else 0.0,
        "speedup_warm": reference_s / batched_warm_s if batched_warm_s > 0 else 0.0,
        "compiles_cold": cc.compiles,
        "compiles_warm": cw.compiles,
        "peak_rss_mib": round(rss.peak_mib, 1),
        "exact_match": exact,
    }


def make_scale_grid(points: int) -> SweepGrid:
    """A network-aware grid with exactly ``points`` points: deadline (20) x
    fps (5) x bandwidth (20) x rtt (points/2000).  Growing the grid only
    stretches the rtt axis, so every size hits the same shape buckets."""
    n_rtt, rem = divmod(points, 2000)
    if rem or n_rtt < 1:
        raise ValueError(f"scale grid size must be a positive multiple of 2000, got {points}")
    return SweepGrid(
        deadline_ms=tuple(150.0 + 10.0 * i for i in range(20)),
        fps=(24.0, 30.0, 48.0, 50.0, 60.0),
        bandwidth_mbps=tuple(0.3 + 0.2 * i for i in range(20)),
        rtt_ms=tuple(30.0 + 4.0 * i for i in range(n_rtt)),
    )


def bench_scale_cell(points: int) -> dict:
    """The streaming + persistent-cache headline (module docstring).

    Protocol: spot-check a 16-point corner against the reference loop, then
    run the full grid three times — cold (compiles, populates the disk
    cache), warm (everything hot), and cold-again after
    :func:`_clear_compiled` (fresh-process simulation: executables reload
    from the persistent cache).  Gate: cached-cold within 2x of warm, and
    zero XLA compiles on both the warm and cached-cold passes.
    """
    grid = make_scale_grid(points)
    pol, params = SCALE_POLICY
    session = Session(
        ScenarioSpec(policy=PolicySpec(pol, params), n_frames=SCALE_N_FRAMES,
                     trace=TraceSpec(mbps=2.5), label=f"sweep_bench/scale/{points}")
    )
    sub = SweepGrid(
        deadline_ms=grid.deadline_ms[:2], fps=grid.fps[:2],
        bandwidth_mbps=grid.bandwidth_mbps[:2], rtt_ms=grid.rtt_ms[:2],
    )
    ref = session.run_sweep(sub, backend="reference")
    bat = session.run_sweep(sub, backend="batched")
    spot_ok = all(
        _stats_equiv(a.stats, b.stats) for a, b in zip(ref.points, bat.points)
    )

    cache_dir = enable_compile_cache()
    run_kw = dict(backend="batched", chunk_size=SCALE_CHUNK, keep_points=False)
    _clear_compiled()  # the spot check must not pre-warm the cold pass
    with _RssSampler() as rss:
        with CompileCounter() as c1:
            t0 = time.perf_counter()
            rep1 = session.run_sweep(grid, **run_kw)
            cold_s = time.perf_counter() - t0
        with CompileCounter() as cw:
            t0 = time.perf_counter()
            rep2 = session.run_sweep(grid, **run_kw)
            warm_s = time.perf_counter() - t0
        _clear_compiled()
        with CompileCounter() as c2:
            t0 = time.perf_counter()
            rep3 = session.run_sweep(grid, **run_kw)
            cached_cold_s = time.perf_counter() - t0
    assert rep1.meta["summary"] == rep2.meta["summary"] == rep3.meta["summary"]
    assert rep1.meta["points_streamed"] == points
    return {
        "policy": pol,
        "ladder": "scale",
        "trace": "constant",
        "grid_points": len(grid),
        "n_frames": SCALE_N_FRAMES,
        "chunk_size": SCALE_CHUNK,
        "chunks": rep1.meta["chunks"],
        "compile_cache": cache_dir,
        "cold_s": cold_s,
        "warm_s": warm_s,
        "cached_cold_s": cached_cold_s,
        "cached_cold_over_warm": cached_cold_s / warm_s if warm_s > 0 else 0.0,
        "cached_cold_within_2x_warm": cached_cold_s <= 2.0 * warm_s,
        "compiles_cold": c1.compiles,
        "compiles_warm": cw.compiles,
        "compiles_cached_cold": c2.compiles,
        "cache_hits_cached_cold": c2.cache_hits,
        "peak_rss_mib": round(rss.peak_mib, 1),
        "spot_check_exact": spot_ok,
        "summary": rep1.meta["summary"],
    }


def run(sizes=SIZES) -> dict:
    cells = [bench_cell(pol, params, size) for size in sizes for pol, params in POLICIES]
    cells += [
        bench_cell(pol, params, size, net=True)
        for size in sizes
        for pol, params in NET_POLICIES
    ]
    return {"bench": "sweep", "n_frames": N_FRAMES, "cells": cells}


# run.py auto-discovery: smoke-sized rows only (the 1000-point ladder is a
# manual / CI-artifact run — see main()).
def sweep_backend_smoke():
    rows = []
    for cell in run(sizes=(10,))["cells"]:
        name = f"sweep/{cell['policy']}/n{cell['grid_points']}"
        rows.append((f"{name}/speedup_warm", cell["batched_warm_s"] * 1e6, cell["speedup_warm"]))
        rows.append((f"{name}/exact", cell["reference_s"] * 1e6, float(cell["exact_match"])))
    return rows


ALL = [sweep_backend_smoke]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smallest grids + 10k scale cell (CI smoke; still emits the JSON artifact)")
    ap.add_argument("--out", default=DEFAULT_OUT, help=f"output path (default {DEFAULT_OUT})")
    ap.add_argument("--scale-points", type=int, default=None,
                    help="scale-cell grid size (default 10000 smoke / 100000 full; 0 skips it)")
    args = ap.parse_args(argv)

    scale_points = args.scale_points
    if scale_points is None:
        scale_points = 10_000 if args.smoke else 100_000

    result = run(sizes=(10,) if args.smoke else SIZES)
    if scale_points:
        result["cells"].append(bench_scale_cell(scale_points))
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2)
        fh.write("\n")

    print(f"{'ladder':>8} {'policy':>14} {'points':>7} {'ref (s)':>9} {'cold (s)':>9} "
          f"{'warm (s)':>9} {'speedup':>8} {'rss MiB':>8} {'exact':>6}")
    ok = True
    for c in result["cells"]:
        if c["ladder"] == "scale":
            continue
        print(f"{c['ladder']:>8} {c['policy']:>14} {c['grid_points']:>7} "
              f"{c['reference_s']:>9.2f} {c['batched_cold_s']:>9.2f} "
              f"{c['batched_warm_s']:>9.2f} {c['speedup_warm']:>7.1f}x "
              f"{c['peak_rss_mib']:>8.0f} {str(c['exact_match']):>6}")
        ok &= c["exact_match"]
        # the >= 10x acceptance bar applies to the jax ladder's 1000-point
        # network-aware cells (see module docstring for the network
        # ladder's honest-CPU-number rationale).
        if c["ladder"] == "jax" and c["grid_points"] >= 1000:
            ok &= c["speedup_warm"] >= 10.0
    for c in result["cells"]:
        if c["ladder"] != "scale":
            continue
        print(f"\nscale {c['policy']} {c['grid_points']} pts in {c['chunks']} chunks of "
              f"{c['chunk_size']}: cold {c['cold_s']:.1f}s ({c['compiles_cold']} compiles), "
              f"warm {c['warm_s']:.1f}s, cached-cold {c['cached_cold_s']:.1f}s "
              f"({c['cached_cold_over_warm']:.2f}x warm, {c['cache_hits_cached_cold']} cache "
              f"hits, {c['compiles_cached_cold']} compiles), peak RSS {c['peak_rss_mib']:.0f} MiB")
        ok &= c["spot_check_exact"]
        ok &= c["cached_cold_within_2x_warm"]
        ok &= c["compiles_warm"] == 0 and c["compiles_cached_cold"] == 0
    print(f"\nwrote {args.out}")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
