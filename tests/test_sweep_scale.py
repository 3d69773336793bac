"""The sweep scale-out layer: chunked/streamed ``run_sweep`` and the mesh
sharding substrate (docs/simulation.md "Scaling sweeps").

Contracts under test:

* **Chunk invariance** — ``run_sweep(chunk_size=k)`` is bit-identical to
  the unchunked path on every deterministic stats field, for every batched
  policy family (local jitted DPs, network-aware planners, fleet engines,
  detect+track workloads).  Chunking only re-partitions ``_stitch``'s
  shape groups, and padding is inert, so nothing may change but wall time.
* **Streaming** — ``keep_points=False`` folds every chunk into an
  incremental :class:`SweepSummary`, equal to the fold over the kept
  points, and the summary-carrying report JSON round-trips.
* **Sharding fallback** — on a single device (this suite) the mesh path
  is the plain jitted program; ``REPRO_SWEEP_SHARD=0`` must be a no-op.
  Multi-device bit-identity runs in a subprocess with forced host devices
  (XLA_FLAGS must precede the jax import).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from repro.core import PolicySpec
from repro.session import (
    FleetSpec,
    ScenarioSpec,
    Session,
    SweepGrid,
    SweepReport,
    SweepSummary,
    TraceSpec,
)

# schedule_time is measured wall clock (apportioned per group) — everything
# else run_sweep reports is deterministic and must survive re-chunking.
DET_FIELDS = (
    "frames_total",
    "frames_processed",
    "frames_missed_deadline",
    "frames_offloaded",
    "accuracy_sum",
    "elapsed",
    "schedule_calls",
    "npu_busy_s",
)

PIECEWISE = TraceSpec(
    kind="piecewise", points=((0.0, 3.0), (0.4, 0.9), (1.1, 5.0)), rtt_ms=60.0
)


def _assert_det_equal(a: SweepReport, b: SweepReport) -> None:
    assert len(a.points) == len(b.points)
    for pa, pb in zip(a.points, b.points):
        assert pa.overrides == pb.overrides
        assert len(pa.streams) == len(pb.streams)
        for sa, sb in zip(pa.streams, pb.streams):
            for f in DET_FIELDS:
                assert getattr(sa, f) == getattr(sb, f), (pa.overrides, f)


def _fold(points) -> SweepSummary:
    s = SweepSummary()
    for p in points:
        s.update(p)
    return s


# Every batched policy family: (id, spec, grid).  The grids mix window
# buckets (fps axis) and cut at a non-divisor chunk size so chunk
# boundaries split shape groups mid-group.
def _cases():
    yield (
        "jax_accuracy",
        ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=12),
        SweepGrid(deadline_ms=(10.0, 150.0, 350.0), fps=(10.0, 30.0)),
    )
    yield (
        "jax_utility",
        ScenarioSpec(policy=PolicySpec("jax_utility", {"alpha": 200.0}), n_frames=12),
        SweepGrid(fps=(20.0, 50.0), params={"alpha": (50.0, 200.0)}),
    )
    yield (
        "max_accuracy",
        ScenarioSpec(policy=PolicySpec("max_accuracy"), n_frames=14, trace=PIECEWISE),
        SweepGrid(deadline_ms=(150.0, 250.0), fps=(10.0, 30.0), rtt_ms=(40.0, 90.0)),
    )
    yield (
        "max_utility",
        ScenarioSpec(policy=PolicySpec("max_utility", {"alpha": 200.0}), n_frames=14),
        SweepGrid(deadline_ms=(200.0, 350.0), fps=(30.0,), params={"alpha": (50.0, 200.0)}),
    )
    yield (
        "jax_utility-fleet",
        ScenarioSpec(
            policy=PolicySpec("jax_utility", {"alpha": 200.0}),
            n_frames=10,
            fleet=FleetSpec(capacity=2),
        ),
        SweepGrid(n_clients=(1, 2, 3), deadline_ms=(150.0, 250.0)),
    )
    yield (
        "max_accuracy-fleet",
        ScenarioSpec(
            policy=PolicySpec("max_accuracy"),
            n_frames=8,
            fleet=FleetSpec(n_clients=2, capacity=2),
        ),
        SweepGrid(bandwidth_mbps=(1.0, 4.0), deadline_ms=(150.0, 250.0)),
    )
    yield (
        "track_accuracy",
        ScenarioSpec(
            policy=PolicySpec("track_accuracy", {"k_max": 4}),
            n_frames=12,
            workload="track",
        ),
        SweepGrid(bandwidth_mbps=(0.5, 3.0), deadline_ms=(100.0, 200.0)),
    )
    yield (
        "track_fixed-fleet",
        ScenarioSpec(
            policy=PolicySpec("track_fixed", {"k": 3}),
            n_frames=10,
            fleet=FleetSpec(n_clients=2, capacity=2),
            workload="track",
        ),
        SweepGrid(bandwidth_mbps=(1.0, 4.0), deadline_ms=(150.0,)),
    )


CASES = {cid: (spec, grid) for cid, spec, grid in _cases()}
# The two jitted-local families compile in seconds and anchor the fast
# lane; the network-aware/fleet/track programs are multi-second compiles
# and certify chunk invariance in the slow (CI) matrix.
FAST_CASES = ("jax_accuracy", "jax_utility")


def _chunk_case(cid: str) -> None:
    spec, grid = CASES[cid]
    unchunked = Session(spec).run_sweep(grid, backend="batched")
    chunked = Session(spec).run_sweep(grid, backend="batched", chunk_size=3)
    assert unchunked.backend == chunked.backend == "batched"
    assert chunked.meta["chunks"] == -(-len(grid) // 3)
    _assert_det_equal(unchunked, chunked)
    # the incremental summary equals the fold over the kept points
    assert chunked.meta["summary"] == _fold(unchunked.points).to_json()


@pytest.mark.parametrize("cid", FAST_CASES)
def test_chunked_matches_unchunked_fast(cid):
    _chunk_case(cid)


@pytest.mark.slow
@pytest.mark.parametrize("cid", sorted(set(CASES) - set(FAST_CASES)))
def test_chunked_matches_unchunked(cid):
    _chunk_case(cid)


def test_streamed_summary_and_round_trip():
    spec, grid = CASES["jax_accuracy"]
    kept = Session(spec).run_sweep(grid, backend="batched", chunk_size=4)
    streamed = Session(spec).run_sweep(
        grid, backend="batched", chunk_size=4, keep_points=False
    )
    assert streamed.points == []
    assert streamed.meta["points_streamed"] == len(grid)
    assert streamed.meta["summary"] == kept.meta["summary"]
    summary = SweepSummary.from_json(streamed.meta["summary"])
    assert summary.n_points == len(grid)
    assert summary.frames_total == sum(
        s.frames_total for p in kept.points for s in p.streams
    )
    assert summary.best_point in [p.overrides for p in kept.points]
    # a summary-carrying report is still a lossless artifact
    rt = SweepReport.from_json(json.loads(json.dumps(streamed.to_json())))
    assert rt == streamed


def test_chunk_size_validation():
    spec, grid = CASES["jax_accuracy"]
    with pytest.raises(ValueError, match="chunk_size"):
        Session(spec).run_sweep(grid, chunk_size=0)


def test_reference_backend_chunks_too():
    spec = ScenarioSpec(policy=PolicySpec("local"), n_frames=6)
    grid = SweepGrid(bandwidth_mbps=(1.0, 2.5, 4.0))
    ref = Session(spec).run_sweep(grid)
    chunked = Session(spec).run_sweep(grid, chunk_size=2)
    assert chunked.backend == "reference"
    _assert_det_equal(ref, chunked)


def test_shard_kill_switch_is_identical(monkeypatch):
    spec, grid = CASES["jax_accuracy"]
    on = Session(spec).run_sweep(grid, backend="batched")
    monkeypatch.setenv("REPRO_SWEEP_SHARD", "0")
    off = Session(spec).run_sweep(grid, backend="batched")
    _assert_det_equal(on, off)


def _drop_executables():
    """Fresh-process simulation: drop every in-process executable, keep disk."""
    import jax

    from repro.core import sim_batch
    from repro.core.sweep_shard import _sharded_jit

    for name in dir(sim_batch):
        obj = getattr(sim_batch, name)
        if callable(getattr(obj, "cache_clear", None)):
            obj.cache_clear()
    _sharded_jit.cache_clear()
    jax.clear_caches()


def test_cached_reload_is_identical(compile_cache_dir):
    """Executables loaded from the persistent compilation cache must score
    identically to the ones XLA just built.  Regression for the donation
    hazard documented in core/sweep_shard.py: with ``donate_argnums`` set,
    cache-reloaded programs returned corrupted lanes."""
    from repro.core.compile_cache import CompileCounter, enable_compile_cache

    spec, grid = CASES["jax_accuracy"]
    assert enable_compile_cache() == str(compile_cache_dir)
    _drop_executables()  # earlier tests in this process must not pre-warm the cold run
    first = Session(spec).run_sweep(grid, backend="batched")
    assert any(compile_cache_dir.iterdir()), "the cold run wrote nothing to the cache"
    _drop_executables()
    with CompileCounter() as counter:
        reloaded = Session(spec).run_sweep(grid, backend="batched")
    assert counter.cache_hits > 0, "the reload compiled instead of loading from the cache"
    _assert_det_equal(first, reloaded)


def test_lane_program_rejects_interleaved_axes():
    from repro.core.sweep_shard import LaneProgram

    with pytest.raises(ValueError, match="lane args must lead"):
        LaneProgram(lambda a, b, c: a, (0, None, 0))


_SHARD_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
from repro.core import PolicySpec
from repro.session import ScenarioSpec, Session, SweepGrid

import jax
assert jax.device_count() == 4
from repro.launch.mesh import make_sweep_mesh
assert make_sweep_mesh().size == 4

spec = ScenarioSpec(policy=PolicySpec("jax_accuracy"), n_frames=12)
# 5 points: the mesh pads the 5-lane group to 8 — padding must be inert
grid = SweepGrid(deadline_ms=(10.0, 100.0, 150.0, 200.0, 350.0), fps=(30.0,))
sharded = Session(spec).run_sweep(grid, backend="batched")
os.environ["REPRO_SWEEP_SHARD"] = "0"
plain = Session(spec).run_sweep(grid, backend="batched")
fields = ("frames_total", "frames_processed", "frames_missed_deadline",
          "frames_offloaded", "accuracy_sum", "elapsed", "schedule_calls",
          "npu_busy_s")
for pa, pb in zip(sharded.points, plain.points):
    for f in fields:
        a, b = getattr(pa.stats, f), getattr(pb.stats, f)
        assert a == b, (pa.overrides, f, a, b)
print("SHARD_EQUIV_OK")
"""


@pytest.mark.slow
def test_sharded_groups_bit_identical_across_devices():
    """4 forced host devices: shard_map over the scenario mesh (with lane
    padding) must be bit-identical to the plain jitted program.  Needs a
    subprocess because XLA_FLAGS is read at jax import."""
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    env.pop("REPRO_SWEEP_SHARD", None)
    out = subprocess.run(
        [sys.executable, "-c", _SHARD_EQUIV],
        capture_output=True, text=True, timeout=600,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    assert "SHARD_EQUIV_OK" in out.stdout


def test_sweep_cli_chunked_summary(tmp_path, capsys, compile_cache_dir):
    from repro.session import main

    spec_file = tmp_path / "scenario.json"
    grid_file = tmp_path / "grid.json"
    spec = ScenarioSpec(policy=PolicySpec("local"), n_frames=6)
    spec_file.write_text(json.dumps(spec.to_json()))
    grid_file.write_text(json.dumps(SweepGrid(bandwidth_mbps=(1.0, 2.5, 4.0)).to_json()))
    assert main([
        "sweep", str(spec_file), "--grid", str(grid_file),
        "--chunk-size", "2", "--summary-only",
    ]) == 0
    report = SweepReport.from_json(json.loads(capsys.readouterr().out))
    assert report.points == []
    assert report.meta["chunks"] == 2
    assert report.meta["summary"]["n_points"] == 3
    assert report.meta["compile_cache"] == str(compile_cache_dir)
    assert compile_cache_dir.is_dir()
