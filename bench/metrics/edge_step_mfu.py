"""bf16 GEMM operations of the served edge rows (padding excluded), over
the summed host spans of ``EdgeBatchServer.flush``, as a share of the
chip's bf16 peak."""
from harness import work


def read(run):
    busy = run.spans.total("edge_flush")
    if not run.edge_frames or busy <= 0:
        return None
    ops = run.edge_frames * work.total_ops(run.gemms)
    return 100.0 * ops / busy / run.peaks["bf16_flops_per_s"]
