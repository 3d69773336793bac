"""int8 GEMM operations of the frames the NPU path served, over the summed
host spans of the NPU endpoint calls, as a share of the chip's int8 peak."""
from harness import work


def read(run):
    busy = run.spans.total("npu_call")
    if not run.npu_frames or busy <= 0:
        return None
    ops = run.npu_frames * work.total_ops(run.gemms)
    return 100.0 * ops / busy / run.peaks["int8_ops_per_s"]
