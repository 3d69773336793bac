"""Least time of the NPU frames' GEMMs (the larger of int8 operations over
the int8 peak and bytes over HBM bandwidth, per GEMM, at unpadded shapes)
over the device time of the ``npu_matmul`` kernel's events in the trace."""
from harness import work

# the kernel's instructions in a TPU trace: the pallas_call takes its name
# from kernels/npu_matmul/kernel.py's jitted ``int8_matmul``
KERNEL = r"^int8_matmul(\.\d+)?$"


def read(run):
    tr = run.device_trace
    if tr is None or not run.traced_npu_frames:
        return None
    seconds = tr.seconds_matching(KERNEL)
    if seconds <= 0:
        return None
    return 100.0 * run.traced_npu_frames * work.int8_least_s(run.gemms, run.peaks) / seconds
