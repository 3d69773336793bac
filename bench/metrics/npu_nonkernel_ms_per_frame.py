"""Device time of one NPU frame spent outside the ``npu_matmul`` kernel: the
traced window's busy device time less the kernel's, over the NPU frames
served in it, in ms.  In a cell whose frames all take the int8 path, all
device work is the NPU forward: around the kernel that is im2col and
relayouts in the convnets, and window attention, norms, GELU, window
partition and roll, and weight conversions in Swin."""

# the kernel's instructions in a TPU trace, as npu_matmul_roofline finds them
KERNEL = r"^int8_matmul(\.\d+)?$"


def read(run):
    tr = run.device_trace
    if tr is None or not run.traced_npu_frames:
        return None
    return 1e3 * (tr.busy_s - tr.seconds_matching(KERNEL)) / run.traced_npu_frames
