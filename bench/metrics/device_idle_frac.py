"""Share of the traced window in which no operation ran on the device."""


def read(run):
    tr = run.device_trace
    if tr is None:
        return None
    return 1.0 - tr.busy_s / tr.window_s
