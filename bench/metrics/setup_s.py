"""Set-up: process start to the first frame of the window (loading, weights,
compiling or loading compiled programs, warm-up)."""


def read(run):
    return run.setup_seconds
