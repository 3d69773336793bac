"""Frames answered over the whole window's time."""
from harness.stats import rate


def read(run):
    return rate(run.answered, run.window_s)
