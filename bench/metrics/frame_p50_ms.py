"""Median over the window's scheduled frames of answer time minus due time."""
from harness.stats import percentile


def read(run):
    if not run.latencies_s:
        return None
    return 1e3 * percentile(run.latencies_s, 50)
