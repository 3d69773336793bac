"""95th percentile of the host span round each planner round (``next_plan``)."""
from harness.stats import percentile


def read(run):
    d = run.spans.durations("plan")
    return 1e3 * percentile(d, 95) if d else None
