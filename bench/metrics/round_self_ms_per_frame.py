"""Self time of the program's ``round`` spans (each planner horizon less the
spans inside it: planning, copies, forwards, resizes, flushes), summed over
the window and divided by the frames answered: the loop's own host work."""
from harness import program


def read(run):
    total = program.self_ms(run, "round")
    if total is None or not run.answered:
        return None
    return total / run.answered
