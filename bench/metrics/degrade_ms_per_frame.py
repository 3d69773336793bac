"""The program's ``offload.degrade`` spans (the resize of each offloaded
frame to its resolution) summed over the window, per frame answered."""
from harness import program


def read(run):
    d = program.durations_ms(run, "offload.degrade")
    if not d or not run.answered:
        return None
    return sum(d) / run.answered
