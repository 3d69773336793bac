"""Median of the program's ``edge.sync`` span: per edge batch, the wait for
the bf16 forward's result and the copy of its logits to the host."""
from harness import program


def read(run):
    return program.p50_ms(run, "edge.sync")
