"""Median of the program's ``npu.sync`` span: the wait for the int8
forward's result and the copy of its logits to the host."""
from harness import program


def read(run):
    return program.p50_ms(run, "npu.sync")
