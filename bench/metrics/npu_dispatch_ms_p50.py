"""Median of the program's ``npu.dispatch`` span: the int8 forward's call
until it returns, before the device's result is waited for."""
from harness import program


def read(run):
    return program.p50_ms(run, "npu.dispatch")
