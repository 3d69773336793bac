"""Compiles the program recorded inside the measured window: a compile
there is a shape that set-up did not warm."""
from harness import program


def read(run):
    return program.count(run, "compile")
