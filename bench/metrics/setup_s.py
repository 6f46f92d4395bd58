"""Seconds from the start of the process to the start of the window:
imports, weights, planning, compiles and the warm-up segments."""


def read(run):
    return run.setup_s
