"""Seconds JAX spent tracing, lowering and compiling inside the window."""


def read(run):
    return run.compile_window_s
