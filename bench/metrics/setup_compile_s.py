"""Seconds JAX spent tracing, lowering and compiling during set-up."""


def read(run):
    return run.compile_setup_s
