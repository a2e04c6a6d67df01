"""Seconds JAX spent lowering and compiling (persistent-cache reads
included) before the window opened, from its own compile events."""


def read(run):
    return run.setup_compile_s
