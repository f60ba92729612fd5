"""Executables JAX built (compiled, or loaded from the compilation cache)
inside the window, from its backend compile events. Should read 0."""


def read(run):
    return float(run.compiles)
