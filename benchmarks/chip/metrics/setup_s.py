"""Seconds from the start of the process to the end of the warm-up: JAX
and TPU start-up, inputs made on the device, compiles or compile-cache
loads, the kernel autotune sweep and the warm-up jobs."""


def read(run):
    return run.setup_s
