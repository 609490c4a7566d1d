"""Seconds of XLA backend compilation in set-up, summed from JAX's
``backend_compile_duration`` events; near 0 when every program came
from the persistent cache."""


def read(run):
    return run.compile_s
