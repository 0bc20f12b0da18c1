"""Backend compiles inside the measured window (JAX monitoring)."""


def read(run):
    return run.compiles
