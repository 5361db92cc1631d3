"""setup_s (s): from the process's start to the first timed request:
interpreter and JAX start, the cell's data, and the warm-up of its shapes
(compilation, or loading from the compile cache)."""


def read(run):
    return run.setup_s
