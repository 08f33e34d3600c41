"""Backend compiles and compile-cache loads inside the window, from
``jax.monitoring``: there should be none."""


def read(run):
    return run.counter("compiles")
