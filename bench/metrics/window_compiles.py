"""Device: XLA backend compiles plus persistent-cache loads inside the
measured window (``jax.monitoring``)."""


def read(run):
    return run.window_compiles
