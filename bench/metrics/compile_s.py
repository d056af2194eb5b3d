"""Engine: XLA backend-compile seconds inside the window
(``jax.monitoring``)."""


def read(run):
    return run.compile_s
