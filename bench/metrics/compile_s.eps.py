"""Engine: XLA backend-compile seconds inside the window
(``jax.monitoring``), in the epsilon cell."""


def read(run):
    return run.compile_s
