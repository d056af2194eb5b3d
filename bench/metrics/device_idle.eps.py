"""Device: idle share of the traced window in %, in the epsilon
cell."""

from bench.readers import device_idle_pct


def read(run):
    return device_idle_pct(run)
