"""Spilled host loop: engine ms per ``OocStats.iterations``, in the
throughput cell."""

from bench.readers import ooc_iter_ms


def read(run):
    return ooc_iter_ms(run)
