"""Refinement loop: device->host reads per spilled host-loop iteration
(``OocStats.host_syncs`` over ``OocStats.iterations``)."""

from bench.loopstats import per_iteration


def read(run):
    return per_iteration(run, "host_syncs")
