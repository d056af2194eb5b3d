"""Refinement loop: host ms per spilled host-loop iteration spent
waiting on device->host reads (the ``ooc.sync`` spans, summed as
``OocStats.sync_s``), in the throughput cell."""

from bench.loopstats import per_iteration


def read(run):
    s = per_iteration(run, "sync_s")
    return None if s is None else 1e3 * s
