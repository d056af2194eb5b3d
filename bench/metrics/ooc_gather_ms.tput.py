"""Storage: host ms per spilled host-loop iteration spent making the
iteration's leaves cache-resident (the ``ooc.gather`` spans, summed as
``OocStats.gather_s``: disk or prefetcher reads, the padded upload and
the slot map), in the throughput cell."""

from bench.loopstats import per_iteration


def read(run):
    s = per_iteration(run, "gather_s")
    return None if s is None else 1e3 * s
