"""Refinement loop: host ms per spilled host-loop iteration, timed
inside the program from the first to the last iteration of each engine
call (``OocStats.loop_s`` over ``OocStats.iterations``), in the
throughput cell. The in-program twin of ``ooc_iter_ms.tput``, which
times the whole engine call from outside."""

from bench.loopstats import per_iteration


def read(run):
    s = per_iteration(run, "loop_s")
    return None if s is None else 1e3 * s
