"""Arithmetic of the readers of the spilled host loop's in-program
counters (``OocStats.loop_s``, ``gather_s``, ``sync_s``,
``host_syncs``, taken by the program from the stamps of its
``ooc.*`` spans)."""

from __future__ import annotations

from typing import Optional


def per_iteration(run, field: str) -> Optional[float]:
    """``field`` summed over the window's spilled engine calls, over
    their ``OocStats.iterations``; None where no call's stats carry the
    field (a program without the counter) or no call iterated."""
    st = [s for s in run.stats() if hasattr(s, field)]
    iters = sum(s.iterations for s in st)
    return sum(getattr(s, field) for s in st) / iters if iters else None
