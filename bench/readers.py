"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader that finds nothing to read returns None, and the harness then
leaves its metric out of the line.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

# the kernels that score rows against queries (``repro.ops.<name>``)
SCORE_SCOPES = ("sq_l2", "l2", "coop_score_select")


def ooc_iter_ms(run) -> Optional[float]:
    """Engine time per spilled host-loop iteration: the host time of the
    window's engine calls over their ``OocStats.iterations``."""
    calls = [c for c in run.calls
             if getattr(c.result, "stats", None) is not None]
    iters = sum(c.result.stats.iterations for c in calls)
    if not iters:
        return None
    return 1e3 * sum(c.t1 - c.t0 for c in calls) / iters


def device_idle_pct(run) -> Optional[float]:
    """Share of the traced window in which no op ran on the device."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def total(run, field: str) -> int:
    """A per-lane counter of the engine's answers, summed over the
    window's calls (padded lanes included: their work is real)."""
    return int(sum(np.asarray(getattr(c.result, field)).sum()
                   for c in run.calls))
