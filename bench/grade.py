"""How ``correct`` is decided: every answer of the window against the
reference.

Numbers compared, each against its limit in ``bench/limits/<cell>.json``:

  errors          answers that came back as an error entry
  unanswered      admitted requests whose answer never came
  off_lane        answers served on another lane than the traffic
                  file's ``guarantee`` names (its ``kind``, and each
                  number it gives: ``nprobe`` for ng, ``epsilon``), shed
                  or degraded
  bad_answers     answers with a wrong shape, an id out of range or
                  missing, a repeated id, a non-finite distance, or
                  distances out of order
  dist_err_sq     the largest gap, over all answers, between a reported
                  squared distance and the float64 squared distance of
                  the id it is reported for
  eps_violations  (epsilon lane) answers holding a row farther than
                  (1 + epsilon) times the true k-th neighbour, beyond the
                  rounding that dist_err_sq admits

``recall`` (mean recall@k against the reference) is a metric, not a
check.
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence

import numpy as np

from .reference import TopK, true_sq


def grade(records: Sequence, pool: np.ndarray, data: np.ndarray,
          ref: Dict[int, int], top: TopK, k: int,
          guarantee: Mapping[str, object], limits: Dict[str, float]) -> dict:
    """``records`` are the window's :class:`bench.load.Record`; ``ref``
    maps a pool index to its row in ``top``; ``guarantee`` is the lane
    the traffic asks for. Returns the compared numbers, the recall and
    the answered count."""
    kind = guarantee["kind"]
    numbers = {n: v for n, v in guarantee.items() if n != "kind"}
    epsilon = float(numbers.get("epsilon", 0.0))
    n_rows = data.shape[0]
    errors = unanswered = off_lane = bad = eps_bad = 0
    worst = 0.0
    recalls: List[float] = []
    slack = limits["dist_err_sq"]
    for rec in records:
        if rec.rejected is not None:
            continue
        e = rec.entry
        if e is None:
            unanswered += 1
            continue
        if "error" in e:
            errors += 1
            continue
        stats = e.get("stats")
        if (e.get("kind") != kind or e.get("shed") or e.get("degraded")
                or any(getattr(e.get("guarantee"), name, None) != value
                       for name, value in numbers.items())
                or (stats is not None and getattr(stats, "degraded", False))):
            off_lane += 1
        ids = np.asarray(e["ids"])
        dists = np.asarray(e["dists"], np.float64)
        if (ids.shape != (k,) or dists.shape != (k,)
                or not np.isfinite(dists).all()
                or ((ids < 0) | (ids >= n_rows)).any()
                or len(set(ids.tolist())) != k
                or (np.diff(dists) < 0).any()):
            bad += 1
            continue
        q = pool[rec.query]
        tsq = true_sq(data, q, ids)
        worst = max(worst, float(np.abs(dists ** 2 - tsq).max()))
        r = ref[rec.query]
        if kind == "epsilon" and (
                tsq > (1 + epsilon) ** 2 * top.d2[r, k - 1] + slack).any():
            eps_bad += 1
        recalls.append(len(set(ids.tolist()) & set(top.ids[r].tolist())) / k)
    checks = {"errors": errors, "unanswered": unanswered,
              "off_lane": off_lane, "bad_answers": bad,
              "dist_err_sq": worst}
    if kind == "epsilon":
        checks["eps_violations"] = eps_bad
    return {"checks": checks,
            "correct": all(v <= limits[name] for name, v in checks.items()),
            "recall": float(np.mean(recalls)) if recalls else float("nan"),
            "graded": len(recalls)}
