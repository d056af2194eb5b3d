"""Kernels: roofline share of the scoring kernels (the
``repro.ops.sq_l2``, ``l2`` and ``coop_score_select`` scopes) in %.

Work is 2 x series length FLOPs per row scored (``QueryResult.
rows_scanned`` over the window's engine calls), the least any
implementation of exact scoring needs. The counters give rows per lane,
not distinct rows per call, so the bytes term is left out: the share is
of the compute bound, at the chip's bf16 peak."""

from bench.readers import SCORE_SCOPES, total


def read(run):
    if run.trace is None or not run.peaks:
        return None
    busy = sum(run.trace.scope_s.get(s, 0.0) for s in SCORE_SCOPES)
    rows = total(run, "rows_scanned")
    if busy <= 0 or rows <= 0:
        return None
    least = 2.0 * run.series_len * rows / run.peaks["flops_bf16"]
    return 100.0 * least / busy
