"""Serving front: median queue wait of the window's answered tickets
(``queue_wait_ms`` on each ticket), in ms."""

import statistics


def read(run):
    waits = [r.entry["queue_wait_ms"] for r in run.answered]
    return statistics.median(waits) if waits else None
