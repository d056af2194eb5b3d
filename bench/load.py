"""The one general load generator: an open loop over a front.

A traffic file (``bench/traffic/<name>.json``) gives its parameters;
this module holds the only code that offers load. Requests arrive at a
fixed Poisson rate whether or not earlier ones were answered: below the
knee, as independent users do; above it, as a backlog that keeps every
batch full. Each request is timed on the benchmark's own clock
(``time.perf_counter``) from the moment it was due, so a stall of the
submitter or of the front is charged to every request it delays.
Rejected, failed and unanswered requests are kept as records with no
answer; the metrics count them as missing.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, List, Optional

import numpy as np

clock = time.perf_counter

# how long an answer is waited for, from its due time or from the answer
# before it, whichever is later, before the request counts as never
# answered: a backlog that is still being served is late, not lost
ANSWER_WAIT_S = 60.0


@dataclasses.dataclass
class Record:
    """One request of the window."""
    uid: int
    query: int                     # index into the query pool
    due: float                     # clock: when it was due
    sent: float = math.nan         # clock: when submit() returned
    done: float = math.nan         # clock: when its answer was seen
    entry: Optional[dict] = None   # the ticket's answer
    rejected: Optional[str] = None  # admission's reason

    @property
    def answered(self) -> bool:
        return self.entry is not None and "error" not in self.entry


@dataclasses.dataclass
class Window:
    start: float                   # first request due
    close: float                   # last answer (or the last due time)
    records: List[Record]
    lateness_s: List[float]        # open loop: sent - due, per request

    @property
    def seconds(self) -> float:
        return self.close - self.start


def poisson_schedule(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets (s) of an open loop at ``rate`` per second over
    ``seconds``: the gaps are the quantiles of the exponential law at
    that rate, shuffled by the seed, so every seed offers the same
    number of requests over the same span, in another order."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 11])
    gaps = rng.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def query_order(pool: int, seed: int) -> np.ndarray:
    """The order in which the pool's queries are asked, from the seed."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 13])
    return rng.permutation(pool)


def _submit(front, make_request, rec: Record, rejected_cls):
    try:
        ticket = front.submit(make_request(rec))
    except rejected_cls as e:
        rec.rejected = e.reason
        ticket = None
    rec.sent = clock()
    return ticket


def open_loop(front, make_request: Callable[[Record], object],
              order: np.ndarray, offsets: np.ndarray, rejected_cls,
              annotate=None) -> Window:
    """Send request i at ``start + offsets[i]`` whether or not earlier
    ones were answered. One collector thread waits for the answers in
    the order they were sent (one lane answers first in, first out, so
    each answer is seen as it lands). The window closes with the last
    answer: above the knee that includes serving the backlog left at
    the last due time."""
    records = [Record(uid=i, query=int(order[i % len(order)]), due=0.0)
               for i in range(len(offsets))]
    tickets: List = [None] * len(records)
    sent = threading.Semaphore(0)
    lateness: List[float] = []

    def collect():
        last = -math.inf
        for i, rec in enumerate(records):
            sent.acquire()
            tk = tickets[i]
            if tk is not None:
                remaining = max(max(rec.due, last) + ANSWER_WAIT_S
                                - clock(), 1.0)
                try:
                    rec.entry = tk.result(timeout=remaining)
                    last = clock()
                except TimeoutError:
                    rec.entry = None
            rec.done = clock()

    collector = threading.Thread(target=collect, name="bench-collector")
    collector.start()
    ctx = annotate("bench.window") if annotate else None
    if ctx:
        ctx.__enter__()
    start = clock()
    try:
        for i, rec in enumerate(records):
            rec.due = start + float(offsets[i])
            wait = rec.due - clock()
            if wait > 0:
                time.sleep(wait)
            tickets[i] = _submit(front, make_request, rec, rejected_cls)
            lateness.append(rec.sent - rec.due)
            sent.release()
    finally:
        # a submitter that failed part-way still lets the collector end
        for _ in range(len(records) - len(lateness)):
            sent.release()
        collector.join()
        if ctx:
            ctx.__exit__(None, None, None)
    close = max([r.done for r in records if r.entry is not None]
                + [records[-1].due])
    return Window(start=start, close=close, records=records,
                  lateness_s=lateness)
