"""Async double-buffered host-side leaf prefetcher.

The out-of-core search loop knows, while the device is scoring
iteration t's leaves, exactly which leaves iteration t+1 will want
(each query's next ranks in its lb visit order, assuming it stays
active). ``schedule()`` hands that set to a daemon thread which reads
the leaves from the memmap into padded host buffers; ``take()`` pops a
staged buffer on the demand path. The staging area is bounded to
``depth`` scheduled batches ("double-buffered" at the default depth=2),
so a query that stops early wastes at most ``depth`` batches of reads.

The prefetcher only READS (memmap -> host buffer). The device upload
stays in DeviceLeafCache._fill, which already batches one scatter per
iteration; overlapping h2d as well would need per-slot donation and
buys little on top of overlapping the disk latency, which dominates.
"""

from __future__ import annotations

import collections
import itertools
import threading
import warnings
from typing import Optional, Sequence

import numpy as np

from repro.obs import REGISTRY, now

from .layout import LeafStore

_prefetcher_ids = itertools.count()


class LeafPrefetcher:
    def __init__(self, store: LeafStore, depth: int = 2,
                 name: Optional[str] = None):
        self.store = store
        self.depth = int(depth)
        self.name = name or f"prefetch{next(_prefetcher_ids)}"
        # every shared field below is annotated guarded_by and the
        # annotation is CHECKED: python -m repro.analysis enforces
        # that all access outside __init__ sits in `with self._lock:`
        # (docs/ANALYSIS.md — this class is where the old "mutated
        # ONLY under self._lock" comment lived unchecked)
        self._lock = threading.Condition()
        self._queue: collections.deque = \
            collections.deque()                   # guarded_by: _lock
        self._staged: "collections.OrderedDict[int, np.ndarray]" = \
            collections.OrderedDict()             # guarded_by: _lock
        self._inflight: set = set()               # guarded_by: _lock
        self._wanted: set = set()                 # guarded_by: _lock
        self._batches_staged: collections.deque = \
            collections.deque()                   # guarded_by: _lock
        self._stop = False                        # guarded_by: _lock
        self._dead = False                        # guarded_by: _lock
        # leaf mid-read right now:
        self._reading: Optional[int] = None       # guarded_by: _lock
        # counters: mutated ONLY under self._lock (the reader thread
        # races reset_counters otherwise — a straggler cold-pass read
        # landing after the reset would pollute warm-run stats); the
        # epoch stamps each read with its measurement window so even a
        # read that outlives reset_counters' quiesce timeout cannot
        # leak its bytes into the next window. Since PR 6 the counters
        # are registry-backed (store.prefetch.* in repro.obs.REGISTRY):
        # reset_counters() starts a window via marks, the registry
        # keeps the process-lifetime totals.
        self._epoch = 0                           # guarded_by: _lock
        lbl = {"prefetch": self.name}
        self._c_bytes_read = REGISTRY.counter(
            "store.prefetch.bytes_read", **lbl)
        self._c_leaves_read = REGISTRY.counter(
            "store.prefetch.leaves_read", **lbl)
        # deadline expiries (take/reset_counters) and close() leaks
        # are SURFACED, not swallowed: a silently slow disk shows up
        # here first (docs/OBSERVABILITY.md)
        self._c_quiesce_take = REGISTRY.counter(
            "store.prefetch.quiesce_timeout", site="take", **lbl)
        self._c_quiesce_reset = REGISTRY.counter(
            "store.prefetch.quiesce_timeout", site="reset", **lbl)
        self._c_close_leaked = REGISTRY.counter(
            "store.prefetch.close_leaked", **lbl)
        # a reader that died on an I/O error leaves the cache on demand
        # reads only; counted so a caller can tell it happened
        self._c_died = REGISTRY.counter("store.prefetch.died", **lbl)
        self._c_bytes_read.mark()
        self._c_leaves_read.mark()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    @property
    def bytes_read(self) -> int:
        """Disk bytes read this window (includes speculative reads)."""
        return self._c_bytes_read.since_mark

    @property
    def leaves_read(self) -> int:
        return self._c_leaves_read.since_mark

    # ------------------------------------------------------------------
    def schedule(self, leaves: Sequence[int]) -> None:
        """Stage a predicted future leaf batch (speculative). Callers
        with frontier lookahead schedule several batches per iteration
        (nearest window first — it is read first)."""
        batch = list(dict.fromkeys(int(x) for x in leaves))
        with self._lock:
            # bound the staging area: drop the oldest whole batch(es)
            while len(self._batches_staged) >= self.depth:
                self._batches_staged.popleft()
            todo = [lf for lf in batch
                    if lf not in self._staged and lf not in self._inflight]
            self._batches_staged.append(batch)
            # keep every structure bounded to the LIVE batches: a leaf
            # no longer in any tracked batch is dropped from the
            # staging dict and the read queue and (if mid-read) its
            # completion is discarded. Membership is tested against
            # the UNION of live batches, never per dropped batch —
            # overlapping windows (the frontier-lookahead regime
            # re-schedules next iteration's window every iteration)
            # must not have their staged buffers destroyed by an old
            # batch's eviction, which would force a duplicate read.
            self._wanted = set()
            for bt in self._batches_staged:
                self._wanted.update(bt)
            for lf in [s for s in self._staged if s not in self._wanted]:
                del self._staged[lf]
            self._queue = collections.deque(
                lf for lf in self._queue if lf in self._wanted)
            self._inflight &= self._wanted
            self._inflight.update(todo)
            self._queue.extend(todo)
            self._lock.notify_all()

    def take(self, leaf: int,
             timeout: float = 10.0) -> Optional[np.ndarray]:
        """Pop a staged leaf buffer; None if this leaf was never
        scheduled (or was dropped / the thread died).

        A leaf still queued or in flight is WAITED for: the thread is
        reading it right now (or is about to), so waiting costs at most
        the tail of one batch of reads, whereas returning None would
        make the caller issue a duplicate synchronous read of bytes the
        prefetcher already paid for. The prefetcher remains a pure
        overlap optimization, never a correctness dependency — every
        None falls back to a sync read in the cache.

        Stop/dead Nones are expected teardown; a DEADLINE expiry means
        the disk is slower than the timeout and the miss silently
        doubles the read — so expiries are surfaced
        (``store.prefetch.quiesce_timeout{site=take}`` + a warning)
        instead of vanishing into the fallback.
        """
        leaf = int(leaf)
        deadline = now() + timeout
        with self._lock:
            while True:
                if leaf in self._staged:
                    return self._staged.pop(leaf)
                if leaf not in self._inflight and leaf not in self._queue:
                    return None
                if self._stop or self._dead:
                    return None
                remaining = deadline - now()
                if remaining <= 0:
                    self._c_quiesce_take.inc()
                    warnings.warn(
                        f"prefetcher {self.name}: take({leaf}) gave "
                        f"up after {timeout:.1f}s with the read "
                        "still pending — the caller falls back to a "
                        "duplicate sync read (slow disk?)",
                        RuntimeWarning, stacklevel=2)
                    return None
                self._lock.wait(remaining)

    def reset_counters(self, timeout: float = 10.0) -> None:
        """Zero the read counters for a fresh measurement window.

        Quiesces first: queued (not yet started) speculative reads are
        dropped, and an in-flight read is WAITED for — so no byte read
        on behalf of the previous window can land after the zeroing.
        Even if the wait times out (pathologically slow disk), the
        epoch bump makes the straggler's completion drop its counter
        update, so the new window still starts clean.
        """
        deadline = now() + timeout
        with self._lock:
            for lf in self._queue:
                self._inflight.discard(lf)
            self._queue.clear()
            while self._reading is not None and not self._dead:
                remaining = deadline - now()
                if remaining <= 0:
                    # the epoch bump below still keeps the window
                    # clean, but a quiesce that cannot finish inside
                    # the timeout is a slow-disk signal the operator
                    # must see, not an implementation detail
                    self._c_quiesce_reset.inc()
                    warnings.warn(
                        f"prefetcher {self.name}: reset_counters "
                        f"quiesce timed out after {timeout:.1f}s "
                        f"with leaf {self._reading} mid-read; the "
                        "epoch guard keeps the new window clean",
                        RuntimeWarning, stacklevel=2)
                    break
                self._lock.wait(remaining)
            self._epoch += 1
            self._c_bytes_read.mark()
            self._c_leaves_read.mark()

    def close(self, timeout: float = 5.0) -> None:
        """Stop the reader thread and join it. A thread that outlives
        the join timeout (wedged in a read syscall) is REPORTED —
        ``store.prefetch.close_leaked`` counter + warning — instead of
        leaking silently; it is a daemon thread, so the report is
        about the wedged I/O, not process shutdown."""
        with self._lock:
            self._stop = True
            self._lock.notify_all()
        self._thread.join(timeout=timeout)
        if self._thread.is_alive():
            self._c_close_leaked.inc()
            warnings.warn(
                f"prefetcher {self.name}: reader thread still alive "
                f"{timeout:.1f}s after close() — wedged in a read? "
                "(daemon thread; it cannot block exit, but its memmap "
                "stays open)", RuntimeWarning, stacklevel=2)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    # ------------------------------------------------------------------
    def _run(self) -> None:
        try:
            while True:
                with self._lock:
                    while not self._queue and not self._stop:
                        self._lock.wait()
                    if self._stop:
                        return
                    leaf = self._queue.popleft()
                    self._reading = leaf
                    epoch = self._epoch
                buf = self.store.read_leaf(leaf)
                nbytes = self.store.leaf_nbytes(leaf)
                with self._lock:
                    self._inflight.discard(leaf)
                    self._reading = None
                    if not self._stop and leaf in self._wanted:
                        self._staged[leaf] = buf
                    if epoch == self._epoch:  # not reset mid-read
                        self._c_bytes_read.inc(nbytes)
                        self._c_leaves_read.inc()
                    self._lock.notify_all()
        except Exception:  # I/O failure: unblock waiters, go demand-only
            self._c_died.inc()
            with self._lock:
                self._dead = True
                self._reading = None
                self._inflight.clear()
                self._queue.clear()
                self._lock.notify_all()
