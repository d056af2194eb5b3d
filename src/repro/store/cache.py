"""Fixed-size device leaf cache over a LeafStore.

A slot pool ``slots [S, max_leaf, payload_cols]`` lives on device in
the store's ENCODED payload dtype (f32/bf16 rows, or uint8 PQ codes for
codec="pq" — decoding happens in the scoring step, never here); the
host keeps the leaf->slot map and runs CLOCK (second-chance) eviction.
Each search iteration calls :meth:`get_slots` with the leaf batch it is
about to score; hits just set the reference bit, misses are read from
disk (through the prefetcher when one is attached), stacked into ONE
host buffer and uploaded with ONE donated scatter — the pool buffer is
reused in place (O(misses) work per iteration), and the h2d traffic per
iteration is a single [misses, max_leaf, payload_cols] transfer, never
a per-leaf trickle.

Counters (``stats()``) are the bench currency of the paper's on-disk
regime: disk bytes actually read, h2d bytes shipped, hit/miss counts,
and how many of the misses the prefetcher had already staged. Since
PR 6 every counter is REGISTRY-BACKED (repro.obs.metrics): each cache
owns labeled ``store.cache.*`` counters in the process-wide registry —
``reset_counters()`` starts a new per-query window via counter marks
(the attribute/``stats()`` views report the window, preserving the old
reset semantics bit-for-bit) while the registry keeps process-lifetime
totals, so per-query resets can never erase fleet-level accounting.
The same window values feed the typed ``OocStats`` schema and the span
tree (store/ooc.py), so the three views cannot drift.

Hits are counted PER REQUEST: every occurrence of a leaf in the
``get_slots`` batch that did not trigger a disk read is a hit — so when
many query lanes visit the same leaf (the regime cooperative scoring
targets) the hit rate credits each lane. ``hits_distinct`` keeps the
per-distinct view (leaves resident at batch start).
"""

from __future__ import annotations

import functools
import itertools
import threading
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.obs import REGISTRY

from .layout import LeafStore
from .prefetch import LeafPrefetcher

_cache_ids = itertools.count()


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_fill(slots, slot_ids, dev):
    """Donated in-place scatter of freshly read leaves into the pool.

    Donation is load-bearing: without it the whole [S, M, C] pool is
    copied every iteration — O(capacity) instead of O(misses)."""
    return slots.at[slot_ids].set(dev)


class DeviceLeafCache:
    def __init__(
        self,
        store: LeafStore,
        capacity_leaves: int,
        prefetcher: Optional[LeafPrefetcher] = None,
        name: Optional[str] = None,
    ):
        if capacity_leaves < 1:
            raise ValueError("capacity_leaves must be >= 1")
        self.store = store
        self.capacity = int(capacity_leaves)
        self.prefetcher = prefetcher
        self.name = name or f"cache{next(_cache_ids)}"
        m, c = store.max_leaf, store.payload_cols
        # the CLOCK state is lock-guarded (checked guarded_by
        # annotations, docs/ANALYSIS.md): the continuous-batching
        # ROADMAP item makes engine.query re-entrant, so concurrent
        # get_slots calls must see a consistent slot map. RLock —
        # get_slots holds it across _evict_one/_fill, which
        # re-acquire. Lock order (asserted by the obs lock-order
        # recorder in tests): cache._lock -> prefetcher._lock, never
        # the reverse.
        self._lock = threading.RLock()
        self.slots = jnp.zeros((self.capacity, m, c),
                               jnp.dtype(store.data_dtype))  # guarded_by: _lock
        self.slot_of: dict = {}      # leaf -> slot   # guarded_by: _lock
        self.owner = np.full(self.capacity, -1,
                             np.int64)                # guarded_by: _lock
        self.refbit = np.zeros(self.capacity, bool)   # guarded_by: _lock
        self.hand = 0                                 # guarded_by: _lock
        # registry-backed counters, windowed by reset_counters()
        lbl = {"cache": self.name}
        self._c_hits = REGISTRY.counter("store.cache.hits", **lbl)
        self._c_hits_distinct = REGISTRY.counter(
            "store.cache.hits_distinct", **lbl)
        self._c_misses = REGISTRY.counter("store.cache.misses", **lbl)
        self._c_bytes_read_sync = REGISTRY.counter(
            "store.cache.bytes_read_sync", **lbl)
        self._c_bytes_h2d = REGISTRY.counter(
            "store.cache.bytes_h2d", **lbl)
        self._c_prefetch_hits = REGISTRY.counter(
            "store.cache.prefetch_hits", **lbl)
        self._counters = (
            self._c_hits, self._c_hits_distinct, self._c_misses,
            self._c_bytes_read_sync, self._c_bytes_h2d,
            self._c_prefetch_hits)
        for ctr in self._counters:
            ctr.mark()  # a fresh cache starts a fresh window

    # windowed counter views (the pre-PR6 attribute surface)
    @property
    def hits(self) -> int:
        """Per-request: every non-read occurrence this window."""
        return self._c_hits.since_mark

    @property
    def hits_distinct(self) -> int:
        """Distinct leaves resident at batch start, this window."""
        return self._c_hits_distinct.since_mark

    @property
    def misses(self) -> int:
        """Distinct leaves read (disk or staged), this window."""
        return self._c_misses.since_mark

    @property
    def bytes_read_sync(self) -> int:
        """Demand-path disk reads only; total disk traffic = this +
        the attached prefetcher's bytes_read (stats())."""
        return self._c_bytes_read_sync.since_mark

    @property
    def bytes_h2d(self) -> int:
        """Padded slot bytes shipped to device, this window."""
        return self._c_bytes_h2d.since_mark

    @property
    def prefetch_hits(self) -> int:
        """Misses served from the prefetcher, this window."""
        return self._c_prefetch_hits.since_mark

    # ------------------------------------------------------------------
    def contains(self, leaf: int) -> bool:
        """True if the leaf is slot-resident right now (no side
        effects — unlike get_slots this neither touches the CLOCK
        reference bit nor counts a hit). The prefetch scheduler uses
        it to skip staging leaves that could never miss."""
        with self._lock:
            return int(leaf) in self.slot_of

    def _evict_one(self, pinned: set) -> int:
        """CLOCK: advance the hand, clearing reference bits, until an
        unpinned slot with refbit=0 comes up."""
        with self._lock:
            for _ in range(2 * self.capacity + 1):
                s = self.hand
                self.hand = (self.hand + 1) % self.capacity
                if s in pinned:
                    continue
                if self.refbit[s]:
                    self.refbit[s] = False
                    continue
                if self.owner[s] >= 0:
                    del self.slot_of[int(self.owner[s])]
                self.owner[s] = -1
                return s
        raise RuntimeError(
            f"cache thrash: all {self.capacity} slots pinned by one "
            "iteration; raise capacity_leaves above the per-iteration "
            "working set")

    def get_slots(self, leaves: Sequence[int]) -> np.ndarray:
        """Make every leaf resident; returns their slot numbers.

        ``leaves`` may contain duplicates (multiple query lanes visiting
        the same leaf) — each distinct leaf is read and uploaded once;
        every occurrence beyond the read counts as a (per-request) hit.

        The whole batch is one critical section: residency decisions,
        eviction, and the fill scatter happen under ``self._lock`` so
        a concurrent caller can never observe a slot map that points
        at not-yet-uploaded payload.
        """
        slots = np.empty(len(leaves), np.int64)
        with self._lock:
            pinned = {self.slot_of[lf] for lf in leaves
                      if lf in self.slot_of}
            miss_leaves: List[int] = []
            miss_slots: List[int] = []
            assigned: dict = {}
            for i, lf in enumerate(leaves):
                lf = int(lf)
                if lf in self.slot_of:
                    s = self.slot_of[lf]
                    # resident (or just filled earlier in this batch):
                    # served without a read -> per-request hit; only
                    # leaves resident BEFORE the batch count as
                    # distinct hits
                    self._c_hits.inc()
                    if lf not in assigned:
                        self._c_hits_distinct.inc()
                    self.refbit[s] = True
                    slots[i] = s
                    assigned.setdefault(lf, s)
                    continue
                s = self._evict_one(pinned)
                pinned.add(s)
                self.slot_of[lf] = s
                self.owner[s] = lf
                self.refbit[s] = True
                assigned[lf] = s
                self._c_misses.inc()
                miss_leaves.append(lf)
                miss_slots.append(s)
                slots[i] = s
            if miss_leaves:
                self._fill(miss_leaves, miss_slots)
        return slots

    def _fill(self, leaves: List[int], slot_ids: List[int]) -> None:
        m, c = self.store.max_leaf, self.store.payload_cols
        buf = np.zeros((len(leaves), m, c), self.store.data_dtype)
        with obs.span("store.read"):
            for j, lf in enumerate(leaves):
                staged = None
                if self.prefetcher is not None:
                    staged = self.prefetcher.take(lf)
                if staged is not None:
                    buf[j] = staged
                    self._c_prefetch_hits.inc()  # bytes already counted
                    #                              by the prefetcher thread
                else:
                    self.store.read_leaf(lf, out=buf[j])
                    self._c_bytes_read_sync.inc(
                        self.store.leaf_nbytes(lf))
        self._c_bytes_h2d.inc(buf.nbytes)  # real misses, not the pad
        with obs.span("store.h2d"):
            # pad the batch to the next power of two by REPEATING the
            # last row (idempotent duplicate scatter) so the jitted
            # scatter sees O(log capacity) distinct shapes instead of
            # one per miss count
            pad = 1 << (len(leaves) - 1).bit_length()
            ids_arr = np.empty(pad, np.int32)
            ids_arr[: len(leaves)] = slot_ids
            ids_arr[len(leaves):] = slot_ids[-1]
            if pad != len(leaves):
                buf = np.concatenate(
                    [buf, np.broadcast_to(buf[-1], (pad - len(leaves),)
                                          + buf.shape[1:])])
            with self._lock:
                self.slots = _scatter_fill(
                    self.slots, jnp.asarray(ids_arr), jnp.asarray(buf))

    # ------------------------------------------------------------------
    @property
    def bytes_read(self) -> int:
        """TOTAL disk bytes this cache caused: demand reads plus every
        byte the attached prefetcher read (including speculation for
        leaves that were never consumed) — each byte counted once."""
        pf = self.prefetcher.bytes_read if self.prefetcher else 0
        return self.bytes_read_sync + pf

    def reset_counters(self) -> None:
        """Start a fresh per-query measurement window (counter marks;
        the registry keeps the process-lifetime totals)."""
        for ctr in self._counters:
            ctr.mark()
        if self.prefetcher is not None:
            # quiesces first: a cold-pass read still in flight must not
            # land its bytes after the zeroing (bench_query_disk warm
            # stats would otherwise be polluted)
            self.prefetcher.reset_counters()

    def stats(self) -> dict:
        total = self.hits + self.misses
        distinct = self.hits_distinct + self.misses
        # repro: allow[stats-schema] pre-PR6 back-compat view of the SAME registry counters; search_ooc copies these fields into the typed OocStats field-for-field, so the two views cannot drift
        return {
            "capacity_leaves": self.capacity,
            "hits": self.hits,
            "hits_distinct": self.hits_distinct,
            "misses": self.misses,
            "hit_rate": self.hits / total if total else 0.0,
            "hit_rate_distinct":
                self.hits_distinct / distinct if distinct else 0.0,
            "bytes_read": self.bytes_read,
            "bytes_read_sync": self.bytes_read_sync,
            "bytes_h2d": self.bytes_h2d,
            "prefetch_hits": self.prefetch_hits,
        }
