"""Request batching for serving: buckets, deadlines, graceful degrade.

A lightweight continuous-batching front end: requests are bucketed by
prompt length (power-of-two buckets keep compiled shapes bounded), each
bucket drains as a uniform batch, and a per-request deadline maps onto
the paper's FULL guarantee taxonomy for the retrieval path
(:func:`guarantee_for_deadline`): a relaxed deadline gets the
deterministic epsilon guarantee, a moderate one degrades to the
probabilistic delta-epsilon tier (the paper's Fig. 8 regime — almost
always exact, bounded failure probability), and a tight one to
ng(nprobe) — precisely the paper's observation that the first
best-so-far answers are near-exact. That makes load shedding a
*quality* knob rather than a drop decision.

The retrieval front (:meth:`Scheduler.run_retrieval`) drives
``DistributedEngine.query`` — resident or out-of-core over spilled
shards, the engine decides — one query batch per guarantee group:
requests drained together but carrying different deadlines are
partitioned by their mapped guarantee (``retrieval_groups``), each
group padded to a power-of-two lane bucket so compiled batch shapes
stay bounded exactly like the prompt buckets.
"""

from __future__ import annotations

import dataclasses
import threading
from collections import defaultdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import obs
from repro.core.guarantees import Guarantee


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray          # [S] int32
    max_new_tokens: int = 16
    deadline_ms: Optional[float] = None
    # retrieval query in the engine's series space ([n] float); None =
    # this request wants no retrieval
    series: Optional[np.ndarray] = None
    # stamped on obs.now — THE one monotonic clock of the serving
    # stack (launch/serve.py subtracts it from the same clock for
    # queue-wait; mixing time.monotonic here with time.perf_counter
    # there made that subtraction incoherent)
    submitted_at: float = dataclasses.field(default_factory=obs.now)


def bucket_of(length: int, min_bucket: int = 16) -> int:
    b = min_bucket
    while b < length:
        b *= 2
    return b


def guarantee_for_deadline(
    deadline_ms: Optional[float], *, full_budget_ms: float = 50.0,
    delta_budget_frac: float = 0.5, nprobe_floor: int = 1,
    nprobe_ceil: int = 64, epsilon: float = 0.0,
    degraded_delta: float = 0.99, degraded_epsilon: float = 1.0,
) -> Guarantee:
    """Map a latency budget onto the paper's taxonomy (graceful
    degradation across ALL THREE knobs):

      deadline >= full budget (or none)   epsilon-guaranteed
                                          Guarantee(epsilon=epsilon)
      >= delta_budget_frac * full         delta-epsilon: probabilistic
                                          (degraded_delta,
                                          max(epsilon,
                                          degraded_epsilon))
      below that                          ng(nprobe), nprobe scaled
                                          linearly with the remaining
                                          fraction of the delta budget

    Every tier still returns an answer — the paper's Fig. 8 point that
    the first best-so-far is already near-exact is what makes the
    bottom tier acceptable."""
    if deadline_ms is None or deadline_ms >= full_budget_ms:
        return Guarantee(epsilon=epsilon)
    frac = max(deadline_ms, 1e-3) / full_budget_ms
    if frac >= delta_budget_frac:
        return Guarantee(delta=degraded_delta,
                         epsilon=max(epsilon, degraded_epsilon))
    sub = frac / delta_budget_frac
    nprobe = int(round(nprobe_floor
                       + sub * (nprobe_ceil - nprobe_floor)))
    return Guarantee(nprobe=max(nprobe_floor, nprobe))


def remaining_budget_ms(r: Request, at: float) -> Optional[float]:
    """The deadline budget a request has LEFT at time ``at`` (an
    ``obs.now`` stamp): ``deadline_ms`` minus the queue wait already
    spent. None (no deadline) stays None; a fully-spent budget clamps
    to ~0 and maps to the bottom ng tier instead of going negative."""
    if r.deadline_ms is None:
        return None
    waited_ms = (at - r.submitted_at) * 1e3
    return max(r.deadline_ms - waited_ms, 1e-3)


def retrieval_groups(
    reqs: Sequence[Request], at: Optional[float] = None, **gkw,
) -> List[Tuple[Guarantee, List[Request]]]:
    """Partition a drained batch by its deadline-mapped guarantee
    (insertion-ordered, deterministic): the engine takes ONE guarantee
    per query batch, so mixed-deadline batches fan out into one
    engine call per distinct guarantee.

    ``at`` (an ``obs.now`` stamp) switches the mapping from the
    SUBMITTED deadline to the budget REMAINING at drain time: a
    request that already burned 40ms of a 50ms budget in the queue
    maps from the 10ms it has left, not the tier it could have hit had
    it drained instantly. The drain loops pass their drain timestamp;
    the default (None) keeps this function pure for callers that want
    the submitted-deadline partition."""
    groups: Dict[Guarantee, List[Request]] = {}
    for r in reqs:
        budget = (r.deadline_ms if at is None
                  else remaining_budget_ms(r, at))
        g = guarantee_for_deadline(budget, **gkw)
        groups.setdefault(g, []).append(r)
    return list(groups.items())


class Scheduler:
    """Length-bucketed FIFO batching + the deadline-aware retrieval
    front.

    Queue state is lock-guarded (checked guarded_by annotations,
    docs/ANALYSIS.md): the async-serving ROADMAP item has submitters
    and the drain loop on different threads, so submit/next_batch must
    already be safe to interleave."""

    def __init__(self, max_batch: int = 8, min_bucket: int = 16):
        self.max_batch = max_batch
        self.min_bucket = min_bucket
        self._lock = threading.Lock()
        self.queues: Dict[int, List[Request]] = \
            defaultdict(list)                     # guarded_by: _lock
        self.completed: Dict[int, np.ndarray] = {}  # guarded_by: _lock

    def submit(self, req: Request):
        bucket = bucket_of(len(req.prompt), self.min_bucket)
        with self._lock:
            self.queues[bucket].append(req)

    def next_batch(self) -> Optional[Tuple[int, List[Request]]]:
        """Drain up to ``max_batch`` requests from the bucket whose HEAD
        request has waited longest. Draining buckets in sorted-key
        order (the old policy) starves large prompts: under sustained
        small-request load the smallest bucket never empties, so a
        request in a bigger bucket waits forever. Oldest-head-first is
        FIFO across buckets (each bucket is FIFO internally), so every
        bucket drains within one max_batch round of its head's turn."""
        with self._lock:
            best = None
            for bucket, q in self.queues.items():
                if q and (best is None
                          or q[0].submitted_at
                          < self.queues[best][0].submitted_at):
                    best = bucket
            if best is None:
                return None
            q = self.queues[best]
            take = q[: self.max_batch]
            self.queues[best] = q[len(take):]
            return best, take

    def pad_prompts(self, bucket: int, reqs: List[Request]) -> np.ndarray:
        out = np.zeros((len(reqs), bucket), np.int32)
        for i, r in enumerate(reqs):
            out[i, bucket - len(r.prompt):] = r.prompt  # left-pad
        return out

    # ---------------------------------------------- retrieval front
    def run_retrieval(
        self, engine, reqs: Sequence[Request], k: int, **gkw,
    ) -> Dict[int, Dict[str, Any]]:
        """Drive ``engine.query`` for a drained batch: group requests
        by their deadline-mapped guarantee (:func:`retrieval_groups`),
        pad each group's query lanes to a power-of-two bucket
        (duplicating the last row — extra lanes are discarded; bounds
        the compiled/retraced batch shapes), and issue one engine call
        per group. Requests without a ``series`` are skipped. Returns
        {uid: {ids, dists, guarantee, kind, retrieval_ms}} —
        ``retrieval_ms`` is the request's OWN guarantee group's engine
        time (each group is timed to completion separately), so
        per-request latency attribution never charges a request for
        another group's work. Group times also land in the registry
        as ``serve.retrieval_ms{kind=...}`` histograms.

        Guarantees are mapped from the budget REMAINING at drain time
        (``retrieval_groups(..., at=drain_stamp)``): queue wait spends
        the deadline, so a request that waited 40ms of a 50ms budget
        gets the tier its 10ms can still honor."""
        import jax.numpy as jnp

        out: Dict[int, Dict[str, Any]] = {}
        drained_at = obs.now()
        for g, group in retrieval_groups(
                [r for r in reqs if r.series is not None],
                at=drained_at, **gkw):
            qs = np.stack([np.asarray(r.series, np.float32)
                           for r in group])
            lanes = bucket_of(qs.shape[0], 1)
            if lanes > qs.shape[0]:
                qs = np.concatenate(
                    [qs, np.repeat(qs[-1:], lanes - qs.shape[0], 0)])
            with obs.span("serve.retrieval_group", kind=g.kind,
                          lanes=lanes, requests=len(group)):
                t0 = obs.now()
                res = engine.query(jnp.asarray(qs), k, g)
                # host copies block on the device result, so the group
                # time covers the full engine call
                with obs.span("serve.fetch"):
                    ids_np = np.asarray(res.ids)
                    dists_np = np.asarray(res.dists)
                group_ms = (obs.now() - t0) * 1e3
            obs.REGISTRY.histogram(
                "serve.retrieval_ms", kind=g.kind).record(group_ms)
            # fault-tolerant degrade (docs/FAULT.md): if the engine
            # lost shards past retries and replicas, the answer's
            # honest guarantee is delta-epsilon with the recomputed
            # effective_delta — surface that per request instead of
            # echoing the requested tier. Stats travel ON the result
            # (QueryResult.stats): reading mutable engine state here
            # misattributed degradation the moment lane workers ran
            # query() concurrently. getattr tolerates plain
            # SearchResult from stub engines in tests.
            stats = getattr(res, "stats", None)
            degraded = bool(stats is not None and stats.degraded)
            kind = "delta-epsilon" if degraded else g.kind
            if degraded:
                obs.REGISTRY.counter(
                    "serve.degraded", kind=g.kind).inc(len(group))
            for i, r in enumerate(group):
                entry: Dict[str, Any] = {
                    "ids": ids_np[i],
                    "dists": dists_np[i],
                    "guarantee": g,
                    "kind": kind,
                    "retrieval_ms": group_ms,
                    "stats": stats,
                }
                if degraded:
                    entry["degraded"] = True
                    entry["requested_kind"] = g.kind
                    entry["effective_delta"] = float(
                        stats.effective_delta)
                    entry["shards_lost"] = int(stats.shards_lost)
                out[r.uid] = entry
        return out
