"""Continuous-batching serving front: per-guarantee lanes, no barrier.

The static front (:func:`repro.launch.serve.serve_requests`) drains
one batch, answers it to completion, then drains the next — a global
barrier: a cheap ng query drained alongside an expensive epsilon group
waits for the whole round. :class:`ServeFront` replaces that with the
refill-as-you-finish idiom from modern inference stacks (the maxtext
continuous-batching loop the ROADMAP cites):

  lanes     requests are routed by their NOMINAL guarantee kind
            (mapped from the submitted deadline) into one of three
            lanes — ``epsilon`` (also hosting ``exact``),
            ``delta-epsilon``, ``ng``. Each lane has its own worker
            thread draining up to ``max_batch`` requests at a time, so
            an expensive epsilon batch in flight never blocks the ng
            lane from refilling — the barrier is gone.
  remap     at DRAIN time each request's guarantee is recomputed from
            its remaining deadline budget
            (:func:`repro.serve.batching.retrieval_groups` with
            ``at=drain_stamp``): queue wait spends the budget, so the
            tier a request gets is the tier its remaining time can
            honor.
  shed      while the :class:`repro.serve.admission.AdmissionController`
            reports sustained pressure, each drained group is degraded
            one further tier (quality knob, not a drop — docs/SERVING.md).
  admission past the depth cap, submit() rejects with a reason instead
            of queueing into a guaranteed deadline miss.

Each engine call is one ``engine.query`` per (lane-batch x remapped
guarantee) group, lanes padded to a power of two exactly like the
static front. Concurrent calls are safe and bit-exact vs serial
execution: stats travel on the result (``QueryResult.stats``), and
per-shard cache state is serialized by the engine's per-copy locks
(core/engine.py) — the re-entrancy contract this front forced.

Thread-safety: lane deques are guarded by one condition
(``# guarded_by: _cond``); completion is per-ticket (an Event), so
submitters wait on their own request only. Lock order: the front's
condition is released BEFORE ``engine.query`` runs, so front-lock ->
engine-lock edges never form while a worker holds the condition —
``obs.lockorder`` verifies acyclicity in the stress test.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.guarantees import Guarantee

from .admission import AdmissionController
from .batching import (Request, bucket_of, guarantee_for_deadline,
                       retrieval_groups)

__all__ = ["LANES", "Rejected", "ServeFront", "Ticket", "WRITE_LANE",
           "lane_of"]

LANES = ("epsilon", "delta-epsilon", "ng")
# the WRITE lane (docs/INGEST.md): mutations ride their own worker so
# a burst of inserts never queues behind an expensive epsilon batch —
# and vice versa. Writes are O(rows) memtable updates (store/delta.py),
# not engine queries, so the lane needs no admission slot: admission
# protects retrieval deadlines, which writes cannot miss.
WRITE_LANE = "write"


def lane_of(kind: str) -> str:
    """Lane routing: ``exact`` rides the ``epsilon`` lane (same cost
    regime — guarantee-driven visits), the other kinds get their own."""
    return "epsilon" if kind == "exact" else kind


class Rejected(RuntimeError):
    """submit() refused by admission control; ``reason`` says why."""

    def __init__(self, reason: str):
        super().__init__(f"request rejected: {reason}")
        self.reason = reason


class Ticket:
    """A submitted request's completion handle: ``result()`` blocks
    until the lane worker answers (or errors), then returns the entry
    dict ({ids, dists, kind, guarantee, retrieval_ms, queue_wait_ms,
    latency_ms, done_at, ...} — or {"error": ...})."""

    __slots__ = ("uid", "_event", "_entry")

    def __init__(self, uid: int):
        self.uid = uid
        self._event = threading.Event()
        self._entry: Optional[Dict[str, Any]] = None

    def done(self) -> bool:
        return self._event.is_set()

    def _complete(self, entry: Dict[str, Any]) -> None:
        self._entry = entry
        self._event.set()

    def result(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.uid} not answered within {timeout}s")
        assert self._entry is not None
        return self._entry


class ServeFront:
    """The continuous-batching retrieval front (module docstring).

    Construct over a built engine, ``start()`` (or use as a context
    manager), ``submit(Request)`` from any number of threads, read
    answers via the returned :class:`Ticket`. ``stop(drain=True)``
    answers everything queued before returning; ``drain=False``
    completes pending tickets with an error entry instead.

    ``lock_recorder`` (an ``obs.LockOrderRecorder``) wraps the front's
    condition lock so stress tests can assert the full lane+engine
    lock graph stays acyclic.

    ``ooc_opts`` is forwarded to every ``engine.query`` call (the
    out-of-core knobs, e.g. ``share_gathers``); None passes nothing.
    """

    def __init__(self, engine, k: int = 5, *, max_batch: int = 8,
                 admission: Optional[AdmissionController] = None,
                 guarantee_kw: Optional[dict] = None,
                 ooc_opts: Optional[dict] = None,
                 lock_recorder=None):
        self.engine = engine
        self.k = k
        self.max_batch = max_batch
        self.admission = admission or AdmissionController()
        self.gkw = dict(guarantee_kw or {})
        self._query_kw = {} if ooc_opts is None \
            else {"ooc_opts": dict(ooc_opts)}
        lock: Any = threading.RLock()
        if lock_recorder is not None:
            lock = lock_recorder.wrap(lock, "serve.front._cond")
        self._cond = threading.Condition(lock)
        self._lanes: Dict[str, deque] = {
            ln: deque()
            for ln in LANES + (WRITE_LANE,)}          # guarded_by: _cond
        self._stopping = False                        # guarded_by: _cond
        self._drain_on_stop = True                    # guarded_by: _cond
        self._workers: List[threading.Thread] = []

    # ---------------------------------------------------- lifecycle
    def start(self) -> "ServeFront":
        if self._workers:
            return self
        for ln in LANES + (WRITE_LANE,):
            t = threading.Thread(target=self._worker, args=(ln,),
                                 name=f"serve-lane-{ln}", daemon=True)
            self._workers.append(t)
            t.start()
        return self

    def stop(self, drain: bool = True) -> None:
        """Stop the lane workers. ``drain=True`` (default) answers
        every queued request first; ``drain=False`` fails pending
        tickets with an ``{"error": "stopped"}`` entry."""
        with self._cond:
            self._stopping = True
            self._drain_on_stop = drain
            self._cond.notify_all()
        for t in self._workers:
            t.join()
        self._workers = []

    def __enter__(self) -> "ServeFront":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop(drain=True)

    # ------------------------------------------------------- submit
    def submit(self, req: Request) -> Ticket:
        """Admit + enqueue one request; raises :class:`Rejected` past
        the admission cap. Safe from any thread."""
        kind = guarantee_for_deadline(req.deadline_ms, **self.gkw).kind
        reason = self.admission.try_admit(kind)
        if reason is not None:
            raise Rejected(reason)
        ticket = Ticket(req.uid)
        with self._cond:
            if self._stopping:
                self.admission.release()
                raise Rejected("stopped")
            self._lanes[lane_of(kind)].append((req, ticket))
            self._cond.notify_all()
        return ticket

    def submit_write(self, op: str, rows=None, ids=None,
                     uid: int = -1) -> Ticket:
        """Enqueue one mutation on the write lane (docs/INGEST.md):
        ``op='insert'`` with ``rows`` (optionally ``ids``), or
        ``op='delete'`` with ``ids``. Returns a :class:`Ticket` whose
        entry reports the assigned global ids and the ``applied_at``
        stamp — the instant the rows became retrievable, which the
        freshness metric (benchmarks/bench_serve_load.py) measures
        against. Safe from any thread; writes skip admission (module
        constant rationale)."""
        if op not in ("insert", "delete"):
            raise ValueError(f"op must be 'insert'|'delete', got {op!r}")
        if op == "insert" and rows is None:
            raise ValueError("insert needs rows")
        if op == "delete" and ids is None:
            raise ValueError("delete needs ids")
        ticket = Ticket(uid)
        with self._cond:
            if self._stopping:
                raise Rejected("stopped")
            self._lanes[WRITE_LANE].append(
                ((op, rows, ids, obs.now()), ticket))
            self._cond.notify_all()
        return ticket

    # -------------------------------------------------------- drain
    def _take(self, lane: str) -> Optional[List[Tuple[Request, Ticket]]]:
        """Block until this lane has work (or the front stops).
        Returns up to ``max_batch`` entries, or None to exit."""
        with self._cond:
            q = self._lanes[lane]
            while not q and not self._stopping:
                self._cond.wait()
            if not q:
                return None           # stopping and (drained or not)
            if self._stopping and not self._drain_on_stop:
                batch = list(q)
                q.clear()
                for _r, t in batch:
                    t._complete({"error": "stopped"})
                if lane != WRITE_LANE:  # writes hold no admission slot
                    self.admission.release(len(batch))
                return None
            batch = [q.popleft() for _ in range(min(len(q),
                                                    self.max_batch))]
            return batch

    def _worker(self, lane: str) -> None:
        while True:
            with obs.span("serve.lane.wait", lane=lane):
                batch = self._take(lane)
            if batch is None:
                return
            obs.REGISTRY.histogram(
                "serve.lane.batch_size", lane=lane).record(len(batch))
            try:
                if lane == WRITE_LANE:
                    self._process_writes(batch)
                else:
                    self._process(batch)
            except Exception as e:  # noqa: BLE001 — a lane worker must outlive any single batch: complete its tickets with the error and keep serving
                obs.REGISTRY.counter(
                    "serve.loop.errors", lane=lane).inc()
                for _r, t in batch:
                    if not t.done():
                        t._complete({"error": repr(e)})
            finally:
                if lane != WRITE_LANE:  # writes hold no admission slot
                    self.admission.release(len(batch))

    def _process_writes(self, batch) -> None:
        """Apply one drained write-lane batch in submission order:
        ``engine.insert`` / ``engine.delete`` are O(rows) memtable
        updates (store/delta.py), so the write lane stays cheap and
        never holds a retrieval lane's resources. The completion entry
        carries ``applied_at`` — from that instant the next query()
        snapshot sees the mutation (freshness, docs/INGEST.md)."""
        for (op, rows, ids, submitted), t in batch:
            t0 = obs.now()
            if op == "insert":
                out_ids = np.asarray(self.engine.insert(rows, ids))
                n = int(out_ids.shape[0])
            else:
                out_ids = np.asarray(ids, np.int64).reshape(-1)
                self.engine.delete(out_ids)
                n = int(out_ids.shape[0])
            done = obs.now()
            obs.REGISTRY.counter("serve.writes", op=op).inc(n)
            t._complete({
                "op": op, "ids": out_ids, "applied_at": done,
                "queue_wait_ms": max((t0 - submitted) * 1e3, 0.0),
                "latency_ms": max((done - submitted) * 1e3, 0.0),
                "done_at": done,
            })

    def _process(self, batch: List[Tuple[Request, Ticket]]) -> None:
        """Answer one drained lane batch: remap guarantees from the
        REMAINING deadline budget, degrade one tier under shedding,
        then one engine call per resulting guarantee group."""
        import jax.numpy as jnp

        drained_at = obs.now()
        tickets = {r.uid: t for r, t in batch}
        no_series = [r for r, _t in batch if r.series is None]
        for r in no_series:
            # nothing to retrieve — answer immediately (the decode
            # path, if any, is the caller's business)
            tickets[r.uid]._complete({
                "ids": None, "dists": None,
                "kind": guarantee_for_deadline(
                    r.deadline_ms, **self.gkw).kind,
                "retrieval_ms": 0.0,
                "queue_wait_ms": max(
                    (drained_at - r.submitted_at) * 1e3, 0.0),
                "latency_ms": max(
                    (obs.now() - r.submitted_at) * 1e3, 0.0),
                "done_at": obs.now(),
            })
        shedding = self.admission.shedding()
        groups = retrieval_groups(
            [r for r, _t in batch if r.series is not None],
            at=drained_at, **self.gkw)
        for g, group in groups:
            g_final = self.admission.shed(g) if shedding else g
            self._query_group(jnp, g, g_final, group, tickets,
                              drained_at, shed=shedding
                              and g_final != g)

    def _query_group(self, jnp, g_nominal: Guarantee, g: Guarantee,
                     group: List[Request],
                     tickets: Dict[int, Ticket], drained_at: float,
                     *, shed: bool) -> None:
        qs = np.stack([np.asarray(r.series, np.float32)
                       for r in group])
        lanes = bucket_of(qs.shape[0], 1)
        if lanes > qs.shape[0]:
            qs = np.concatenate(
                [qs, np.repeat(qs[-1:], lanes - qs.shape[0], 0)])
        with obs.span("serve.retrieval_group", kind=g.kind,
                      lanes=lanes, requests=len(group)):
            t0 = obs.now()
            res = self.engine.query(jnp.asarray(qs), self.k, g,
                                    **self._query_kw)
            with obs.span("serve.fetch"):
                ids_np = np.asarray(res.ids)
                dists_np = np.asarray(res.dists)
            group_ms = (obs.now() - t0) * 1e3
        obs.REGISTRY.histogram(
            "serve.retrieval_ms", kind=g.kind).record(group_ms)
        # honest tier accounting, same as the static front: a shard
        # lost past retries/replicas degrades the ANSWER's guarantee
        # (docs/FAULT.md) — stats ride the result, never engine state
        stats = getattr(res, "stats", None)
        degraded = bool(stats is not None and stats.degraded)
        kind = "delta-epsilon" if degraded else g.kind
        if degraded:
            obs.REGISTRY.counter(
                "serve.degraded", kind=g.kind).inc(len(group))
        done_at = obs.now()
        for i, r in enumerate(group):
            entry: Dict[str, Any] = {
                "ids": ids_np[i],
                "dists": dists_np[i],
                "guarantee": g,
                "kind": kind,
                "nominal_kind": g_nominal.kind,
                "retrieval_ms": group_ms,
                "queue_wait_ms": max(
                    (drained_at - r.submitted_at) * 1e3, 0.0),
                "latency_ms": max(
                    (done_at - r.submitted_at) * 1e3, 0.0),
                "done_at": done_at,
                "stats": stats,
            }
            if shed:
                entry["shed"] = True
            if degraded:
                entry["degraded"] = True
                entry["requested_kind"] = g.kind
                entry["effective_delta"] = float(stats.effective_delta)
                entry["shards_lost"] = int(stats.shards_lost)
            tickets[r.uid]._complete(entry)
