"""Lightweight thread-safe span tracer for the retrieval stack.

One monotonic clock (``now`` = ``time.perf_counter``) stamps every
span; the SAME clock is exported to the serving front
(serve/batching.Request.submitted_at, launch/serve timings), so
queue-wait arithmetic across modules is coherent by construction —
never mix this with ``time.monotonic()`` or wall-clock time.

:func:`span` is the one tracing entry point, in three states:

  profiler on   while the JAX profiler collects (``jax.profiler.trace``
                / ``start_trace``), every span also opens a
                ``jax.profiler.TraceAnnotation`` under its name, so the
                program's spans land in the ``.xplane.pb`` beside the
                device's ops, on the device trace's clock. This holds
                whether or not :func:`enable` was called.
  obs on        (:func:`enable`) a :class:`Span` records into the
                tracer's list on ``now`` (and mirrors, as above).
  both off      the shared no-op :data:`NULL_SPAN`: one module-global
                flag check and one profiler flag read, no allocation.

Spans never sync the device: a span times the host's side of what it
encloses (dispatch, host reads, I/O). Device time comes from the device
trace.

When obs is on, spans nest through a thread-local stack (each thread
builds its own subtree; ids are process-unique), finished spans land
in the tracer's ordered list, and two consumers read them:

  QueryProfile        a structured per-query summary of one span's
                      subtree: phase durations aggregated by child
                      name, plus ``total(attr)`` folds over numeric
                      span attributes (the obs smoke asserts
                      ``total("bytes_read")`` equals the cache +
                      prefetcher counters bit-exact).
  dump_chrome_trace   the same spans as Chrome trace-event JSON
                      (chrome://tracing, Perfetto) — ``ph="X"``
                      complete events, µs timestamps, span attrs in
                      ``args``.

:class:`Tally` is the always-on counter of a span: the count and host
seconds of the regions it times, each also a span of its name, so the
counter a result carries and the span a trace shows come from the same
two stamps.

Span taxonomy and attribute names are documented in
docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

#: THE monotonic clock of the whole serving stack (satellite: was
#: time.monotonic in serve/batching vs time.perf_counter in
#: launch/serve — queue-wait subtraction across the two was
#: incoherent).
now = time.perf_counter

#: True while the JAX profiler collects (a C++ flag read).
profiling = TraceAnnotation.is_enabled

_enabled = False


def enabled() -> bool:
    """True while obs records :class:`Span` objects (:func:`enable`)."""
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


class _NullSpan:
    """Shared do-nothing span: what :func:`span` hands out while
    tracing is disabled. Accepts the full Span surface so call sites
    never branch."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass

    def add(self, key: str, n) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Annotation(TraceAnnotation):
    """What :func:`span` hands out while the profiler collects and obs
    is off: the profiler's annotation under the span's name, with the
    span surface (``set``/``add`` record nothing)."""

    def set(self, **attrs) -> None:
        pass

    def add(self, key: str, n) -> None:
        pass


@dataclasses.dataclass
class Span:
    """One timed region. Context-manager: ``with tracer.span(...) as
    sp: sp.set(bytes_read=...)``. ``t0``/``t1`` are ``now()`` stamps;
    ``parent`` is the enclosing span's id (-1 at a thread's root)."""

    name: str
    id: int = -1
    parent: int = -1
    t0: float = 0.0
    t1: float = 0.0
    tid: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    _tracer: Optional["Tracer"] = dataclasses.field(
        default=None, repr=False, compare=False)
    _ann: Optional[TraceAnnotation] = dataclasses.field(
        default=None, repr=False, compare=False)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def add(self, key: str, n) -> None:
        self.attrs[key] = self.attrs.get(key, 0) + n

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    @property
    def duration_ms(self) -> float:
        return (self.t1 - self.t0) * 1e3

    def __enter__(self) -> "Span":
        if profiling():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
        self._tracer._push(self)
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = now()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        self._tracer._pop(self)
        return False


class Tracer:
    """Collects finished spans. Thread-safe: each thread nests through
    its own stack; the finished list and the id counter are shared
    under one lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ids = itertools.count()             # guarded_by: _lock
        self._spans: List[Span] = []              # guarded_by: _lock
        self._local = threading.local()

    # ------------------------------------------------------- internals
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, sp: Span) -> None:
        st = self._stack()
        sp.parent = st[-1].id if st else -1
        sp.t0 = now()
        st.append(sp)

    def _pop(self, sp: Span) -> None:
        st = self._stack()
        if st and st[-1] is sp:
            st.pop()
        else:  # mis-nested exit: drop it from wherever it sits
            try:
                st.remove(sp)
            except ValueError:
                pass
        with self._lock:
            self._spans.append(sp)

    # ------------------------------------------------------------- API
    def span(self, name: str, **attrs) -> Span:
        with self._lock:
            sid = next(self._ids)
        return Span(name=name, id=sid, tid=threading.get_ident(),
                    attrs=dict(attrs), _tracer=self)

    def current(self) -> Optional[Span]:
        """The innermost OPEN span on this thread (None outside any)."""
        st = self._stack()
        return st[-1] if st else None

    def spans(self) -> List[Span]:
        """Finished spans, completion-ordered (children before their
        parent — a parent exits last)."""
        with self._lock:
            return list(self._spans)

    def find(self, name: str) -> List[Span]:
        return [s for s in self.spans() if s.name == name]

    def last(self, name: str) -> Optional[Span]:
        hits = self.find(name)
        return hits[-1] if hits else None

    def subtree(self, root: Span) -> List[Span]:
        """root + every finished descendant, completion-ordered."""
        all_spans = self.spans()
        keep = {root.id}
        # completion order puts children BEFORE parents, so walk the
        # list backwards: every span's parent is seen first
        out = []
        for sp in reversed(all_spans):
            if sp.id in keep or sp.parent in keep:
                keep.add(sp.id)
                out.append(sp)
        out.reverse()
        return out

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


_TRACER = Tracer()


def tracer() -> Tracer:
    return _TRACER


def span(name: str, **attrs):
    """The instrumentation entry point: a recording :class:`Span` when
    obs is enabled, the profiler's annotation while only the profiler
    collects, the shared no-op otherwise. ``with obs.span("x") as sp:``
    works identically in every state."""
    if _enabled:
        return _TRACER.span(name, **attrs)
    if profiling():
        return _Annotation(name)
    return NULL_SPAN


class Tally:
    """Count and host seconds of the regions it times, each region also
    an :func:`span` of ``name``. With obs recording, the seconds are the
    spans' own ``t0``/``t1``, so the counter and the spans cannot
    drift. Always on; one instance times one region at a time (reuse
    it, do not nest it in itself)."""

    __slots__ = ("name", "count", "seconds", "_sp", "_t0")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.seconds = 0.0
        self._sp = NULL_SPAN
        self._t0 = 0.0

    def __enter__(self):
        sp = self._sp = span(self.name)
        sp.__enter__()
        self._t0 = sp.t0 if type(sp) is Span else now()
        return sp

    def __exit__(self, *exc) -> bool:
        sp, self._sp = self._sp, NULL_SPAN
        if type(sp) is Span:
            sp.__exit__(*exc)
            t1 = sp.t1
        else:
            t1 = now()
            sp.__exit__(*exc)
        self.count += 1
        self.seconds += t1 - self._t0
        return False


def clear() -> None:
    _TRACER.clear()


# ------------------------------------------------------- QueryProfile
@dataclasses.dataclass
class QueryProfile:
    """Structured summary of one query's span subtree.

    ``phase_ms`` aggregates DIRECT children by name (the per-phase
    breakdown: filter / iterations / finalize, or queue-wait /
    generate / retrieval on the serving side); ``attrs`` are the root
    span's attributes; :meth:`total` folds a numeric attribute over
    the whole subtree (each span counted once)."""

    name: str
    duration_ms: float
    attrs: Dict[str, Any]
    phase_ms: Dict[str, float]
    spans: List[Span]

    def total(self, attr: str, default=0):
        out = default
        for sp in self.spans:
            v = sp.attrs.get(attr)
            if v is not None:
                out = out + v
        return out

    def count(self, name: str) -> int:
        return sum(1 for sp in self.spans if sp.name == name)


def profile(root: Span, trc: Optional[Tracer] = None) -> QueryProfile:
    """Build a QueryProfile from a FINISHED root span."""
    trc = trc or _TRACER
    spans = trc.subtree(root)
    phase: Dict[str, float] = {}
    for sp in spans:
        if sp.parent == root.id:
            phase[sp.name] = phase.get(sp.name, 0.0) + sp.duration_ms
    return QueryProfile(name=root.name, duration_ms=root.duration_ms,
                        attrs=dict(root.attrs), phase_ms=phase,
                        spans=spans)


def last_profile(name: str,
                 trc: Optional[Tracer] = None) -> Optional[QueryProfile]:
    """Profile of the most recent finished span with this name."""
    trc = trc or _TRACER
    root = trc.last(name)
    return profile(root, trc) if root is not None else None


# ------------------------------------------------------- chrome trace
def _json_safe(v):
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    try:  # numpy scalars
        return v.item()
    except AttributeError:
        return str(v)


def chrome_events(spans: List[Span]) -> List[dict]:
    """Spans -> Chrome trace-event "complete" (ph=X) events. ts/dur in
    µs on the shared monotonic clock; attrs become ``args``."""
    pid = os.getpid()
    return [{
        "name": sp.name, "ph": "X", "pid": pid, "tid": sp.tid,
        "ts": sp.t0 * 1e6, "dur": max(sp.t1 - sp.t0, 0.0) * 1e6,
        "args": {k: _json_safe(v) for k, v in sp.attrs.items()},
    } for sp in spans]


def dump_chrome_trace(path: str,
                      trc: Optional[Tracer] = None) -> str:
    """Write every finished span as Chrome trace-event JSON (load in
    chrome://tracing or https://ui.perfetto.dev). Returns ``path``."""
    trc = trc or _TRACER
    doc = {"traceEvents": chrome_events(trc.spans()),
           "displayTimeUnit": "ms"}
    with open(path, "w") as f:
        json.dump(doc, f)
    return path
