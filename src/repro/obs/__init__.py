"""repro.obs — query-plan tracing + metrics for the retrieval stack.

Two halves (docs/OBSERVABILITY.md):

  trace    one span tracer: spans mirror into the JAX profiler's
           trace while it collects; opt-in recording (disabled by
           default, near-zero cost off) of nestable spans on ONE
           monotonic clock (``obs.now``), per-query
           :class:`QueryProfile` summaries, Chrome trace-event JSON
           export; :class:`Tally`, the always-on counter of a span.
  metrics  always-on process-wide registry of labeled counters /
           gauges / log-bucketed histograms (p50/p95/p99).

``OocStats`` is the typed per-query out-of-core telemetry schema both
halves share with the store/engine layer. ``lockorder`` is the
debug-mode lock-order recorder (wrap locks, run a workload,
``assert_acyclic()``) — the dynamic complement to the static
guarded-by pass in :mod:`repro.analysis`.
"""

from .lockorder import (LockOrderError, LockOrderRecorder, wrap
                        as wrap_lock)
from .metrics import (GROWTH, REGISTRY, Counter, Gauge, Histogram,
                      MetricsRegistry, registry)
from .stats import OocStats
from .trace import (NULL_SPAN, QueryProfile, Span, Tally, Tracer,
                    chrome_events, clear, disable, dump_chrome_trace,
                    enable, enabled, last_profile, now, profile,
                    profiling, span, tracer)

__all__ = [
    "GROWTH", "REGISTRY", "Counter", "Gauge", "Histogram",
    "LockOrderError", "LockOrderRecorder", "wrap_lock",
    "MetricsRegistry", "registry", "OocStats", "NULL_SPAN",
    "QueryProfile", "Span", "Tally", "Tracer", "chrome_events",
    "clear", "disable", "dump_chrome_trace", "enable", "enabled",
    "last_profile", "now", "profile", "profiling", "span", "tracer",
]
