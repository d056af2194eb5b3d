"""OocStats — THE typed per-query out-of-core telemetry schema.

Replaces the free-form dicts that used to flow out of
``search_ooc(...).stats`` and the engine (today:
``DistributedEngine.query(...)`` returns it on ``QueryResult.stats``;
the old mutable ``last_ooc_stats`` channel is gone — the
``engine-stats`` analysis rule fails any read of it):
every field is declared once here, the SAME instance feeds the span
tree (``search_ooc`` sets its fields as root-span attributes) and the
metrics registry, so the three views can never drift. Mapping-style
access (``stats["bytes_read"]``) is kept so existing call sites and
benches read it unchanged.

Field groups:

  cache/prefetch   byte and hit accounting from DeviceLeafCache +
                   LeafPrefetcher (registry-backed counters, windowed
                   per query by reset_counters()).
  refinement       what the host loop itself measured: iterations,
                   frontier refills, per-lane visit totals, which
                   stop condition fired per lane and the epsilon/delta
                   slack at stop (mean over lanes attributed to that
                   condition; slack = how far past the threshold the
                   stop fired, in squared-distance units).
  host time        where the loop's host seconds go (always on, taken
                   from the same stamps as the ``ooc.gather`` /
                   ``ooc.sync`` spans): the loop from its first to its
                   last iteration, the gathers, and the device->host
                   reads with their count.
  engine fold      ``shards`` holds the per-shard OocStats when the
                   DistributedEngine aggregates a cross-shard query.

Stop-condition attribution priority (a stopping lane can satisfy
several predicates at once): ``delta`` (the r_delta early stop — the
answer is already good enough) wins over ``epsilon`` (lb pruning — the
remaining leaves cannot improve it) wins over ``exhausted`` (rank
budget / scanned everything).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List


@dataclasses.dataclass
class OocStats:
    # ---- identity / knobs
    codec: str = ""
    share_gathers: bool = False
    prefetch_depth: int = 0
    # ---- cache / prefetcher accounting (DeviceLeafCache.stats())
    capacity_leaves: int = 0
    hits: int = 0
    hits_distinct: int = 0
    misses: int = 0
    hit_rate: float = 0.0
    hit_rate_distinct: float = 0.0
    bytes_read: int = 0          # TOTAL disk bytes incl. rerank + prefetch
    bytes_read_sync: int = 0     # demand-path reads only
    bytes_h2d: int = 0
    prefetch_hits: int = 0
    prefetch_bytes_read: int = 0
    prefetch_leaves_read: int = 0
    bytes_read_rerank: int = 0
    dataset_bytes: int = 0
    # ---- refinement-loop telemetry
    iterations: int = 0
    frontier_refills: int = 0    # lane-refill events across the loop
    leaves_visited: int = 0      # summed over lanes
    rows_scanned: int = 0        # candidates scored, summed over lanes
    pruning_ratio: float = 0.0   # 1 - leaves_visited / (lanes * L)
    stop_delta: int = 0          # lanes stopped by the r_delta early stop
    stop_epsilon: int = 0        # lanes stopped by (1+eps) lb pruning
    stop_exhausted: int = 0      # lanes that ran out of rank budget
    delta_slack: float = 0.0     # mean (1+eps)^2*rd^2 - bsf at delta stops
    eps_slack: float = 0.0       # mean next_lb*(1+eps)^2 - bsf at eps stops
    # ---- host time of the loop (obs.Tally over its spans)
    loop_s: float = 0.0          # first to last iteration, host seconds
    gather_s: float = 0.0        # summed ooc.gather spans
    sync_s: float = 0.0          # summed ooc.sync spans
    host_syncs: int = 0          # device->host reads in the loop
    # ---- fault tolerance (engine fold; per-shard entries carry their
    # own retries/failovers, the degradation triple is engine-level —
    # docs/FAULT.md)
    retries: int = 0             # failed shard attempts that were retried
    failovers: int = 0           # shards served from a non-owner copy
    degraded: bool = False       # answer computed without >=1 shard
    shards_lost: int = 0
    effective_delta: float = 1.0  # honest delta of the returned answer
    # ---- engine cross-shard fold
    shards: List["OocStats"] = dataclasses.field(default_factory=list)

    # ------------------------------------------- dict-style back-compat
    def __getitem__(self, key: str):
        try:
            return getattr(self, key)
        except AttributeError:
            raise KeyError(key) from None

    def get(self, key: str, default=None):
        return getattr(self, key, default)

    def __contains__(self, key) -> bool:
        return isinstance(key, str) and hasattr(self, key)

    def keys(self):
        return [f.name for f in dataclasses.fields(self)]

    def items(self):
        return [(k, getattr(self, k)) for k in self.keys()]

    def __iter__(self) -> Iterator[str]:
        return iter(self.keys())

    def as_dict(self) -> dict:
        out = {k: v for k, v in self.items() if k != "shards"}
        out["shards"] = [s.as_dict() if isinstance(s, OocStats) else s
                         for s in self.shards]
        return out

    # --------------------------------------------------------- helpers
    _SUM_FIELDS = (
        "capacity_leaves", "hits", "hits_distinct", "misses",
        "bytes_read", "bytes_read_sync", "bytes_h2d", "prefetch_hits",
        "prefetch_bytes_read", "prefetch_leaves_read",
        "bytes_read_rerank", "dataset_bytes", "iterations",
        "frontier_refills", "leaves_visited", "rows_scanned",
        "stop_delta", "stop_epsilon", "stop_exhausted",
        "loop_s", "gather_s", "sync_s", "host_syncs",
        "retries", "failovers",
    )

    @classmethod
    def aggregate(cls, per_shard: List["OocStats"]) -> "OocStats":
        """Cross-shard fold: sum the additive fields, recompute the
        hit rates from the summed counts, average the slacks weighted
        by the lanes attributed to each condition, keep the per-shard
        schemas under ``shards``."""
        agg = cls()
        if not per_shard:
            return agg
        agg.codec = per_shard[0].codec
        agg.share_gathers = per_shard[0].share_gathers
        agg.prefetch_depth = per_shard[0].prefetch_depth
        for s in per_shard:
            for f in cls._SUM_FIELDS:
                setattr(agg, f, getattr(agg, f) + s.get(f, 0))
        total = agg.hits + agg.misses
        distinct = agg.hits_distinct + agg.misses
        agg.hit_rate = agg.hits / total if total else 0.0
        agg.hit_rate_distinct = \
            agg.hits_distinct / distinct if distinct else 0.0
        for slack, n in (("delta_slack", "stop_delta"),
                         ("eps_slack", "stop_epsilon")):
            w = sum(s.get(n, 0) for s in per_shard)
            if w:
                setattr(agg, slack, sum(
                    s.get(slack, 0.0) * s.get(n, 0)
                    for s in per_shard) / w)
        # pruning ratio over the union of per-shard leaf populations:
        # mean of the per-shard ratios weighted by nothing is wrong
        # when shard sizes differ, so recompute from visit totals when
        # every shard filled the ratio field
        lanes_l = [s for s in per_shard if s.pruning_ratio or
                   s.leaves_visited]
        if lanes_l:
            agg.pruning_ratio = float(
                sum(s.pruning_ratio for s in per_shard) / len(per_shard))
        agg.shards = list(per_shard)
        return agg
