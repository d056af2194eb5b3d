"""DistributedSearchEngine — the paper's methods at pod scale.

The collection is range-sharded over the mesh's data-parallel axes; each
shard owns a FrozenIndex over its rows (ids stay global) plus the GLOBAL
distance histogram and global N, so per-shard r_delta matches the
single-node semantics. A query batch is replicated to all shards, each
runs the batched Algorithm 2 locally (shard_map), and per-shard top-k
rows are merged with an all-gather + static sort.

Guarantee preservation under sharding (docs/PERF.md §6): every global true
r-th NN lives in some shard where it ranks <= r locally; the local
guarantee bounds that shard's reported r-th by (1+eps) x local true r-th
<= (1+eps) x global true r-th, and the merged r-th best across shards
only improves — so exact/epsilon/delta-epsilon transfer. For delta<1 the
per-shard stopping radius uses the global N, making each shard's early
stop conservative w.r.t. the global distribution.

Fault tolerance: the frozen artifact checkpoints via train/checkpoint.py
like any pytree; straggler mitigation degrades the guarantee to
ng(nprobe) under a deadline — the taxonomy is the mitigation (paper
Fig. 8 shows the first bsf is already near-exact). Since PR 8 the
out-of-core path is fault-tolerant end to end (docs/FAULT.md): shards
are served by CONCURRENT owners (a worker pool streaming results into
the topk_merge_unique fold as they land — the merge is a commutative
(d, id)-lex selection, so completion order cannot change the answer),
``build(replicas=R)`` persists R copies of every shard store with
round-robin owner assignment, a failed/timed-out attempt retries with
capped exponential backoff and fails over to the next copy
(serve/fault.py: RetryPolicy + CircuitBreaker), and a shard lost past
every copy degrades the answer honestly — the query completes over
the surviving shards and OocStats reports ``degraded`` /
``shards_lost`` / ``effective_delta`` with delta recomputed from the
global distance histogram mass the missing rows own
(core.guarantees.effective_delta_after_loss).
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import tempfile
import threading
import warnings
from concurrent.futures import ThreadPoolExecutor, as_completed
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.kernels import ops
from repro.obs import OocStats

from .guarantees import Guarantee, joint_n_total
from .histogram import DistanceHistogram, build_histogram
from .index import FrozenIndex
from .indexes import dstree, isax, vafile
from .search import SearchResult, search_impl
from .spec import (IndexSpec, StoreSpec, coerce_build_args,
                   coerce_store_spec)


class QueryResult(NamedTuple):
    """What :meth:`DistributedEngine.query` returns: the SearchResult
    fields plus the per-query :class:`OocStats` traveling WITH the
    answer. Stats used to be published through the mutable
    ``engine.last_ooc_stats`` field, which misattributes them the
    moment two ``query()`` calls run concurrently (the continuous-
    batching serving front has one in flight per lane) — so the field
    is gone and the ``engine-stats`` analysis rule keeps it gone
    (docs/ANALYSIS.md). ``stats`` is None on the resident shard_map
    path (no I/O to account) and an aggregated OocStats on the
    out-of-core path (per-shard schemas under ``.stats.shards``,
    degradation triple when shards were lost — docs/FAULT.md).
    ``dispatch_s`` is the resident path's host seconds inside the
    eager shard_map call (the ``engine.dispatch`` span); 0.0 on the
    out-of-core path."""

    dists: jax.Array           # [B, k] Euclidean distances, ascending
    ids: jax.Array             # [B, k] global row ids (-1 = missing)
    leaves_visited: jax.Array  # [B] int32, summed over shards
    rows_scanned: jax.Array    # [B] int32, summed over shards
    lb_computed: jax.Array     # scalar int32
    stats: Optional[OocStats] = None
    dispatch_s: float = 0.0

class EngineSegment(NamedTuple):
    """One compacted delta segment (docs/INGEST.md): the leaf-
    contiguous on-disk artifact the background compactor froze out of
    the delta tier — codec-aware through the ordinary ``save_index``
    path, served exactly like one more shard. ``born_seq`` is the
    delta sequence the freeze happened at: any kill with a NEWER
    sequence masks this segment's copy of the id (store.delta kill
    rule), which is what makes publishing safe while deletes race the
    build. ``index`` keeps the pre-encode f32-resident FrozenIndex on
    resident engines so segment scoring matches the resident base
    arithmetic; out-of-core engines serve the segment from its store
    dir (codec-faithful) instead."""
    dir: str
    born_seq: int
    n_rows: int
    ids_np: np.ndarray                  # [npad] global ids (-1 pad)
    index: Optional[FrozenIndex] = None


class _MutView(NamedTuple):
    """Everything one query needs to serve a mutable-tier snapshot
    jointly with the frozen base (docs/INGEST.md): the snapshot
    itself, the joint r_delta row count
    (core.guarantees.joint_n_total — inserts RAISE N, deletes never
    lower it), and each published segment's tombstone mask under this
    snapshot's kills. Computed once per query, immutable afterwards."""
    snap: object                         # store.delta.DeltaSnapshot
    joint_n: int
    seg_dead: Tuple[np.ndarray, ...]     # per segment, [npad] bool


_BUILDERS = {
    "isax2+": isax.build,
    "dstree": dstree.build,
    "va+file": vafile.build,
}


def _pad_to(arr: np.ndarray, target: int, fill) -> np.ndarray:
    if arr.shape[0] == target:
        return arr
    pad = np.full((target - arr.shape[0],) + arr.shape[1:], fill,
                  arr.dtype)
    return np.concatenate([arr, pad], axis=0)


def _discover_replicas(spill_dir: str, shard_dirs: Tuple[str, ...]
                       ) -> Tuple[Tuple[str, ...], ...]:
    """Per shard: (primary, *replica copies) found on disk. Replicas
    live under spill_dir/replicas/rN/shard_NNNN — deliberately NOT
    top-level shard_* names, which open_spill would mis-discover as
    independent shards."""
    rep_root = os.path.join(spill_dir, "replicas")
    rdirs = sorted(os.listdir(rep_root)) \
        if os.path.isdir(rep_root) else []
    out = []
    for d in shard_dirs:
        name = os.path.basename(d)
        copies = [d]
        for rd in rdirs:
            cand = os.path.join(rep_root, rd, name)
            if os.path.isdir(cand):
                copies.append(cand)
        out.append(tuple(copies))
    return tuple(out)


@dataclasses.dataclass
class DistributedEngine:
    mesh: Optional[Mesh]  # None for an OOC-only engine (open_spill)
    axes: Tuple[str, ...] = ("data",)
    method: str = "dstree"
    # the resident index: every shard padded to one shape and laid end
    # to end on axis 0, so each device's block IS its shard
    stacked: Optional[FrozenIndex] = None
    shard_dirs: Optional[Tuple[str, ...]] = None  # spilled store dirs
    # explicit shard count for a MESH-FREE engine (mesh=None +
    # build(keep_resident=False): multi-shard OOC serving without any
    # device mesh — the single-process stand-in for per-host shard
    # ownership); ignored when a mesh is set
    shards: Optional[int] = None
    # per shard: every on-disk copy of its store, PRIMARY FIRST
    # (build(replicas=R) / open_spill discovery); the failover loop
    # rotates the attempt order per shard for round-robin ownership
    shard_replica_dirs: Optional[Tuple[Tuple[str, ...], ...]] = None
    # the typed build/open surface (core/spec.py): what was built and
    # how it is served — including the delta/compaction knobs
    index_spec: Optional[IndexSpec] = None
    store_spec: Optional[StoreSpec] = None
    # ---- mutable tier (docs/INGEST.md), armed by enable_writes() ----
    _delta: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)
    # serializes enable_writes/segment-numbering bookkeeping (the
    # delta tier itself carries its own lock; lock order: _write_lock
    # is a leaf, never held across delta or store calls)
    _write_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    _seg_dir: Optional[str] = dataclasses.field(
        default=None, repr=False, compare=False)
    _seg_seq: int = dataclasses.field(
        default=0, repr=False, compare=False)
    _compactor: Optional[threading.Thread] = dataclasses.field(
        default=None, repr=False, compare=False)
    _compactor_stop: Optional[threading.Event] = dataclasses.field(
        default=None, repr=False, compare=False)
    # per-shard host copies of the stacked id arrays (resident
    # engines): tombstone masks are recomputed from these when the
    # kill set advances, without pulling device arrays per query
    _shard_ids_host: Optional[list] = dataclasses.field(
        default=None, repr=False, compare=False)
    # frozen-unit dead-mask cache keyed by unit, valued
    # (kills_version, mask). Lock-free like _query_fns: dict get/set
    # are GIL-atomic and racing snapshots recompute from their own
    # consistent kill copies
    _dead_cache: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # (kills_version, device [S, max_rows] bool) stacked tombstones
    # for the resident shard_map operand
    _dead_stacked: Optional[tuple] = dataclasses.field(
        default=None, repr=False, compare=False)
    # jitted query fns keyed by (k, guarantee, batch shape, ...): the
    # shard_map body closes over those values, so a fresh closure per
    # call would defeat jit's compile cache. Lock-free on purpose:
    # dict get/set are GIL-atomic and two threads racing to build the
    # same key produce interchangeable callables (last one wins)
    _query_fns: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # out-of-core serving state: per-shard LeafStore handles + warm
    # device leaf caches, opened lazily on the first OOC query and
    # reused across queries (the serving regime)
    _stores: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    _shard_caches: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # serializes _stores/_shard_caches mutation against concurrent
    # shard owners and close(); per-shard search runs OUTSIDE it
    _ooc_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)
    # per shard-store-copy serving locks: CONCURRENT query() calls
    # (one per serving lane) share the warm per-copy DeviceLeafCache,
    # whose slot pool is only consistent for one query at a time (a
    # second query's get_slots may evict a slot the first is about to
    # gather) — so one query's use of one copy is one critical
    # section. Distinct shards/copies still serve fully in parallel;
    # lock order is copy lock -> _ooc_lock -> cache._lock (acyclic,
    # asserted by the lockorder stress test)
    _copy_locks: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # persistent per-(shard, copy) circuit breaker (serve/fault.py),
    # created lazily on the first fault-tolerant OOC query
    _breaker: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_shards(self) -> int:
        if self.mesh is None:
            if self.shards is not None:
                return int(self.shards)
            return len(self.shard_dirs) if self.shard_dirs else 1
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        out = 1
        for a in self.axes:
            out *= shape[a]
        return out

    @classmethod
    def open_spill(cls, store, *, mesh: Optional[Mesh] = None,
                   axes: Tuple[str, ...] = ("data",),
                   index: Optional[IndexSpec] = None,
                   method: Optional[str] = None) -> "DistributedEngine":
        """Open an engine over an existing spilled build artifact
        WITHOUT loading any shard into HBM — the serving path for
        collections larger than device memory (multi-host: each host
        opens the shards it owns). ``store`` is a
        :class:`~repro.core.spec.StoreSpec` (its ``spill_dir`` names
        the artifact; its delta/compaction knobs govern
        :meth:`enable_writes`); a bare spill-dir string and the old
        ``method=`` kwarg keep working for one release via the
        APIDeprecationWarning shim (core/spec.py). ``query``
        auto-detects the missing resident index and serves
        out-of-core. Replica copies persisted by ``build`` with
        ``StoreSpec(replicas=R)`` (spill_dir/replicas/rN/shard_NNNN)
        are discovered too and arm failover."""
        ispec, sspec = coerce_store_spec(store, method=method,
                                         index=index)
        spill_dir = sspec.spill_dir
        shard_dirs = tuple(sorted(
            os.path.join(spill_dir, d) for d in os.listdir(spill_dir)
            if d.startswith("shard_")))
        if not shard_dirs:
            raise ValueError(f"no shard_* stores under {spill_dir!r}")
        eng = cls(mesh=mesh, axes=tuple(axes), method=ispec.method)
        eng.index_spec = ispec
        eng.store_spec = sspec
        eng.shard_dirs = shard_dirs
        eng.shard_replica_dirs = _discover_replicas(spill_dir,
                                                    shard_dirs)
        return eng

    # ------------------------------------------------------------------
    def build(self, data: np.ndarray, key=None, *,
              index: Optional[IndexSpec] = None,
              store: Optional[StoreSpec] = None, **legacy):
        """Shard rows, build per-shard indexes (embarrassingly parallel
        on hosts), stack and device_put with the shard axis mapped onto
        the mesh axes.

        The configuration surface is two typed specs (core/spec.py):
        ``index=IndexSpec(method, params)`` says WHAT to build (method
        + builder params such as ``leaf_cap``); ``store=StoreSpec(...)``
        says WHERE/HOW to serve it. The old loose spelling —
        ``build(spill_dir=..., codec=..., keep_resident=...,
        replicas=..., **builder_params)`` — keeps working for one
        release via the APIDeprecationWarning shim.

        ``StoreSpec.spill_dir`` additionally persists every shard as an
        on-disk store artifact (spill_dir/shard_NNNN, global ids and
        global n_total preserved) so shards can be served out-of-core —
        since PR 4 directly by :meth:`query` (auto-detected, or forced
        with ``ooc=True``), the path toward collections larger than pod
        HBM. ``StoreSpec.codec`` selects each shard's leaf payload
        encoding ("f32"/"bf16"/"pq", store format v2) — compressed
        spill shrinks every shard's bytes-read in the out-of-core
        serving path. ``keep_resident=False`` (requires ``spill_dir``)
        skips stacking the shards into HBM entirely: the engine holds
        only the spilled stores and every query runs the OOC path — on
        a MESH-FREE engine (``mesh=None`` + ``shards=N``) this is the
        only legal mode, and the shard count comes from ``self.shards``.
        ``replicas=R`` persists R on-disk copies of every shard store
        (the primary plus R-1 byte-identical replicas under
        spill_dir/replicas/rN/ — no re-encode, so pq codebooks and
        leaf payloads match bit for bit) with round-robin owner
        assignment; a failed or timed-out shard attempt fails over to
        the next copy before the query degrades (docs/FAULT.md). The
        delta/compaction fields govern :meth:`enable_writes`
        (docs/INGEST.md)."""
        ispec, sspec = coerce_build_args(self.method, index, store,
                                         legacy)
        spill_dir, codec = sspec.spill_dir, sspec.codec
        keep_resident, replicas = sspec.keep_resident, sspec.replicas
        params = ispec.build_params
        if self.mesh is None and keep_resident:
            raise ValueError(
                "mesh-free engine (mesh=None) cannot hold a resident "
                "index: build with StoreSpec(keep_resident=False, "
                "spill_dir=...)")
        key = key if key is not None else jax.random.PRNGKey(0)
        self._query_fns.clear()  # compiled against the previous index
        self.close()             # OOC state + compaction daemon from
        #                          the previous build
        self._delta = None       # writes belonged to the old rows
        self._seg_dir = None
        self._seg_seq = 0
        self._dead_cache.clear()
        self._dead_stacked = None
        self.method = ispec.method
        self.index_spec, self.store_spec = ispec, sspec
        n = data.shape[0]
        s = self.n_shards
        bounds = np.linspace(0, n, s + 1).astype(np.int64)
        sample = data[np.random.default_rng(0).choice(
            n, min(n, 100_000), replace=False)]
        hist = build_histogram(sample, key)  # GLOBAL histogram
        builder = _BUILDERS[ispec.method]

        shards = []
        spill_dirs = []
        for si in range(s):
            lo, hi = bounds[si], bounds[si + 1]
            # pulled to the host at once: the builder leaves each
            # shard on the default device, and holding every shard
            # there until stacking puts the whole collection on one
            # chip
            idx = jax.device_get(
                builder(data[lo:hi], hist=hist, key=key, **params))
            # re-map ids to global, keep global n_total for r_delta
            ids = np.where(idx.ids >= 0, idx.ids + lo, -1)
            idx = dataclasses.replace(
                idx, ids=ids.astype(np.int32), n_total=n)
            if spill_dir is not None:
                d = os.path.join(spill_dir, f"shard_{si:04d}")
                spill_dirs.append(idx.save(d, codec=codec))
                # replica copies are byte-identical file copies of the
                # saved store (same ids, histogram, pq codebook), laid
                # out under replicas/rN so open_spill's shard_*
                # discovery cannot mistake them for extra shards
                for rep in range(1, replicas):
                    rd = os.path.join(spill_dir, "replicas",
                                      f"r{rep}", f"shard_{si:04d}")
                    if os.path.isdir(rd):
                        shutil.rmtree(rd)
                    shutil.copytree(spill_dirs[-1], rd)
            if keep_resident:
                shards.append(idx)  # else: spilled, drop the HBM copy
        self.shard_dirs = tuple(spill_dirs) if spill_dirs else None
        self.shard_replica_dirs = _discover_replicas(
            spill_dir, self.shard_dirs) if spill_dirs else None
        if not keep_resident:
            self.stacked = None
            self._shard_ids_host = None
            return self

        # uniform static metadata + padded array shapes across shards
        max_leafL = max(sh.num_leaves for sh in shards)
        max_rows = max(sh.data.shape[0] for sh in shards)
        max_leaf = max(sh.max_leaf for sh in shards)
        arrs = {"box_lo": [], "box_hi": [], "offsets": [], "data": [],
                "ids": [], "row_norms": []}
        for sh in shards:
            L = sh.num_leaves
            off = np.asarray(sh.offsets)
            # pad leaves with empty extents pointing at the end
            offp = np.concatenate(
                [off, np.full(max_leafL - L, off[-1], off.dtype)])
            arrs["box_lo"].append(_pad_to(
                np.asarray(sh.box_lo), max_leafL, np.float32(1e30)))
            arrs["box_hi"].append(_pad_to(
                np.asarray(sh.box_hi), max_leafL, np.float32(1e30)))
            arrs["offsets"].append(offp)
            arrs["data"].append(_pad_to(
                np.asarray(sh.data), max_rows, np.float32(0)))
            arrs["ids"].append(_pad_to(
                np.asarray(sh.ids), max_rows, np.int64(-1)))
            # padding rows are all-zero, so norm 0 keeps the cache
            # consistent with the padded data
            arrs["row_norms"].append(_pad_to(
                np.asarray(sh.row_norms), max_rows, np.float32(0)))
        # host copies of the per-shard id arrays: the mutable tier
        # recomputes tombstone masks from these without device pulls
        self._shard_ids_host = [np.asarray(a) for a in arrs["ids"]]

        spec0 = P(self.axes if len(self.axes) > 1 else self.axes[0])

        def put(parts, dtype):
            # shards end to end on axis 0, straight from the host into
            # the sharded layout (a jnp.asarray first would stage the
            # whole collection on one chip). The shard_map body then
            # gets each shard's 2-D arrays as they are: the eager
            # dispatch runs every op as its own program, so squeezing a
            # stacked shard axis there copied the shard's whole data
            # block, which at 4 GiB per chip no longer fit in HBM
            return jax.device_put(
                np.concatenate(parts).astype(dtype, copy=False),
                NamedSharding(self.mesh, spec0))

        base = shards[0]
        self.stacked = FrozenIndex(
            box_lo=put(arrs["box_lo"], np.float32),
            box_hi=put(arrs["box_hi"], np.float32),
            offsets=put(arrs["offsets"], np.int32),
            data=put(arrs["data"], arrs["data"][0].dtype),
            ids=put(arrs["ids"], np.int32),
            row_norms=put(arrs["row_norms"], np.float32),
            weights=jax.device_put(
                base.weights, NamedSharding(self.mesh, P())),
            hist=DistanceHistogram(
                edges=jax.device_put(
                    hist.edges, NamedSharding(self.mesh, P())),
                cdf=jax.device_put(
                    hist.cdf, NamedSharding(self.mesh, P())),
            ),
            kind=base.kind, summary=base.summary,
            n_summary=base.n_summary, max_leaf=max_leaf,
            n_total=n, series_len=base.series_len,
        )
        return self

    # ------------- streaming writes (docs/INGEST.md) ------------------
    def _base_meta(self):
        """(n_total, series_len, hist) of the frozen base — from the
        stacked resident index when present, else from shard 0's
        spilled store (global metadata is replicated per shard)."""
        if self.stacked is not None:
            idx = self.stacked
            return int(idx.n_total), int(idx.series_len), idx.hist
        if not self.shard_dirs:
            raise ValueError("build() or open_spill() first")
        res = self._store(self.shard_dirs[0]).resident
        return int(res.n_total), int(res.series_len), res.hist

    def enable_writes(self) -> "DistributedEngine":
        """Arm the mutable tier (docs/INGEST.md): an in-memory
        :class:`repro.store.delta.DeltaTier` absorbing ``insert`` /
        ``delete`` at serving time — searched alongside the frozen
        store by every subsequent :meth:`query` — plus, when
        ``StoreSpec.auto_compact`` is set, the background daemon that
        re-freezes the delta into leaf-contiguous on-disk segments.
        Idempotent; ``insert``/``delete`` call it automatically."""
        from repro.store.delta import DeltaTier

        spec = self.store_spec or StoreSpec()
        if self._delta is None:
            # metadata reads (may open a store, takes _ooc_lock)
            # happen BEFORE _write_lock: _write_lock stays a leaf
            n_total, series_len, _ = self._base_meta()
            with self._write_lock:
                if self._delta is None:
                    if self._seg_dir is None:
                        if spec.spill_dir is not None:
                            self._seg_dir = os.path.join(
                                spec.spill_dir, "segments")
                            os.makedirs(self._seg_dir, exist_ok=True)
                        else:
                            self._seg_dir = tempfile.mkdtemp(
                                prefix="repro-segments-")
                    self._delta = DeltaTier(series_len,
                                            start_id=n_total)
        if spec.auto_compact:
            with self._write_lock:
                if self._compactor is None \
                        or not self._compactor.is_alive():
                    self._compactor_stop = threading.Event()
                    t = threading.Thread(
                        target=self._compact_loop,
                        name="delta-compactor", daemon=True)
                    self._compactor = t
                    t.start()
        return self

    def insert(self, rows, ids=None) -> np.ndarray:
        """Absorb rows into the delta tier at serving time; they are
        retrievable by the NEXT query() (bench_serve_load measures
        that freshness lag). Returns the assigned global ids
        (auto-allocated past the frozen id space when not supplied);
        inserting an existing id supersedes every older copy."""
        self.enable_writes()
        return self._delta.insert(rows, ids)

    def delete(self, ids) -> int:
        """Tombstone global ids everywhere — frozen base shards,
        compacted segments, and the delta memtable (kill-sequence
        rule, docs/INGEST.md)."""
        self.enable_writes()
        return self._delta.delete(ids)

    def compact(self) -> bool:
        """Re-freeze the live delta memtable into one leaf-contiguous
        on-disk segment (codec-aware via the ordinary save_index path)
        and publish it for serving. In-flight queries keep the
        snapshot they started with and never block; writes landing
        during the build go to the fresh active memtable. Returns True
        iff a segment was published. Runs on the background daemon
        when ``StoreSpec.auto_compact`` is set; safe to call manually
        either way (``begin_freeze`` serializes: a second concurrent
        compaction sees the freeze in flight and returns False)."""
        delta = self._delta
        if delta is None:
            return False
        batch = delta.begin_freeze()
        if batch is None:
            return False
        with obs.span("delta.compact", rows=int(batch.ids.shape[0])):
            try:
                seg = self._build_segment(batch)
            except BaseException:  # re-raised: the fold-back must run even for KeyboardInterrupt/SystemExit or the frozen batch's writes would be silently lost
                delta.abort_freeze()
                raise
            delta.publish_segment(seg)
        return True

    def _segment_codec(self) -> str:
        """The leaf codec segments are persisted with: the base
        shards' (so the rebuilt-from-scratch oracle store and the
        frozen+delta pair encode rows identically); falls back to the
        StoreSpec for resident-only engines."""
        if self.shard_dirs:
            return self._store(self.shard_dirs[0]).codec
        return (self.store_spec or StoreSpec()).codec

    def _build_segment(self, batch) -> EngineSegment:
        """Freeze one delta batch into an on-disk segment store: build
        a FrozenIndex over the batch rows with the SAME method/params
        as the base and the GLOBAL histogram (per-segment r_delta
        keeps single-node semantics, exactly like shards), re-map
        builder-local row ids to the batch's global ids, and save
        under segments/seg_NNNN with the base codec. Resident engines
        additionally keep the pre-encode f32 index for serving
        (EngineSegment docstring)."""
        n_base, _, hist = self._base_meta()
        ispec = self.index_spec or IndexSpec(method=self.method)
        builder = _BUILDERS[ispec.method]
        idx = builder(batch.rows, hist=hist,
                      key=jax.random.PRNGKey(0), **ispec.build_params)
        local_ids = np.asarray(idx.ids)
        gids = np.asarray(batch.ids, np.int64)
        ext = np.where(
            local_ids >= 0,
            gids[np.clip(local_ids, 0, gids.shape[0] - 1)], -1)
        idx = dataclasses.replace(
            idx, ids=jnp.asarray(ext, jnp.int32), n_total=n_base)
        with self._write_lock:  # leaf: segment numbering only
            seq = self._seg_seq
            self._seg_seq += 1
        d = os.path.join(self._seg_dir, f"seg_{seq:04d}")
        codec = self._segment_codec()
        if codec == "pq":
            from repro.store.layout import PQ_K
            if batch.rows.shape[0] < PQ_K:
                # pq codebooks train one centroid per code (PQ_K of
                # them) — a memtable smaller than that cannot train a
                # meaningful quantizer, and pq exists to shrink the
                # BIG frozen payload anyway: persist the small segment
                # lossless instead of crashing the compactor
                codec = "f32"
        idx.save(d, codec=codec)
        return EngineSegment(
            dir=d, born_seq=batch.born_seq,
            n_rows=int(batch.ids.shape[0]), ids_np=ext,
            index=idx if self.stacked is not None else None)

    def _compact_loop(self) -> None:
        """Body of the background compaction daemon
        (``StoreSpec.auto_compact``): poll the delta tier every
        ``compact_interval_s`` and compact once the live memtable
        crosses ``delta_max_rows``."""
        spec = self.store_spec or StoreSpec()
        stop = self._compactor_stop
        while not stop.wait(spec.compact_interval_s):
            delta = self._delta
            if delta is None or not delta.freeze_threshold_reached(
                    spec.delta_max_rows):
                continue
            try:
                self.compact()
            except Exception:  # noqa: BLE001 the daemon must outlive any one failed compaction (disk full, transient build error): the frozen batch already folded back into the memtable via abort_freeze, so count it and retry next tick
                obs.REGISTRY.counter("delta.compaction_errors").inc()

    def _stop_compactor(self) -> None:
        """Stop the compaction daemon if running (idempotent; close()
        and build() call it). The thread is joined OUTSIDE
        _write_lock — its body takes that lock for segment
        numbering."""
        with self._write_lock:
            t, self._compactor = self._compactor, None
            ev, self._compactor_stop = self._compactor_stop, None
        if ev is not None:
            ev.set()
        if t is not None and t.is_alive():
            t.join(timeout=10.0)

    def _mutable_view(self, snap) -> _MutView:
        """Precompute what serving one snapshot jointly needs: the
        joint r_delta N and every published segment's tombstone mask.
        ``base_dead`` counts kills landing in the frozen id range
        [0, n_base) — range-sharded build assigns exactly those ids —
        so deletes of never-inserted ids cost nothing."""
        n_base, _, _ = self._base_meta()
        base_dead = 0
        if snap.kills:
            kid = np.fromiter(snap.kills.keys(), np.int64,
                              count=len(snap.kills))
            base_dead = int(((kid >= 0) & (kid < n_base)).sum())
        seg_dead = []
        seg_live = 0
        for seg in snap.segments:
            m = self._unit_dead(("seg", seg.dir), seg.ids_np,
                                seg.born_seq, snap)
            seg_dead.append(m)
            seg_live += seg.n_rows - int(m.sum())
        joint_n = joint_n_total(n_base, base_dead,
                                seg_live + snap.live_rows)
        return _MutView(snap=snap, joint_n=joint_n,
                        seg_dead=tuple(seg_dead))

    def _unit_dead(self, unit, ids_np, born_seq: int, snap,
                   pad_to: Optional[int] = None) -> np.ndarray:
        """One frozen unit's tombstone mask under this snapshot,
        cached by kills_version (recomputing np.isin per query would
        dominate small-batch serving between writes). Lock-free like
        _query_fns: dict get/set are GIL-atomic, version equality
        keys the hit, and racing queries recompute interchangeable
        masks from their own consistent snapshots."""
        hit = self._dead_cache.get(unit)
        if hit is not None and hit[0] == snap.kills_version:
            mask = hit[1]
        else:
            mask = snap.dead_mask(ids_np, born_seq)
            self._dead_cache[unit] = (snap.kills_version, mask)
        if pad_to is not None and pad_to > mask.shape[0]:
            mask = np.pad(mask, (0, pad_to - mask.shape[0]))
        return mask

    # ------------------------------------------------------------------
    def query(
        self, queries, k: int, g: Guarantee = Guarantee(),
        visit_batch: int = 1, sync_bsf: bool = False,
        ooc: Optional[bool] = None, ooc_opts: Optional[dict] = None,
    ) -> QueryResult:
        """Batched distributed k-NN with the requested guarantee.

        Spill-built shards are first class: when the engine has no
        HBM-resident index (``build(keep_resident=False)`` or
        :meth:`open_spill`) the query runs the out-of-core path —
        detected automatically, or forced with ``ooc=True`` on an
        engine that holds both. ``ooc_opts`` forwards out-of-core
        knobs (share_gathers / cache_leaves / prefetch /
        prefetch_depth / rerank / frontier) to search_ooc, plus the
        fault-tolerance knobs the engine consumes itself
        (docs/FAULT.md): ``fault`` (a repro.fault.FaultInjector),
        ``retry`` (a serve.fault.RetryPolicy), ``workers`` (shard
        owner pool width; default min(n_shards, 8), 1 = the
        sequential fold). Per-shard caches stay warm across queries.

        Re-entrant: concurrent ``query()`` calls (the continuous-
        batching serving lanes each keep one in flight) return answers
        bit-exact to serial execution — per-query state travels on the
        returned :class:`QueryResult` (``.stats`` carries the
        aggregate per-shard OocStats, including the degradation block
        when a shard was lost past its replicas), and shared warm
        caches are serialized per shard copy so two queries never
        interleave on one slot pool."""
        # the mutable tier is snapshotted FIRST: everything below this
        # line — base shards, segments, memtable scan, tombstone
        # masks, joint N — serves one consistent point in time, however
        # many writes land while the query runs (docs/INGEST.md)
        mut = None
        if self._delta is not None:
            snap = self._delta.snapshot()
            if snap.live_rows or snap.kills or snap.segments:
                mut = self._mutable_view(snap)
        if ooc is None:
            ooc = self.stacked is None and self.shard_dirs is not None
        if ooc:
            if sync_bsf:
                # the sequential per-shard host loops do not exchange
                # a running best-so-far yet (each shard prunes against
                # its own) — seeding shard i+1's pool from the fold of
                # shards 0..i is the ROADMAP follow-up; until then the
                # flag must not be silently swallowed
                warnings.warn(
                    "sync_bsf is not supported on the out-of-core "
                    "path: shards are searched without cross-shard "
                    "best-so-far exchange (results are identical, "
                    "bytes-read/leaves-visited are not tightened).",
                    UserWarning, stacklevel=2)
            return self._query_ooc(queries, k, g, visit_batch,
                                   dict(ooc_opts or {}), mut=mut)
        assert self.stacked is not None, "build() first"
        idx = self.stacked
        b = queries.shape[0]
        if mut is not None:
            return self._query_resident_mut(idx, queries, k, g,
                                            visit_batch, sync_bsf, mut)
        cache_key = (k, g.delta, g.epsilon, g.nprobe, visit_batch,
                     sync_bsf, b, queries.shape[-1])
        cached = self._query_fns.get(cache_key)
        if cached is not None:
            return self._run_resident(cached, idx, queries, k, b)
        axes = self.axes
        spec_shard = P(axes if len(axes) > 1 else axes[0])
        in_specs = (
            FrozenIndex(
                box_lo=spec_shard, box_hi=spec_shard, offsets=spec_shard,
                data=spec_shard, ids=spec_shard, weights=P(),
                hist=DistanceHistogram(edges=P(), cdf=P()),
                kind=idx.kind, summary=idx.summary,
                n_summary=idx.n_summary, max_leaf=idx.max_leaf,
                n_total=idx.n_total, series_len=idx.series_len,
                row_norms=spec_shard,
            ),
            P(),  # queries replicated
        )

        delta, epsilon, nprobe = g.delta, g.epsilon, g.nprobe

        def local(lidx: FrozenIndex, q) -> SearchResult:
            # search_impl, not search: an inner jit under shard_map
            # miscompiles the refinement loop on jax 0.4.x.
            # repro: allow[jax-while-shard-map] deliberate: this closure is dispatched ONLY through the eager jax.shard_map below (never under jit) precisely because of the 0.4.37 miscompile — ROADMAP pin notes
            res = search_impl(
                lidx, q, k, delta=delta, epsilon=epsilon,
                nprobe=nprobe, visit_batch=visit_batch,
                sync_axes=tuple(axes) if sync_bsf else ())
            # gather per-shard top-k along a new leading axis and merge
            all_d = jax.lax.all_gather(res.dists, axes[-1], tiled=False)
            all_i = jax.lax.all_gather(res.ids, axes[-1], tiled=False)
            if len(axes) > 1:
                for ax in axes[:-1]:
                    all_d = jax.lax.all_gather(all_d, ax, tiled=False)
                    all_i = jax.lax.all_gather(all_i, ax, tiled=False)
                all_d = all_d.reshape(-1, b, k)
                all_i = all_i.reshape(-1, b, k)
            md = all_d.transpose(1, 0, 2).reshape(b, -1)
            mi = all_i.transpose(1, 0, 2).reshape(b, -1)
            sd, si = jax.lax.sort((md, mi), num_keys=1)
            leaves = jax.lax.psum(res.leaves_visited, axes)
            rows = jax.lax.psum(res.rows_scanned, axes)
            lbs = jax.lax.psum(res.lb_computed, axes)
            return SearchResult(sd[:, :k], si[:, :k], leaves, rows, lbs)

        out_specs = SearchResult(P(), P(), P(), P(), P())
        # The shard_map'ed fn is called EAGERLY on purpose: on jax
        # 0.4.x, putting this under jax.jit (inner OR outer) miscompiles
        # the refinement while_loop — verified wrong neighbors on
        # 0.4.37; eager execution is correct. Reusing the same wrapped
        # callable via _query_fns still avoids per-call closure
        # rebuilding and retracing.
        fn = jax.shard_map(
            local, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )
        self._query_fns[cache_key] = fn
        return self._run_resident(fn, idx, queries, k, b)

    def _run_resident(self, fn, idx, queries, k: int, b: int
                      ) -> QueryResult:
        """Dispatch the (cached) shard_map'ed resident query under an
        ``engine.query`` span, timing the eager call as
        ``engine.dispatch`` (``QueryResult.dispatch_s``). Nothing here
        syncs the device; the span's attributes are read back only
        while obs records. The resident path has no I/O to account, so
        ``stats`` is None — thread-safe by construction (eager
        shard_map dispatch touches no per-query engine state)."""
        dispatch = obs.Tally("engine.dispatch")
        with obs.span("engine.query", path="resident", lanes=b, k=k,
                      shards=self.n_shards) as sp:
            with dispatch:
                res = fn(idx, queries)
            if obs.enabled():
                sp.set(leaves_visited=int(np.asarray(
                           res.leaves_visited).sum()),
                       rows_scanned=int(np.asarray(
                           res.rows_scanned).sum()))
        return QueryResult(*res, dispatch_s=dispatch.seconds)

    def _dead_stacked_dev(self, mut: _MutView):
        """The [S * max_rows] tombstone operand for the resident
        shard_map (laid out like the stacked index: one block per
        shard), rebuilt only when the kill set advances — the
        steady-state query between writes reuses the cached device
        array. Same lock-free versioned-cache discipline as
        _dead_cache."""
        snap = mut.snap
        hit = self._dead_stacked
        if hit is not None and hit[0] == snap.kills_version:
            return hit[1]
        ids_host = self._shard_ids_host
        if ids_host is None:  # e.g. checkpoint-restored stacked index
            ids_host = list(np.asarray(self.stacked.ids).reshape(
                self.n_shards, -1))
            self._shard_ids_host = ids_host
        masks = np.concatenate([
            self._unit_dead(("rshard", si), ids, 0, snap)
            for si, ids in enumerate(ids_host)])
        spec0 = P(self.axes if len(self.axes) > 1 else self.axes[0])
        dev = jax.device_put(masks, NamedSharding(self.mesh, spec0))
        self._dead_stacked = (snap.kills_version, dev)
        return dev

    def _query_resident_mut(self, idx, queries, k: int, g: Guarantee,
                            visit_batch: int, sync_bsf: bool,
                            mut: _MutView) -> QueryResult:
        """The resident path with the mutable tier armed: the same
        eager shard_map search as :meth:`query`, plus (a) the
        per-shard tombstone mask as a third operand and (b) the joint
        live-N for r_delta — then the segment + memtable fold
        (:meth:`_fold_mutable`). The closure is rebuilt per call: it
        closes over joint_n, which moves with every insert, and
        dispatch is eager anyway (no compile cache to protect —
        _query_fns exists to avoid RETRACING, which eager closures
        never do)."""
        g.validate()
        b = queries.shape[0]
        axes = self.axes
        spec_shard = P(axes if len(axes) > 1 else axes[0])
        in_specs = (
            FrozenIndex(
                box_lo=spec_shard, box_hi=spec_shard, offsets=spec_shard,
                data=spec_shard, ids=spec_shard, weights=P(),
                hist=DistanceHistogram(edges=P(), cdf=P()),
                kind=idx.kind, summary=idx.summary,
                n_summary=idx.n_summary, max_leaf=idx.max_leaf,
                n_total=idx.n_total, series_len=idx.series_len,
                row_norms=spec_shard,
            ),
            spec_shard,  # [S * max_rows] tombstones, one block per shard
            P(),         # queries replicated
        )
        delta, epsilon, nprobe = g.delta, g.epsilon, g.nprobe
        joint_n = mut.joint_n

        def local_mut(lidx: FrozenIndex, dead_l, q) -> SearchResult:
            # search_impl, not search: an inner jit under shard_map
            # miscompiles the refinement loop on jax 0.4.x.
            # repro: allow[jax-while-shard-map] deliberate: dispatched ONLY through the eager jax.shard_map below (never under jit), same 0.4.37 miscompile rationale as the immutable closure above
            res = search_impl(
                lidx, q, k, delta=delta, epsilon=epsilon,
                nprobe=nprobe, visit_batch=visit_batch,
                dead=dead_l, n_override=joint_n,
                sync_axes=tuple(axes) if sync_bsf else ())
            all_d = jax.lax.all_gather(res.dists, axes[-1], tiled=False)
            all_i = jax.lax.all_gather(res.ids, axes[-1], tiled=False)
            if len(axes) > 1:
                for ax in axes[:-1]:
                    all_d = jax.lax.all_gather(all_d, ax, tiled=False)
                    all_i = jax.lax.all_gather(all_i, ax, tiled=False)
                all_d = all_d.reshape(-1, b, k)
                all_i = all_i.reshape(-1, b, k)
            md = all_d.transpose(1, 0, 2).reshape(b, -1)
            mi = all_i.transpose(1, 0, 2).reshape(b, -1)
            sd, si = jax.lax.sort((md, mi), num_keys=1)
            leaves = jax.lax.psum(res.leaves_visited, axes)
            rows = jax.lax.psum(res.rows_scanned, axes)
            lbs = jax.lax.psum(res.lb_computed, axes)
            return SearchResult(sd[:, :k], si[:, :k], leaves, rows, lbs)

        out_specs = SearchResult(P(), P(), P(), P(), P())
        fn = jax.shard_map(
            local_mut, mesh=self.mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        )
        dead_dev = self._dead_stacked_dev(mut)
        qj = jnp.asarray(queries)
        dispatch = obs.Tally("engine.dispatch")
        with obs.span("engine.query", path="resident+delta", lanes=b,
                      k=k, shards=self.n_shards,
                      delta_rows=mut.snap.live_rows,
                      segments=len(mut.snap.segments)) as sp:
            with dispatch:
                res = fn(idx, dead_dev, qj)
            out = self._fold_mutable(
                QueryResult(*res, dispatch_s=dispatch.seconds), mut, qj,
                k, g, visit_batch, resident=True)
            if obs.enabled():
                sp.set(leaves_visited=int(np.asarray(
                           out.leaves_visited).sum()),
                       rows_scanned=int(np.asarray(
                           out.rows_scanned).sum()))
        return out

    def _fold_mutable(self, base: QueryResult, mut: _MutView, qj,
                      k: int, g: Guarantee, visit_batch: int, *,
                      resident: bool) -> QueryResult:
        """Fold the mutable tier into the frozen-base answer: every
        published segment is served as one more shard — resident
        engines score the kept pre-encode index with the shared eager
        search_impl (same arithmetic as the resident base), OOC
        engines serve the segment's on-disk store through search_ooc
        (codec-faithful) — and the memtable snapshot is brute-scored
        last (store.delta.search_snapshot), all through
        ``ops.topk_merge_unique``. The kill rule guarantees at most
        one live copy of any id across the operands, the merge's
        distinct-id precondition; the merge is a commutative
        (d, id)-lex selection, so this staged fold equals the
        from-scratch rebuild's single sort bit for bit."""
        from repro.store.delta import search_snapshot
        from repro.store.ooc import search_ooc

        snap = mut.snap
        top_d, top_i = base.dists, base.ids
        leaves = np.asarray(base.leaves_visited, np.int64).copy()
        rows = np.asarray(base.rows_scanned, np.int64).copy()
        lbs = int(base.lb_computed)
        b = qj.shape[0]
        for seg, dead in zip(snap.segments, mut.seg_dead):
            dead_arg = jnp.asarray(dead) if dead.any() else None
            if resident and seg.index is not None:
                res = search_impl(
                    seg.index, qj, k, delta=g.delta,
                    epsilon=g.epsilon, nprobe=g.nprobe,
                    visit_batch=visit_batch, dead=dead_arg,
                    n_override=mut.joint_n)
                sd, si = res.dists, res.ids
                leaves += np.asarray(res.leaves_visited, np.int64)
                rows += np.asarray(res.rows_scanned, np.int64)
                lbs += int(res.lb_computed)
            else:
                with self._copy_lock(seg.dir):
                    store = self._store(seg.dir)
                    cache = self._shard_cache(
                        seg.dir, store, b * visit_batch, None,
                        prefetch_depth=1, prefetch=True)
                    out = search_ooc(
                        store, qj, k, g, visit_batch=visit_batch,
                        cache=cache, dead=dead_arg,
                        n_override=mut.joint_n)
                r = out.result
                sd, si = r.dists, r.ids
                leaves += np.asarray(r.leaves_visited, np.int64)
                rows += np.asarray(r.rows_scanned, np.int64)
                lbs += int(r.lb_computed)
            top_d, top_i = ops.topk_merge_unique(sd, si, top_d, top_i)
        sd, si = search_snapshot(
            snap, qj, k,
            codec="f32" if resident else self._segment_codec())
        top_d, top_i = ops.topk_merge_unique(sd, si, top_d, top_i)
        rows += snap.live_rows  # the memtable scan touches every row
        return QueryResult(
            dists=top_d, ids=top_i,
            leaves_visited=jnp.asarray(leaves, jnp.int32),
            rows_scanned=jnp.asarray(rows, jnp.int32),
            lb_computed=jnp.int32(lbs),
            stats=base.stats,
            dispatch_s=base.dispatch_s,
        )

    # ------------------------------------------------------------------
    def _copy_lock(self, d: str) -> threading.RLock:
        """The serving lock for one shard store copy (lazily created
        under ``_ooc_lock``, held for a whole per-shard search):
        concurrent queries — serving lanes each keep one in flight —
        serialize per copy because the warm DeviceLeafCache slot pool
        is single-query state (another query's get_slots may evict a
        slot this one is about to gather from, which would break the
        bit-exact-vs-serial contract). Within one query the shard
        owners touch DISTINCT copies, so PR 8's concurrent fold is
        unaffected."""
        with self._ooc_lock:
            lk = self._copy_locks.get(d)
            if lk is None:
                lk = self._copy_locks[d] = threading.RLock()
            return lk

    def _store(self, d: str):
        """The (lazily opened, cached) store for one shard copy —
        lock-guarded: concurrent shard owners open their stores in
        parallel on the first query."""
        with self._ooc_lock:
            store = self._stores.get(d)
        if store is not None:
            return store
        from repro.store import load_index
        store = load_index(d, resident="summaries")
        with self._ooc_lock:
            # a concurrent open of the same dir (close() racing a
            # query) keeps the first registered handle
            return self._stores.setdefault(d, store)

    def _shard_cache(self, d: str, store, need_leaves: int,
                     cache_leaves: Optional[int], *,
                     prefetch_depth: int, prefetch: bool):
        """The shard copy's persistent warm cache + prefetcher,
        re-validated per query: a cache whose capacity cannot pin this
        query's per-iteration working set (b * visit_batch leaves —
        batch sizes vary per guarantee group in the serving front) is
        retired and rebuilt larger, and the prefetcher thread persists
        with the cache instead of being spawned and joined per query
        (its staging depth grows with the requested lookahead).

        Runs under ``_ooc_lock`` end to end: owners touch DISTINCT
        dirs so the serialization costs nothing on the steady path,
        and it makes the dict re-validation atomic against a
        concurrent ``close()`` (mid-query close retires the cache;
        this query keeps its own reference and finishes on it)."""
        from repro.store import DeviceLeafCache, LeafPrefetcher

        need = max(int(need_leaves), 1)
        with self._ooc_lock:
            cache = self._shard_caches.get(d)
            if cache is not None \
                    and cache.capacity < min(need,
                                             max(store.num_leaves, 1)):
                if cache.prefetcher is not None:
                    cache.prefetcher.close()
                    cache.prefetcher = None
                cache = None
            if cache is None:
                cap = cache_leaves if cache_leaves is not None \
                    else max(store.num_leaves // 8, 1)
                cap = min(max(cap, need), max(store.num_leaves, 1))
                cache = DeviceLeafCache(store, cap)
                self._shard_caches[d] = cache
            else:
                # warm CONTENTS persist across queries (the serving
                # regime); counters reset so QueryResult.stats reports
                # this query's bytes, not the cache's lifetime
                cache.reset_counters()
            if prefetch:
                depth = max(2, prefetch_depth + 1)
                if cache.prefetcher is not None \
                        and cache.prefetcher.depth < depth:
                    cache.prefetcher.close()
                    cache.prefetcher = None
                if cache.prefetcher is None:
                    cache.prefetcher = LeafPrefetcher(store,
                                                      depth=depth)
        return cache

    def close(self) -> None:
        """Release out-of-core serving state: stop every per-shard
        prefetcher thread and drop the warm caches/stores. build()
        calls this before rebuilding; harmless on a resident-only
        engine. Idempotent and thread-safe: state is snapshotted and
        detached under the lock, prefetcher threads are joined outside
        it (a query in flight keeps its own cache reference and falls
        back to demand reads once its prefetcher stops). The delta
        tier's DATA survives a close — only the compaction daemon
        stops (a later insert()/enable_writes() restarts it);
        build() additionally resets the tier for the new rows."""
        self._stop_compactor()
        with self._ooc_lock:
            caches = list(self._shard_caches.values())
            self._shard_caches.clear()
            self._stores.clear()
        for cache in caches:
            if cache.prefetcher is not None:
                cache.prefetcher.close()
                cache.prefetcher = None

    def _query_ooc(self, queries, k: int, g: Guarantee,
                   visit_batch: int, opts: dict,
                   mut: Optional[_MutView] = None) -> QueryResult:
        """Serve the query batch from the spilled shard stores:
        CONCURRENT shard owners (one worker per shard, pool width
        ``workers``) each drive the host refinement loop over their
        store — the SAME shared core search_impl traces
        (core/refine.py) — and stream their answers into a cross-shard
        ``ops.topk_merge_unique`` fold on this thread as they land.
        Completion order cannot change the answer: the merge is a
        commutative, associative (d, id)-lex selection over globally
        disjoint ids, so the fold equals the sequential fold bit for
        bit. Parity with the resident shard_map path: per-shard
        results are bit-exact to the resident per-shard search for
        lossless codecs (tests/test_store.py) and both merges select
        the k smallest distances — so ids AND dists match the resident
        engine answer bit-for-bit (modulo cross-shard ties, which
        (d, id)-lex ordering resolves deterministically). Guarantee
        preservation is the same argument as the shard_map path
        (module docstring): every shard's answer satisfies the local
        guarantee against the GLOBAL histogram/n_total persisted in
        its store, and the merge only improves each rank.

        Fault tolerance (docs/FAULT.md): each shard serve runs under
        serve/fault.serve_shard_with_failover — retries with capped
        backoff across the shard's store copies (round-robin owner
        first), per-attempt deadlines checked cooperatively inside the
        host loop, a persistent circuit breaker skipping copies that
        keep failing. A shard lost past every copy degrades the
        answer instead of failing the query: the fold completes over
        the survivors and the returned ``QueryResult.stats`` carries
        ``degraded`` / ``shards_lost`` / ``effective_delta`` with delta recomputed
        from the global histogram mass the missing rows own
        (core.guarantees.effective_delta_after_loss)."""
        from repro.serve import fault as sfault
        from repro.store.ooc import search_ooc

        from .guarantees import effective_delta_after_loss

        if not self.shard_dirs:
            raise ValueError(
                "no spilled shards: build(spill_dir=...) or "
                "open_spill() first")
        g.validate()
        qj = jnp.asarray(queries)
        b = qj.shape[0]
        cache_leaves = opts.pop("cache_leaves", None)
        injector = opts.pop("fault", None)
        policy = opts.pop("retry", None) or sfault.RetryPolicy()
        n_sh = len(self.shard_dirs)
        workers = int(opts.pop("workers", 0) or min(n_sh, 8))
        prefetch_depth = int(opts.get("prefetch_depth", 1))
        prefetch = bool(opts.get("prefetch", True))
        replica_dirs = self.shard_replica_dirs \
            or tuple((d,) for d in self.shard_dirs)
        with self._ooc_lock:
            if self._breaker is None:
                self._breaker = sfault.CircuitBreaker()
            breaker = self._breaker

        def attempt_for(si):
            def attempt(d, fctx):
                # one query's use of one copy is one critical section
                # (_copy_lock): cache revalidation, counter window and
                # slot-pool occupancy stay single-query even when
                # serving lanes race on the same shard. An attempt
                # that waits out its deadline here fails on its first
                # in-loop check and falls over to another copy — a
                # DIFFERENT lock — instead of queueing forever.
                with self._copy_lock(d):
                    store = self._store(d)
                    cache = self._shard_cache(
                        d, store, b * visit_batch, cache_leaves,
                        prefetch_depth=prefetch_depth,
                        prefetch=prefetch)
                    dead = None
                    n_over = None
                    if mut is not None:
                        # replica copies are byte-identical to the
                        # primary (same ids array), so the mask is
                        # keyed by SHARD, shared across copies
                        m = self._unit_dead(
                            ("sshard", si),
                            np.asarray(store.resident.ids), 0,
                            mut.snap, pad_to=store.mmap.shape[0])
                        dead = m if m.any() else None
                        n_over = mut.joint_n
                    # the child ooc.query span carries the shard's
                    # bytes_read attr — one subtree level owns each
                    # numeric attr, so QueryProfile.total() never
                    # double-counts. Worker-thread spans root their
                    # own per-thread subtree (obs/trace.py).
                    with obs.span("engine.shard", shard=si,
                                  copy=fctx.replica):
                        return search_ooc(
                            store, qj, k, g,
                            visit_batch=visit_batch, cache=cache,
                            fault=fctx, dead=dead,
                            n_override=n_over, **opts)
            return attempt

        def serve_one(si):
            copies = replica_dirs[si]
            # round-robin ownership: shard si's owner is copy
            # (si % R); failover walks the remaining copies in order
            order = tuple(copies[(si + j) % len(copies)]
                          for j in range(len(copies)))
            return sfault.serve_shard_with_failover(
                attempt_for(si), shard=si, replica_dirs=order,
                policy=policy, breaker=breaker, injector=injector)

        top_d = jnp.full((b, k), jnp.inf, jnp.float32)
        top_i = jnp.full((b, k), -1, jnp.int32)
        leaves = np.zeros(b, np.int64)
        rows = np.zeros(b, np.int64)
        lbs = 0
        per_shard = []
        infos = []
        lost = []
        with obs.span("engine.query", path="ooc", lanes=b, k=k,
                      shards=n_sh, workers=workers) as root:

            def fold(si, served):
                out, info = served
                out.stats.retries = info.retries
                out.stats.failovers = info.failovers
                obs.REGISTRY.counter(
                    "engine.shard.bytes_read", shard=str(si)).inc(
                        out.stats.bytes_read)
                r = out.result
                # shard dists are already sqrt'd like the resident
                # merge operands; ids are globally disjoint across
                # shards, so the unique-merge's dedup is a no-op — it
                # is used for its (d, id)-lex selection and its
                # explicit precondition
                nonlocal top_d, top_i, lbs, leaves, rows
                top_d, top_i = ops.topk_merge_unique(
                    r.dists, r.ids, top_d, top_i)
                leaves += np.asarray(r.leaves_visited, np.int64)
                rows += np.asarray(r.rows_scanned, np.int64)
                lbs += int(r.lb_computed)
                per_shard.append(out.stats)
                infos.append(info)

            if workers <= 1 or n_sh == 1:
                # sequential fold: no worker threads, spans nest
                # under this root exactly as before PR 8
                for si in range(n_sh):
                    try:
                        served = serve_one(si)
                    except sfault.ShardLost:
                        lost.append(si)
                        continue
                    fold(si, served)
            else:
                with ThreadPoolExecutor(
                        max_workers=min(workers, n_sh),
                        thread_name_prefix="shard-owner") as ex:
                    futs = {ex.submit(serve_one, si): si
                            for si in range(n_sh)}
                    for fut in as_completed(futs):
                        si = futs[fut]
                        try:
                            served = fut.result()
                        except sfault.ShardLost:
                            lost.append(si)
                            continue
                        fold(si, served)
            if len(lost) == n_sh:
                raise sfault.ShardLost(
                    -1, RuntimeError(
                        f"every shard lost ({sorted(lost)}): no "
                        "surviving answer to degrade to"))
            stats = OocStats.aggregate(per_shard)
            stats.effective_delta = float(g.delta)
            if lost:
                self._degrade(stats, sorted(lost), infos, top_d, k, g,
                              effective_delta_after_loss)
                root.set(degraded=True, shards_lost=stats.shards_lost,
                         effective_delta=stats.effective_delta)
            root.set(bytes_read_total=stats.bytes_read,
                     iterations=stats.iterations)
        out = QueryResult(
            dists=top_d, ids=top_i,
            leaves_visited=jnp.asarray(leaves, jnp.int32),
            rows_scanned=jnp.asarray(rows, jnp.int32),
            lb_computed=jnp.int32(lbs),
            stats=stats,
        )
        if mut is not None:
            out = self._fold_mutable(out, mut, qj, k, g, visit_batch,
                                     resident=False)
        return out

    def _degrade(self, stats: OocStats, lost, infos, top_d, k: int,
                 g: Guarantee, effective_delta_after_loss) -> None:
        """Downgrade the answer's guarantee honestly after shard loss:
        count the rows the fold never saw (global n_total minus the
        survivors' real rows — robust to uneven range-sharding) and
        recompute delta from the global histogram mass those rows own
        at each lane's surviving kth distance. The result is a
        delta-epsilon guarantee whatever the request was — exact and
        epsilon claims cannot survive unseen rows."""
        surv = [self._store(i.served_dir) for i in infos]
        n_total = int(surv[0].resident.n_total)
        n_seen = sum(
            int((np.asarray(s.resident.ids) >= 0).sum()) for s in surv)
        n_lost = max(n_total - n_seen, 0)
        stats.degraded = True
        stats.shards_lost = len(lost)
        stats.effective_delta = effective_delta_after_loss(
            surv[0].resident.hist, np.asarray(top_d[:, k - 1]),
            n_lost, delta=g.delta, epsilon=g.epsilon)
        obs.REGISTRY.counter("engine.degraded_queries").inc()
        obs.REGISTRY.counter("engine.shards_lost").inc(len(lost))
        warnings.warn(
            f"shards {lost} lost past retries and replicas: answer "
            f"degraded to delta-epsilon with effective_delta="
            f"{stats.effective_delta:.3g} over {n_lost} unseen rows "
            "(docs/FAULT.md)", UserWarning, stacklevel=4)
