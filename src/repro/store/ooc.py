"""Out-of-core Algorithm 2: device filter, streamed refinement.

Semantics are IDENTICAL to core.search.search — this module does not
mirror the refinement loop, it DRIVES the same one: frontier
tick/advance, candidate layout, duplicate-leaf masking, the
codec-dispatched score+merge step and the stopping predicates are all
the shared core/refine.py functions (search_impl traces them inside
its lax.while_loop; this host loop calls them jitted), so the exact /
epsilon / delta-epsilon guarantees transfer untouched. The ONLY
difference is residency, supplied by two LeafSource implementations:

  CachedStoreSource   f32/bf16 leaves gathered from the
                      DeviceLeafCache slot pool (fed from disk through
                      the prefetcher); fused-L2 scoring over the
                      ENCODED slots (bf16 upcasts inside the kernel —
                      bit-exact to in-memory search over the bfloat16
                      index).
  PQSource            uint8 PQ codes ADC-scored on device (the
                      kernels/pq_adc one-hot MXU trick); the loop
                      tracks padded row POSITIONS and ``finalize`` runs
                      the exact re-rank against ``exact.bin`` so the
                      epsilon/delta-epsilon guarantee checks survive
                      the lossy payload. Carve-out: the EXACT
                      (epsilon=0) guarantee does NOT survive pq — the
                      stop predicate's kth-best is an ADC approximation
                      that can prune the true neighbor's leaf early;
                      search_ooc warns if asked for it.

Control flow moves from lax.while_loop to a host loop because each
iteration performs I/O. The host loop:

  1. ticks the (shared) frontier for this iteration's leaf window;
  2. makes those leaves cache-resident (one batched h2d upload);
  3. schedules the next ``prefetch_depth`` visit windows on the
     prefetcher, so the disk reads overlap the device scoring it is
     about to launch;
  4. runs the jitted shared refine step (gather from slots ->
     decode/score -> topk merge) on device;
  5. pulls back the per-lane kth-best and evaluates the shared
     stopping predicates in numpy f32 (bit-identical arithmetic to the
     device f32 ops of the in-memory loop).

Cooperative scoring (``share_gathers=True``) is search_impl's
cooperative branch verbatim — the same refine_step corner with the
cache slot pool as the gather pool (for pq: the fused
ops.pq_adc_select kernel, which on TPU streams the uint8 codes
through the one-hot MXU contraction tile by tile so the [B, B*V*M]
ADC matrix never reaches HBM — docs/PERF.md §4).
"""

from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import refine
from repro.core.histogram import r_delta
from repro.core.refine import INF, Gathered, ScoreCtx, default_frontier
from repro.core.search import SearchResult
from repro.core.summaries.pq import adc_lut_batch
from repro.obs import OocStats

from .cache import DeviceLeafCache
from .layout import LeafStore
from .prefetch import LeafPrefetcher


class OocResult(NamedTuple):
    result: SearchResult
    stats: OocStats


@jax.jit
def _filter_stage(resident, q):
    """Lower bound every leaf (device) — the shared filter pass; the
    visit order is partially selected from it window by window."""
    return refine.leaf_lower_bounds(resident, q)


# jitted host-loop entry points over the SHARED core primitives (the
# in-memory while_loop traces the same functions inline — bit-exact
# visit order / scoring / stopping parity by construction)
_frontier_refill = jax.jit(refine.frontier_select,
                           static_argnames=("f",))
_frontier_tick = jax.jit(refine.frontier_tick,
                         static_argnames=("v", "lookahead"))
_frontier_advance = jax.jit(refine.frontier_advance,
                            static_argnames=("v",))
_frontier_window = jax.jit(refine.frontier_window,
                           static_argnames=("offset", "v"))
_refine_step = jax.jit(refine.refine_step,
                       static_argnames=("share", "pq", "force_pallas"))
_coop_mask = jax.jit(refine.coop_mask)


@functools.partial(jax.jit, static_argnames=("max_leaf", "clamp"))
def _slot_layout(offsets, leaf, slot, *, max_leaf, clamp):
    """The spilled gather's device side as one compiled call: the shared
    candidate_layout of the [B, V] window, and each position's index
    into the cache's [S, M, C] slot pool read as [S*M, C]. ``slot`` is
    the window's [B, V] cache slots, -1 where the visit is unusable
    (out of rank or lane stopped): those positions are invalid and
    gather slot 0, which the scoring step masks."""
    b, v = leaf.shape
    row_idx, valid = refine.candidate_layout(offsets, leaf, slot >= 0,
                                             max_leaf, clamp)
    gather_idx = (jnp.maximum(slot, 0)[:, :, None] * max_leaf
                  + jnp.arange(max_leaf, dtype=jnp.int32)[None, None, :])
    return gather_idx.reshape(b, v * max_leaf), row_idx, valid


class CachedStoreSource:
    """LeafSource over a LeafStore: leaves reach the device through a
    DeviceLeafCache (disk -> host buffer -> one batched h2d scatter),
    ``gather`` maps this iteration's window to cache slots, and
    ``prefetch`` hands the next windows to the attached prefetcher.
    Scoring is the shared refine_step with the slot pool as the gather
    pool (raw codecs: fused L2 over encoded slots)."""

    pq = False

    def __init__(self, store: LeafStore, cache: DeviceLeafCache, *,
                 prefetch: bool = True):
        self.store = store
        self.cache = cache
        self.prefetch_enabled = prefetch

    @property
    def resident(self):
        return self.store.resident

    def query_ctx(self, queries: jax.Array) -> ScoreCtx:
        res = self.store.resident
        return ScoreCtx(qf=jnp.asarray(queries, jnp.float32),
                        ids=res.ids, norms=res.row_norms, luts=None)

    def track_width(self, k: int) -> int:
        return k

    def gather(self, leaf: np.ndarray, ok: np.ndarray,
               leaf_dev: Optional[jax.Array] = None) -> Gathered:
        """Make the [B, V] window cache-resident and expose it as a
        refine_step gather pool. The full per-lane request list (dups
        included) feeds the cache so its per-request hit accounting
        credits lanes sharing a leaf. ``leaf_dev`` is the same window
        already on the device (the host loop's tick output); the layout
        is one compiled call and the pool is the cache's own slot
        buffer, handed over uncopied."""
        slot = np.full(leaf.shape, -1, np.int32)
        slot[ok] = self.cache.get_slots(leaf[ok].tolist())
        if leaf_dev is None:
            leaf_dev = jnp.asarray(leaf, jnp.int32)
        gather_idx, row_idx, valid = _slot_layout(
            self.resident.offsets, leaf_dev, jnp.asarray(slot),
            max_leaf=self.store.max_leaf,
            clamp=self.store.mmap.shape[0] - 1)
        return Gathered(buf=self.cache.slots, gather_idx=gather_idx,
                        row_idx=row_idx, valid=valid)

    def prefetch(self, windows) -> None:
        """Stage future visit windows ([(leaf [B, V], ok [B, V])],
        nearest first) on the attached prefetcher, skipping leaves
        already cache-resident — a warm cache must not touch the disk.
        ``prefetch=False`` disables scheduling even on an attached
        prefetcher: callers use it to measure pure demand reads."""
        pf = self.cache.prefetcher
        if not self.prefetch_enabled or pf is None:
            return
        for leaf_w, ok_w in windows:
            nxt = [int(lf) for lf in np.unique(leaf_w[ok_w])
                   if not self.cache.contains(int(lf))]
            if nxt:
                pf.schedule(nxt)

    def score(self, ctx, g, valid, top_d, top_i, *, share):
        return _refine_step(ctx, g.buf, g.gather_idx, g.row_idx,
                            valid, top_d, top_i, share=share,
                            pq=self.pq)

    def finalize(self, ctx, top_d, top_i, k: int):
        return top_d, top_i, 0


class PQSource(CachedStoreSource):
    """CachedStoreSource whose slots hold uint8 PQ codes: scoring is
    the refine_step pq corner (ADC LUTs in the query ctx, padded row
    positions as candidates) and ``finalize`` is the exact re-rank
    against raw exact.bin rows."""

    pq = True

    def __init__(self, store: LeafStore, cache: DeviceLeafCache, *,
                 rerank: int = 4, **kw):
        super().__init__(store, cache, **kw)
        if store.codebook is None:
            raise ValueError("codec='pq' store has no codebook")
        self.rerank = max(1, int(rerank))

    def query_ctx(self, queries: jax.Array) -> ScoreCtx:
        return ScoreCtx(qf=jnp.asarray(queries, jnp.float32),
                        ids=self.resident.ids, norms=None,
                        luts=adc_lut_batch(self.store.codebook, queries))

    def track_width(self, k: int) -> int:
        return k * self.rerank

    def finalize(self, ctx, top_d, top_i, k: int):
        return _exact_rerank(self.store, ctx.qf, top_d, top_i, k)


def _exact_rerank(store: LeafStore, qf, top_d, top_i, k: int):
    """Re-score the PQ candidate pool (padded row positions) in f32
    against raw rows from exact.bin; return exact top-k (d_sq, ids)
    plus the re-rank bytes read. Tiny random reads — each distinct
    candidate row is read once for the whole batch."""
    pos = np.asarray(top_i)                          # [B, kk]
    uniq = np.unique(pos[pos >= 0])
    n = store.series_len
    if uniq.size == 0:
        return top_d[:, :k], top_i[:, :k], 0
    rows = np.asarray(store.read_rows_exact(uniq), np.float32)
    rerank_bytes = int(uniq.size) * n \
        * int(np.dtype(store.exact_mmap.dtype
                       if store.exact_mmap is not None
                       else store.mmap.dtype).itemsize)
    gather = np.searchsorted(uniq, np.clip(pos, 0, None))
    cand = rows[gather]                              # [B, kk, n]
    # direct difference form, not the expanded |q|^2-2qx+|x|^2: the
    # expanded form loses ~1e-3 absolute accuracy to cancellation at
    # near-zero distances, which would break the "reported distances
    # are exact" contract of the re-rank (and the guarantee checks
    # when a query coincides with a stored series); the candidate
    # pool is tiny so the elementwise cost is irrelevant
    diff = jnp.asarray(cand) - jnp.asarray(qf)[:, None, :]
    d = jnp.sum(diff * diff, axis=-1)
    d = jnp.where(jnp.asarray(pos >= 0), d, INF)
    ids_h = np.asarray(store.resident.ids)
    cids = np.where(pos >= 0,
                    ids_h[np.clip(pos, 0, ids_h.shape[0] - 1)], -1)
    sd, si = jax.lax.sort((d, jnp.asarray(cids, jnp.int32)), num_keys=1)
    return sd[:, :k], si[:, :k], rerank_bytes


def _host_refine(
    src, queries: jax.Array, k: int, *, delta: float, epsilon: float,
    nprobe: Optional[int], visit_batch: int, share_gathers: bool,
    frontier: Optional[int], prefetch_depth: int, fault=None,
    dead: Optional[jax.Array] = None, n_override: Optional[int] = None,
):
    """The host-driven refinement loop over a LeafSource — the same
    Algorithm 2 iteration search_impl runs under lax.while_loop,
    executed step by step so each iteration can perform I/O. Returns
    (SearchResult with SQUARED final pool pre-finalize sqrt applied,
    refinement telemetry dict, rerank_bytes).

    Telemetry is read-only observation of values the loop already
    syncs to host (active mask, ranks, bsf, next_lb) — it cannot
    change visit order, scoring, or stopping arithmetic. Every
    device->host read goes through one helper that counts and times it
    under an ``ooc.sync`` span; the loop's host time (``loop_s``,
    ``gather_s``, ``sync_s``, ``host_syncs``) is always on. Spans never
    sync the device, so a traced loop keeps the untraced schedule.

    ``fault`` is the serving-layer injection hook (duck-typed —
    serve/fault.FaultContext in production): ``fault.check("gather")``
    runs before every leaf-gather I/O and ``fault.check("score")``
    before every device scoring step, which is where injected faults
    fire and cooperative per-attempt deadlines are polled
    (docs/FAULT.md). ``fault=None`` (every non-chaos caller) adds no
    work to the loop.

    ``dead``/``n_override`` are the mutable-tier hooks
    (docs/INGEST.md): a [npad] bool tombstone mask folded into
    refine_step's validity, and the live joint row count substituted
    into r_delta (same contract as core.search.search_impl)."""
    res = src.resident
    b, n = queries.shape
    L = res.num_leaves
    v = int(visit_batch)
    depth = max(1, int(prefetch_depth))
    traced = obs.enabled()
    gather_t = obs.Tally("ooc.gather")
    sync_t = obs.Tally("ooc.sync")

    def host(x) -> np.ndarray:
        """Every device->host read of the loop: counted, timed and
        spanned as ``ooc.sync``."""
        with sync_t:
            return np.asarray(x)

    ctx = src.query_ctx(queries)
    if dead is not None:
        ctx = ctx._replace(dead=jnp.asarray(dead))
    with obs.span("ooc.filter", leaves=L, lanes=b):
        lb_sq = _filter_stage(res, queries)  # [B, L], stays on device

    # frontier width F covers this iteration's visits, the next_lb
    # probe AND the prefetch lookahead (depth extra windows); ANY
    # width emits the same visit order (core/refine.py). F must
    # exceed the lookahead by at least one window — at F == lookahead
    # the refill condition (pos > F-1-lookahead) holds every
    # iteration and the amortized refill degenerates to one full
    # frontier_select per step
    la_want = (1 + depth) * v
    F = min(max(default_frontier(L, v), la_want + v), L) \
        if frontier is None \
        else min(max(int(frontier), min(la_want + v, L)), L)
    lookahead = min(la_want, F)
    fr = refine.frontier_init(b, F)

    eps_mult = np.float32((1.0 + epsilon) ** 2)
    rd = float(r_delta(
        res.hist, delta,
        res.n_total if n_override is None else n_override))
    rd_sq = np.float32(rd) * np.float32(rd)
    max_rank = L if nprobe is None else min(nprobe, L)

    kk = src.track_width(k)
    top_d = jnp.full((b, kk), INF)
    top_i = jnp.full((b, kk), -1, jnp.int32)
    rank = np.zeros(b, np.int64)
    active = np.ones(b, bool)
    leaves_visited = np.zeros(b, np.int64)
    rows_scanned = np.zeros(b, np.int64)
    iters = 0
    # refinement telemetry (read-only; see docstring)
    refills = 0
    stop_n = {"delta": 0, "epsilon": 0, "exhausted": 0}
    slack_sum = {"delta": 0.0, "epsilon": 0.0}
    slack_n = {"delta": 0, "epsilon": 0}

    t_loop = obs.now()
    while active.any():
        with obs.span("ooc.iteration", iter=iters):
            with obs.span("ooc.tick"):
                active_j = jnp.asarray(active)
                # mirror frontier_tick's refill predicate (same F/
                # lookahead/pos inputs) to count lane-refill events;
                # pos is host-read BEFORE the tick so the count
                # observes, never participates
                pos_host = host(fr.pos)
                refills += int(
                    (active & (pos_host > F - 1 - lookahead)).sum())
                fr, leaf_j = _frontier_tick(fr, lb_sq, active_j,
                                            v=v, lookahead=lookahead)
                leaf = host(leaf_j)

            rk = rank[:, None] + np.arange(v)[None, :]
            in_range = rk < max_rank
            ok = in_range & active[:, None]
            if fault is not None:
                fault.check("gather")
            with gather_t as g_span:
                # demand-path (sync) reads only: the prefetcher thread
                # lands its bytes concurrently, so a cache.bytes_read
                # delta here would be racy — the root span carries the
                # authoritative total instead
                pre_read = src.cache.bytes_read_sync if traced else 0
                g = src.gather(leaf, ok, leaf_dev=leaf_j)
                if traced:
                    g_span.set(bytes_read_sync=(
                        src.cache.bytes_read_sync - pre_read))

            # overlap: stage the next `depth` visit windows while the
            # device scores this one (nearest window first — it is
            # read first)
            with obs.span("ooc.prefetch"):
                windows = []
                for d in range(1, depth + 1):
                    base = np.minimum(rank + d * v, max_rank)
                    ok_d = ((base[:, None] + np.arange(v)[None, :])
                            < max_rank) & active[:, None]
                    if ok_d.any():
                        windows.append(
                            (host(_frontier_window(fr, d * v, v)),
                             ok_d))
                src.prefetch(windows)

            if fault is not None:
                fault.check("score")
            with obs.span("ooc.score", lanes=int(active.sum())):
                if share_gathers:
                    pool_valid = _coop_mask(leaf_j, jnp.asarray(ok),
                                            g.valid)
                    top_d, top_i = src.score(ctx, g, pool_valid, top_d,
                                             top_i, share=True)
                else:
                    top_d, top_i = src.score(ctx, g, g.valid, top_d,
                                             top_i, share=False)

            with obs.span("ooc.stop"):
                valid_np = host(g.valid)
                leaves_visited += np.where(active, in_range.sum(1), 0)
                rows_scanned += np.where(active, valid_np.sum(1), 0)

                fr, next_lb_j = _frontier_advance(fr, active_j, v=v)
                rank_next = np.minimum(rank + v, max_rank)
                exhausted = rank_next >= max_rank
                next_lb = host(next_lb_j).astype(np.float32)
                bsf = host(top_d[:, k - 1])       # f32
                stop = refine.stop_mask(next_lb, exhausted, bsf,
                                        eps_mult, rd_sq)
                # attribute each newly stopped lane to ONE condition
                # (priority delta > epsilon > exhausted — a lane can
                # satisfy several at once) and measure the slack at
                # stop: how far past the threshold the predicate
                # fired, in squared-distance units
                newly = active & stop
                if newly.any():
                    m_delta = newly & (bsf <= eps_mult * rd_sq)
                    m_eps = newly & ~m_delta & (next_lb * eps_mult > bsf)
                    m_exh = newly & ~m_delta & ~m_eps
                    stop_n["delta"] += int(m_delta.sum())
                    stop_n["epsilon"] += int(m_eps.sum())
                    stop_n["exhausted"] += int(m_exh.sum())
                    if m_delta.any():
                        s = (eps_mult * rd_sq - bsf)[m_delta]
                        slack_sum["delta"] += float(s.sum())
                        slack_n["delta"] += int(m_delta.sum())
                    # epsilon slack only over finite next_lb: an inf
                    # next_lb means the frontier pool ran dry, not a
                    # measurable margin
                    m_eps_f = m_eps & np.isfinite(next_lb)
                    if m_eps_f.any():
                        s = (next_lb * eps_mult - bsf)[m_eps_f]
                        slack_sum["epsilon"] += float(s.sum())
                        slack_n["epsilon"] += int(m_eps_f.sum())
            active = active & ~stop
            rank = rank_next
            iters += 1
    loop_s = obs.now() - t_loop

    with obs.span("ooc.finalize") as f_span:
        top_d, top_i, rerank_bytes = src.finalize(ctx, top_d, top_i, k)
        # rerank-specific attr name: the ooc.query root owns the
        # subtree's single "bytes_read" (total() must not double-
        # count the rerank bytes folded into it)
        f_span.set(bytes_read_rerank=rerank_bytes)
    result = SearchResult(
        dists=jnp.sqrt(top_d),
        ids=top_i,
        leaves_visited=jnp.asarray(leaves_visited, jnp.int32),
        rows_scanned=jnp.asarray(rows_scanned, jnp.int32),
        lb_computed=jnp.int32(L),
    )
    lv_total = int(leaves_visited.sum())
    # repro: allow[stats-schema] internal transport dict: search_ooc splices these refinement fields straight into the typed OocStats constructor — never a user-facing stats surface
    telem = {
        "iterations": iters,
        "frontier_refills": refills,
        "leaves_visited": lv_total,
        "rows_scanned": int(rows_scanned.sum()),
        "pruning_ratio": 1.0 - lv_total / (b * L) if b * L else 0.0,
        "stop_delta": stop_n["delta"],
        "stop_epsilon": stop_n["epsilon"],
        "stop_exhausted": stop_n["exhausted"],
        "delta_slack": slack_sum["delta"] / slack_n["delta"]
        if slack_n["delta"] else 0.0,
        "eps_slack": slack_sum["epsilon"] / slack_n["epsilon"]
        if slack_n["epsilon"] else 0.0,
        "loop_s": loop_s,
        "gather_s": gather_t.seconds,
        "sync_s": sync_t.seconds,
        "host_syncs": sync_t.count,
    }
    return result, telem, rerank_bytes


def make_source(store: LeafStore, cache: DeviceLeafCache, *,
                prefetch: bool = True, rerank: int = 4):
    """Codec-dispatched LeafSource over an opened store + device
    cache: PQSource for codec="pq", CachedStoreSource otherwise."""
    if store.codec == "pq":
        return PQSource(store, cache, prefetch=prefetch, rerank=rerank)
    return CachedStoreSource(store, cache, prefetch=prefetch)


def search_ooc(
    store: LeafStore,
    queries: jax.Array,  # [B, n]
    k: int,
    g=None,
    *,
    visit_batch: int = 1,
    cache: Optional[DeviceLeafCache] = None,
    cache_leaves: Optional[int] = None,
    prefetch: bool = True,
    share_gathers: bool = False,
    rerank: int = 4,
    frontier: Optional[int] = None,
    prefetch_depth: int = 1,
    fault=None,
    dead: Optional[jax.Array] = None,
    n_override: Optional[int] = None,
    **legacy,
) -> OocResult:
    """k-NN over an on-disk index without device-resident raw data.

    The guarantee is ONE object — ``g=Guarantee(...)`` (constructors
    in core.guarantees); the historical loose ``delta=``/``epsilon=``/
    ``nprobe=`` kwargs still work for one release via the
    APIDeprecationWarning shim (core/spec.py — an error under
    scripts/verify.sh).
    Pass ``cache`` to reuse (and warm) a cache across calls, or
    ``cache_leaves`` to size a fresh one; default is 1/8 of the leaves
    (clamped to at least one iteration's working set).
    ``prefetch=False`` disables speculative scheduling for this call —
    including on a prefetcher already attached to a supplied cache —
    so stats measure pure demand-path reads. ``prefetch_depth`` is the
    frontier-aware lookahead in visit windows: the host frontier hands
    the prefetcher the next ``depth x visit_batch`` leaf ids instead
    of one window (deeper lookahead hides more disk latency on
    sequential visit runs; a lane that stops early wastes at most
    ``depth`` windows of reads).
    ``share_gathers=True`` scores every gathered slot against all query
    lanes (cooperative batching — module docstring). For codec="pq"
    stores, ``rerank``*k candidates per lane are kept through the ADC
    loop and exactly re-ranked against raw rows at the end.
    ``frontier`` tunes the lazy visit-order window width (None ->
    core.refine.default_frontier, widened to cover the prefetch
    lookahead); any width emits the same visit order.
    ``fault`` threads a serving-layer fault context into the host
    loop (checked before every gather and score — docs/FAULT.md);
    injected faults and attempt deadlines propagate out of this call
    as exceptions for the engine's failover loop to catch.
    ``dead``/``n_override`` thread the mutable tier's tombstone mask
    and live joint row count into the host loop (docs/INGEST.md).
    """
    from repro.core.spec import coerce_guarantee

    g = coerce_guarantee(g, legacy, caller="search_ooc")
    if legacy:
        raise TypeError(
            f"search_ooc() got unexpected keyword arguments "
            f"{sorted(legacy)}")
    delta, epsilon, nprobe = g.delta, g.epsilon, g.nprobe
    res = store.resident
    b, n = queries.shape
    L = res.num_leaves
    v = int(visit_batch)
    per_iter = b * v  # worst-case distinct leaves one iteration pins
    depth = max(1, int(prefetch_depth))

    own_prefetcher = None
    if cache is None:
        if cache_leaves is None:
            cache_leaves = max(L // 8, 1)
        cache_leaves = min(max(cache_leaves, per_iter), max(L, 1))
        cache = DeviceLeafCache(store, cache_leaves)
    if prefetch and cache.prefetcher is None:
        # staging bound covers every speculative window in flight
        own_prefetcher = LeafPrefetcher(store, depth=depth + 1)
        cache.prefetcher = own_prefetcher
    pf_used = cache.prefetcher

    if store.codec == "pq" and epsilon == 0.0 and nprobe is None:
        # the stopping predicate compares EXACT leaf lower bounds
        # against the ADC (approximate) kth-best, which can
        # underestimate and prune the true NN's leaf before it is
        # visited; the re-rank only rescores pooled candidates and
        # cannot recover it — so epsilon=0 is NOT exact under pq.
        warnings.warn(
            "codec='pq' cannot honor the exact (epsilon=0) "
            "guarantee: ADC-scored stopping may prune the true "
            "neighbor's leaf. Use epsilon>0 (the epsilon/"
            "delta-epsilon checks hold after the exact re-rank), "
            "nprobe, or a lossless codec.", UserWarning,
            stacklevel=2)

    src = make_source(store, cache, prefetch=prefetch, rerank=rerank)
    guarantee = _guarantee_kind(epsilon=epsilon, delta=delta,
                                nprobe=nprobe)
    root = obs.span("ooc.query", codec=store.codec, lanes=b, k=k,
                    guarantee=guarantee, share_gathers=bool(share_gathers))
    with root:
        try:
            result, telem, rerank_bytes = _host_refine(
                src, queries, k, delta=delta, epsilon=epsilon,
                nprobe=nprobe, visit_batch=v,
                share_gathers=share_gathers, frontier=frontier,
                prefetch_depth=depth, fault=fault, dead=dead,
                n_override=n_override)
        finally:
            if own_prefetcher is not None:
                own_prefetcher.close()
                if cache.prefetcher is own_prefetcher:
                    cache.prefetcher = None

        stats = OocStats(codec=store.codec,
                         share_gathers=bool(share_gathers),
                         prefetch_depth=depth,
                         dataset_bytes=store.dataset_nbytes,
                         bytes_read_rerank=rerank_bytes,
                         **telem)
        for key, val in cache.stats().items():
            setattr(stats, key, val)
        stats.bytes_read += rerank_bytes
        if pf_used is not None:
            if cache.prefetcher is None:  # transient pf detached:
                stats.bytes_read += pf_used.bytes_read  # fold bytes in
            stats.prefetch_bytes_read = pf_used.bytes_read
            stats.prefetch_leaves_read = pf_used.leaves_read
        # the SAME schema instance feeds the span tree (attrs) and the
        # registry — the three views cannot drift
        root.set(bytes_read=stats.bytes_read,
                 bytes_h2d=stats.bytes_h2d,
                 iterations=stats.iterations,
                 frontier_refills=stats.frontier_refills,
                 leaves_visited=stats.leaves_visited,
                 rows_scanned=stats.rows_scanned,
                 pruning_ratio=stats.pruning_ratio,
                 stop_delta=stats.stop_delta,
                 stop_epsilon=stats.stop_epsilon,
                 stop_exhausted=stats.stop_exhausted,
                 delta_slack=stats.delta_slack,
                 eps_slack=stats.eps_slack)
        _publish_ooc_metrics(stats, guarantee)
    return OocResult(result=result, stats=stats)


def _guarantee_kind(*, epsilon: float, delta: float,
                    nprobe: Optional[int]) -> str:
    """Label for the guarantee tier a query ran under (the metric /
    span ``guarantee`` label): ng (fixed rank budget) > delta-epsilon
    (probabilistic early stop armed) > epsilon > exact."""
    if nprobe is not None:
        return "ng"
    if delta < 1.0:
        return "delta-epsilon"
    if epsilon > 0.0:
        return "epsilon"
    return "exact"


def _publish_ooc_metrics(stats: OocStats, guarantee: str) -> None:
    """Fold one query's OocStats into the process-wide registry,
    labeled by codec + guarantee tier."""
    lbl = {"codec": stats.codec or "raw", "guarantee": guarantee}
    reg = obs.REGISTRY
    reg.counter("ooc.queries", **lbl).inc()
    for field in ("bytes_read", "bytes_read_sync", "bytes_h2d",
                  "bytes_read_rerank", "prefetch_bytes_read",
                  "leaves_visited", "rows_scanned", "frontier_refills",
                  "stop_delta", "stop_epsilon", "stop_exhausted"):
        val = stats.get(field, 0)
        if val:
            reg.counter(f"ooc.{field}", **lbl).inc(val)
    reg.histogram("ooc.iterations", **lbl).record(stats.iterations)
    reg.histogram("ooc.pruning_ratio", **lbl).record(stats.pruning_ratio)
