"""Batched, TPU-native Algorithm 1 / Algorithm 2 (the paper's §3.2.3).

Semantics are the paper's exactly; the execution strategy is the TPU
adaptation (docs/PERF.md):

  1. lower-bound every leaf in one vectorized pass (box_mindist kernel);
  2. LAZY leaf frontier -> per-query visit order (the priority-queue
     order): instead of a full [B, L] argsort, partially select only the
     first F ranks with lax.top_k and refill each lane's frontier from
     the remaining lb pool when it runs low. The refill threshold is the
     last consumed (lb, leaf-id) pair, so every refill selects exactly
     the lexicographic successors — the emitted order is provably the
     stable argsort order (globally non-decreasing lb, Algorithm 2's
     correctness condition) while per-query sort work scales with ranks
     VISITED, not with L.
  3. `lax.while_loop` over visit ranks: each iteration every active query
     lane gathers its next `visit_batch` leaves, computes true distances
     (fused L2 with squared row norms cached at freeze time), merges
     into its running sorted top-k via O(k) partial-selection merges
     (kernels/ops.py topk_merge*), and evaluates the stopping predicate
         next_lb > bsf/(1+eps)            [Alg.2 line 10/20 pruning]
       | bsf <= (1+eps) * r_delta         [Alg.2 line 16 early stop]
       | visited >= nprobe                [ng-approximate]
       | exhausted                        [scanned everything]
     where bsf is the kth-best true distance (k-NN generalization [42]).

Since PR 4 the loop BODY is not defined here: every parity-critical
piece — frontier tick/advance, candidate layout, duplicate-leaf
masking, the codec-dispatched score+merge step, and the stopping
predicates — lives once in core/refine.py, and this while_loop simply
traces those shared functions over a :class:`refine.ResidentSource`
(the HBM residency). store/ooc.py drives the SAME functions from its
host loop over the cached-store sources, so in-memory/out-of-core
parity holds by construction.

Guarantees: with nprobe=None this is exact for (delta=1, eps=0),
epsilon-approximate for (1, eps), delta-epsilon otherwise — identical to
Algorithm 2 because leaves are visited in non-decreasing lb order and the
predicates match (frontier proof in docs/PERF.md). All comparisons run
in squared-distance space to avoid sqrt in the loop.

`visit_batch > 1` amortizes loop overhead (essential for VA+file where a
"leaf" is a single series); it can only visit *more* than strictly
necessary, never fewer, so guarantees are preserved.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from . import refine
from .guarantees import Guarantee
from .histogram import r_delta
from .index import FrozenIndex

default_frontier = refine.default_frontier


class SearchResult(NamedTuple):
    dists: jax.Array          # [B, k] Euclidean distances, ascending
    ids: jax.Array            # [B, k] original row ids (-1 = missing)
    leaves_visited: jax.Array  # [B] int32
    rows_scanned: jax.Array    # [B] int32 raw series touched
    lb_computed: jax.Array     # scalar int32 (= L, the filter pass size)


def search_impl(
    index: FrozenIndex,
    queries: jax.Array,  # [B, n]
    k: int,
    *,
    delta: float = 1.0,
    epsilon: float = 0.0,
    nprobe: Optional[int] = None,
    visit_batch: int = 1,
    force_pallas: bool = False,
    sync_axes: tuple = (),
    share_gathers: bool = False,
    frontier: Optional[int] = None,
    dead: Optional[jax.Array] = None,
    n_override: Optional[int] = None,
) -> SearchResult:
    """Batched Algorithm 2 body (see module docstring for semantics).

    share_gathers (cooperative query batching, §Perf beyond-paper):
    every iteration's gathered rows are scored against ALL query lanes
    (one MXU matmul) instead of only the lane that requested them.
    Extra candidates can only improve a lane's top-k, so every
    guarantee is preserved, while each lane's best-so-far tightens from
    the whole batch's I/O — the per-query bytes drop measurably
    (docs/PERF.md §4). Raises arithmetic intensity from ~0.5 to
    ~0.5*B flops/byte on the refinement stream.

    sync_axes (inside shard_map only): exchange the best-so-far with
    `pmin` over the given mesh axes every iteration, so pruning uses the
    GLOBAL kth-best. Exactness-preserving: the global kth-best distance
    is <= every shard's local kth-best, so the stop threshold only
    tightens; any locally-unvisited candidate with lb above it cannot
    enter the global top-k (§Perf beyond-paper optimization — the
    collective analogue of the paper's shared bsf). Loop continuation
    becomes a global flag carried in-state so shards iterate in
    lockstep (collectives inside the body, none in cond).

    frontier: lazy leaf-frontier width F (ranks partially selected per
    refill; None -> default_frontier). Any width yields the SAME visit
    order — the stable argsort order — it only tunes how much lookahead
    each refill materializes.

    dead / n_override (mutable tier, docs/INGEST.md): ``dead`` is a
    [n_padded] bool tombstone mask over this index's row positions —
    masked rows score inf in refine_step and never surface.
    ``n_override`` substitutes the LIVE joint row count for
    ``index.n_total`` in the delta-guarantee radius r_delta (inserts
    must RAISE N: r_delta shrinks with N, so a stale smaller N would be
    anti-conservative)."""
    b, n = queries.shape
    L = index.num_leaves
    v = visit_batch

    src = refine.ResidentSource(index, force_pallas=force_pallas)
    ctx = src.query_ctx(queries)
    if dead is not None:
        ctx = ctx._replace(dead=dead)

    # ---- filter: lower bound to every leaf ----
    lb_sq = refine.leaf_lower_bounds(index, queries,
                                     force_pallas=force_pallas)  # [B, L]

    # lazy frontier: refilled window by window inside the loop body
    # (never a full [B, L] argsort)
    F = default_frontier(L, v) if frontier is None \
        else min(max(int(frontier), v + 1), L)

    eps_mult = jnp.float32((1.0 + epsilon) ** 2)
    rd = r_delta(index.hist, delta,
                 index.n_total if n_override is None else n_override)
    rd_sq = (rd * rd).astype(jnp.float32)
    max_rank = L if nprobe is None else min(nprobe, L)

    class State(NamedTuple):
        rank: jax.Array       # [B] next visit rank
        top_d: jax.Array      # [B, k] squared, ascending
        top_i: jax.Array      # [B, k]
        active: jax.Array     # [B] bool
        leaves: jax.Array     # [B]
        rows: jax.Array       # [B]
        go: jax.Array         # scalar bool: any shard still active
        fr: refine.FrontierState

    init = State(
        rank=jnp.zeros((b,), jnp.int32),
        top_d=jnp.full((b, k), refine.INF),
        top_i=jnp.full((b, k), -1, jnp.int32),
        active=jnp.ones((b,), bool),
        leaves=jnp.zeros((b,), jnp.int32),
        rows=jnp.zeros((b,), jnp.int32),
        go=jnp.asarray(True),
        fr=refine.frontier_init(b, F),
    )

    def cond(s: State):
        return s.go

    def body(s: State) -> State:
        fr, leaf = refine.frontier_tick(s.fr, lb_sq, s.active,
                                        v=v, lookahead=v)

        # ranks to visit this iteration: [B, V]
        rk = s.rank[:, None] + jnp.arange(v)[None, :]
        in_range = rk < max_rank
        ok = in_range & s.active[:, None]
        g = src.gather(leaf, ok)
        if share_gathers:
            # all lanes' rows pooled; every query scores every row.
            # Copies of a leaf pooled twice THIS iteration are masked
            # (coop_mask) so pool ids stay distinct — the
            # topk_merge_unique/coop_score_select precondition; dedup
            # across ITERATIONS happens in the merge.
            pool_valid = refine.coop_mask(leaf, ok, g.valid)
            top_d, top_i = src.score(ctx, g, pool_valid,
                                     s.top_d, s.top_i, share=True)
        else:
            top_d, top_i = src.score(ctx, g, g.valid,
                                     s.top_d, s.top_i, share=False)

        visited = jnp.sum(in_range, axis=1).astype(jnp.int32)
        leaves = s.leaves + jnp.where(s.active, visited, 0)
        rows_c = s.rows + jnp.where(
            s.active, jnp.sum(g.valid, axis=1).astype(jnp.int32), 0)

        fr, next_lb = refine.frontier_advance(fr, s.active, v=v)
        rank_next = jnp.minimum(s.rank + v, max_rank)
        exhausted = rank_next >= max_rank
        bsf = top_d[:, k - 1]
        if sync_axes:
            bsf = jax.lax.pmin(bsf, sync_axes)  # global kth-best
        stop = refine.stop_mask(next_lb, exhausted, bsf, eps_mult, rd_sq)
        active = s.active & ~stop
        go = jnp.any(active)
        if sync_axes:
            go = jax.lax.pmax(go.astype(jnp.int32), sync_axes) > 0
        return State(rank_next, top_d, top_i, active, leaves, rows_c,
                     go, fr)

    final = jax.lax.while_loop(cond, body, init)
    return SearchResult(
        dists=jnp.sqrt(final.top_d),
        ids=final.top_i,
        leaves_visited=final.leaves,
        rows_scanned=final.rows,
        lb_computed=jnp.int32(L),
    )


# Jitted core of the public entry point. Callers already inside a
# shard_map region must use `search_impl` directly: nesting this jit
# under shard_map miscompiles the while_loop on jax 0.4.x (the
# refinement loop exits after ~2 iterations with check_rep=False),
# observed on 0.4.37.
_search_jit = jax.jit(
    search_impl,
    static_argnames=("k", "nprobe", "visit_batch", "force_pallas",
                     "sync_axes", "share_gathers", "frontier",
                     "n_override"),
)


def search(index: FrozenIndex, queries: jax.Array, k: int,
           g: Optional[Guarantee] = None, **kw) -> SearchResult:
    """Public jitted entry point (`search_impl` semantics). The
    guarantee is ONE object — ``g=Guarantee(...)`` (constructors in
    core.guarantees: exact/epsilon/delta_epsilon/ng); the historical
    loose ``delta=``/``epsilon=``/``nprobe=`` kwargs still work for one
    release via a shim that emits APIDeprecationWarning (an error under
    scripts/verify.sh, and the ``guarantee-kwargs`` analysis rule fails
    in-repo callers). The call is a ``core.search`` span (repro.obs),
    which never syncs the device: jit's async dispatch is kept, and
    the span's visit counts are read back only while obs records."""
    from repro import obs
    from .spec import coerce_guarantee

    g = coerce_guarantee(g, kw, caller="search")
    kw.update(delta=g.delta, epsilon=g.epsilon, nprobe=g.nprobe)
    with obs.span("core.search", lanes=queries.shape[0], k=k,
                  leaves=index.num_leaves) as sp:
        res = _search_jit(index, queries, k, **kw)
        if obs.enabled():
            sp.set(leaves_visited=int(jnp.sum(res.leaves_visited)),
                   rows_scanned=int(jnp.sum(res.rows_scanned)))
    return res


def search_ooc(store, queries: jax.Array, k: int,
               g: Optional[Guarantee] = None, **kw):
    """Out-of-core Algorithm 2 over a LeafStore (see repro.store):
    identical visit order and stopping predicates to :func:`search` —
    only residency differs, so every guarantee transfers (exception:
    the lossy codec="pq" payload supports the epsilon/delta-epsilon
    checks via its exact re-rank but not exact epsilon=0 search, and
    warns if asked). The guarantee is one ``g=Guarantee(...)`` object
    (loose delta/epsilon/nprobe kwargs are the deprecated shim, as in
    :func:`search`); also accepts visit_batch plus
    cache/cache_leaves/prefetch, share_gathers (cooperative scoring,
    as in :func:`search_impl`), frontier (lazy visit-order window
    width), prefetch_depth (speculative lookahead in visit windows),
    rerank (codec="pq" exact re-rank pool multiplier), and
    dead/n_override (tombstones + live-N joint guarantee,
    docs/INGEST.md); returns OocResult(result=SearchResult,
    stats=OocStats)."""
    from repro.store.ooc import search_ooc as impl

    return impl(store, queries, k, g, **kw)


def search_with_guarantee(
    index: FrozenIndex, queries: jax.Array, k: int, g: Guarantee, **kw
) -> SearchResult:
    return search(index, queries, k, g, **kw)


def brute_force(queries: jax.Array, data: jax.Array, k: int,
                **kw) -> SearchResult:
    """Exact linear-scan yardstick (fused L2 + top-k)."""
    from repro.kernels import ops

    d, i = ops.l2_topk(queries, data, k, **kw)
    b = queries.shape[0]
    n = data.shape[0]
    return SearchResult(
        dists=jnp.sqrt(jnp.maximum(d, 0.0)),
        ids=i.astype(jnp.int32),
        leaves_visited=jnp.full((b,), n, jnp.int32),
        rows_scanned=jnp.full((b,), n, jnp.int32),
        lb_computed=jnp.int32(0),
    )
