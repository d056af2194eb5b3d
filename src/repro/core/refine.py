"""THE refinement core: one loop body, parameterized by a LeafSource.

The paper's Algorithm 2 refinement loop used to exist twice — a
device ``lax.while_loop`` body in core/search.py and a hand-mirrored
host loop in store/ooc.py — with four jitted scoring steps mirroring
the (solo | cooperative) x (raw | pq) matrix on the out-of-core side.
This module is the single definition of every parity-critical piece:

  frontier    ``FrontierState`` + :func:`frontier_tick` /
              :func:`frontier_advance` — the lazy visit-order window
              (refill threshold = last consumed (lb, leaf-id) pair, so
              the emitted order IS the stable argsort order; proof in
              docs/PERF.md §2). The in-memory while_loop traces these
              functions inline; the out-of-core host loop calls the
              same functions jitted. Bit-exact visit-order parity holds
              by construction, not by mirroring.
  layout      :func:`candidate_layout` — [B, V] leaf window -> padded
              row positions + validity, identical in both residencies.
  dedup       :func:`dup_leaf_mask` / :func:`coop_mask` — the
              same-iteration duplicate-leaf mask that keeps the
              cooperative merges' distinct-id precondition.
  scoring     :func:`refine_step` — codec-dispatched score + select +
              merge. The four former ``_refine_step*`` variants are
              its (share, pq) corners; the in-memory branches are the
              same corners with the HBM data array as the gather pool.
  stopping    :func:`stop_mask` — Algorithm 2's predicates, written
              with operators only so the SAME function evaluates on
              device f32 tracers and host numpy f32 (IEEE-identical).

LeafSource protocol.  A source supplies residency: ``query_ctx``
builds the per-query scoring context (f32 queries + ids/norms, or PQ
ADC LUTs), ``gather(leaf, ok)`` makes a leaf window's rows reachable
on device (:class:`Gathered`: a gather pool + indices + validity),
``score`` folds them into the running top-k via :func:`refine_step`,
and ``finalize`` post-processes the final pool (identity everywhere
except the PQ exact re-rank). Implementations:

  ResidentSource          (here)       HBM-resident FrozenIndex; pure
                                       device gather, traced inside
                                       search_impl's while_loop.
  CachedStoreSource       (store/ooc)  memmap leaves through a
                                       DeviceLeafCache + prefetcher;
                                       host-driven gather.
  PQSource                (store/ooc)  uint8 codes ADC-scored on
                                       device + exact re-rank.

tests/test_refine.py runs the conformance suite against all three.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.kernels import ops

INF = jnp.float32(jnp.inf)


# ---------------------------------------------------------------- frontier
def default_frontier(num_leaves: int, visit_batch: int) -> int:
    """Default lazy-frontier width: a few refill-free batches of
    lookahead (covering this iteration's visits, the next_lb probe and
    the prefetch window) without approaching the full leaf count."""
    return min(num_leaves, max(64, 4 * visit_batch))


def frontier_select(lb_sq: jax.Array, thr_lb: jax.Array,
                    thr_id: jax.Array, f: int) -> tuple:
    """Partially select each lane's next ``f`` visit ranks: the
    lexicographic (lb, leaf-id) successors of the lane's threshold
    pair (thr = (-1, -1) selects the first window). lax.top_k breaks
    lb ties by lower leaf id — the stable argsort tie order — so
    chaining selections reproduces the full sorted visit order exactly
    (Algorithm 2's non-decreasing-lb condition; docs/PERF.md §2)."""
    L = lb_sq.shape[1]
    iota = jnp.arange(L, dtype=jnp.int32)
    remaining = jnp.where(
        (lb_sq > thr_lb[:, None])
        | ((lb_sq == thr_lb[:, None])
           & (iota[None, :] > thr_id[:, None])),
        lb_sq, INF)
    nv, ni = jax.lax.top_k(-remaining, f)
    return -nv, ni


class FrontierState(NamedTuple):
    """Per-lane lazy visit-order window (rank window) + refill
    threshold. Starts EMPTY (pos = F): the first :func:`frontier_tick`
    fills it from the (-1, -1) threshold, which selects ranks [0, F)."""
    lb: jax.Array      # [B, F] window lower bounds
    ids: jax.Array     # [B, F] window leaf ids
    pos: jax.Array     # [B] next unconsumed window position
    thr_lb: jax.Array  # [B] last consumed lb (refill threshold)
    thr_id: jax.Array  # [B] last consumed leaf id


def frontier_init(b: int, f: int) -> FrontierState:
    return FrontierState(
        lb=jnp.full((b, f), jnp.inf, jnp.float32),
        ids=jnp.zeros((b, f), jnp.int32),
        pos=jnp.full((b,), f, jnp.int32),
        thr_lb=jnp.full((b,), -1.0, jnp.float32),
        thr_id=jnp.full((b,), -1, jnp.int32),
    )


def frontier_window(st: FrontierState, offset: int, v: int) -> jax.Array:
    """[B, V] leaf ids at window positions pos+offset .. pos+offset+V-1
    (clamped to the window end; callers mask out-of-rank slots).
    offset=0 is this iteration's visit window; offset=d*V is the d-th
    speculative prefetch window."""
    f = st.lb.shape[1]
    ppos = jnp.minimum(
        st.pos[:, None] + offset + jnp.arange(v, dtype=jnp.int32)[None, :],
        f - 1)
    return jnp.take_along_axis(st.ids, ppos, axis=1)


def frontier_tick(st: FrontierState, lb_sq: jax.Array, active: jax.Array,
                  *, v: int, lookahead: int) -> tuple:
    """Refill lanes whose window no longer covers the next
    ``lookahead`` positions (amortized: once per floor(F/v) iterations
    per lane; skipped entirely via lax.cond when no lane needs it),
    then emit this iteration's [B, V] leaf window. Refilling selects
    the F lexicographic (lb, leaf-id) successors of the lane's
    threshold — exactly the next F ranks of the stable argsort order —
    so ANY width/lookahead yields the same visit order."""
    f = st.lb.shape[1]
    need = active & (st.pos > f - 1 - min(lookahead, f))

    def refill(args):
        lb, ids, pos = args
        nv, ni = frontier_select(lb_sq, st.thr_lb, st.thr_id, f)
        sel = need[:, None]
        return (jnp.where(sel, nv, lb), jnp.where(sel, ni, ids),
                jnp.where(need, 0, pos))

    lb, ids, pos = jax.lax.cond(
        jnp.any(need), refill, lambda a: a, (st.lb, st.ids, st.pos))
    st = st._replace(lb=lb, ids=ids, pos=pos)
    return st, frontier_window(st, 0, v)


def frontier_advance(st: FrontierState, active: jax.Array,
                     *, v: int) -> tuple:
    """Consume this iteration's v positions: peek the next unvisited
    lb (the stopping predicate's next_lb), move the refill threshold
    to the last consumed (lb, leaf-id) pair — the lexicographic
    successor selection point — and advance the window position.
    Inactive lanes keep their threshold (their windows are dead)."""
    f = st.lb.shape[1]
    peek = jnp.minimum(st.pos + v, f - 1)[:, None]
    next_lb = jnp.take_along_axis(st.lb, peek, axis=1)[:, 0]
    last = jnp.minimum(st.pos + v - 1, f - 1)[:, None]
    thr_lb = jnp.where(
        active, jnp.take_along_axis(st.lb, last, axis=1)[:, 0], st.thr_lb)
    thr_id = jnp.where(
        active, jnp.take_along_axis(st.ids, last, axis=1)[:, 0], st.thr_id)
    return st._replace(pos=st.pos + v, thr_lb=thr_lb,
                       thr_id=thr_id), next_lb


# ------------------------------------------------------------------ layout
def candidate_layout(offsets: jax.Array, leaf: jax.Array, ok: jax.Array,
                     max_leaf: int, clamp: int) -> tuple:
    """[B, V] leaf window + slot-usable mask -> ([B, V*M] padded row
    positions clamped to ``clamp``, [B, V*M] validity). A position is
    valid iff it lies inside its leaf's extent AND its slot is usable
    (in visit range, lane active). Invalid positions read a clamped
    (garbage) row that the scoring step masks to inf — identical
    arithmetic in both residencies."""
    b, v = leaf.shape
    start = offsets[leaf]
    end = offsets[leaf + 1]
    pos = jnp.arange(max_leaf, dtype=jnp.int32)[None, None, :]
    idx = start[:, :, None] + pos
    valid = (idx < end[:, :, None]) & ok[:, :, None]
    idx = jnp.minimum(idx, clamp)
    return idx.reshape(b, v * max_leaf), valid.reshape(b, v * max_leaf)


# ------------------------------------------------------------------- dedup
def dup_leaf_mask(leaf: jax.Array, ok: jax.Array) -> jax.Array:
    """[B, V] leaf ids + slot-usable mask -> [B, V] True where the slot
    repeats a leaf already pooled by an EARLIER usable slot this
    iteration. The cooperative paths mask those copies out before
    scoring, which (a) keeps ops.topk_merge_unique's distinct-id
    precondition and (b) changes nothing semantically — the copies
    carry bit-identical (d, id) pairs.

    dup[i] = exists j < i with leaf_j == leaf_i and ok[j]; computed in
    O(BV log BV): sort slots by (leaf, ok-first rank), find each leaf
    group's leader (its minimal-position usable slot), and a slot is a
    duplicate iff that leader is usable and strictly earlier."""
    bv = leaf.shape[0] * leaf.shape[1]
    fl = jnp.asarray(leaf, jnp.int32).reshape(bv)
    fo = jnp.asarray(ok).reshape(bv)
    posv = jnp.arange(bv, dtype=jnp.int32)
    rank = jnp.where(fo, posv, posv + bv)  # usable slots sort first
    leaf_s, _, pos_s, ok_s = jax.lax.sort(
        (fl, rank, posv, fo.astype(jnp.int32)), num_keys=2)
    t = jnp.arange(bv, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), leaf_s[1:] != leaf_s[:-1]])
    start_idx = jax.lax.cummax(jnp.where(is_start, t, 0))
    leader_ok = ok_s[start_idx] > 0
    leader_pos = pos_s[start_idx]
    dup_s = leader_ok & (leader_pos < pos_s)
    dup = jnp.zeros((bv,), bool).at[pos_s].set(dup_s)
    return dup.reshape(leaf.shape)


def coop_mask(leaf: jax.Array, ok: jax.Array,
              valid: jax.Array) -> jax.Array:
    """Cooperative-pool validity: ``valid`` [B, V*M] with
    same-iteration duplicate leaf copies masked out (the
    topk_merge_unique / coop_score_select distinct-id precondition).
    Per-lane visit accounting keeps using the unmasked ``valid``."""
    b, v = leaf.shape
    m = valid.shape[1] // v
    dup = dup_leaf_mask(leaf, ok)
    return valid & ~jnp.repeat(dup, m, axis=1, total_repeat_length=v * m)


# ----------------------------------------------------------------- scoring
class ScoreCtx(NamedTuple):
    """Per-query-batch scoring context (built once per search by
    ``LeafSource.query_ctx``)."""
    qf: jax.Array                  # [B, n] f32 queries
    ids: jax.Array                 # [npad] int32 global row ids
    norms: Optional[jax.Array]     # [npad] f32 squared row norms (raw)
    luts: Optional[jax.Array]      # [B, m, K] ADC tables (pq only)
    dead: Optional[jax.Array] = None  # [npad] bool row tombstones
    #   (docs/INGEST.md): True rows are superseded by the delta tier
    #   (deleted or re-inserted) and must never surface from this
    #   frozen unit. None = immutable store, zero masking cost.


class Gathered(NamedTuple):
    """One iteration's gatherable candidates. ``pool[gather_idx]``
    yields the ENCODED candidate rows; ``row_idx`` maps the same slots
    to padded row positions (ids / norms / re-rank reads). ``buf`` is
    the pool as its residency holds it, and what the scoring step is
    handed: the [P, cols] HBM data array, or the cache's [S, M, cols]
    slot pool, which :func:`refine_step` reads as [S*M, cols]."""
    buf: jax.Array         # [P, cols] or [S, M, cols] gather pool
    gather_idx: jax.Array  # [B, V*M] int32 into pool
    row_idx: jax.Array     # [B, V*M] int32 padded row positions
    valid: jax.Array       # [B, V*M] bool

    @property
    def pool(self) -> jax.Array:
        """``buf`` as the [P, cols] pool ``gather_idx`` indexes (a copy
        of a 3-D slot pool when called eagerly)."""
        return self.buf.reshape(-1, self.buf.shape[-1])


def refine_step(ctx: ScoreCtx, pool: jax.Array, gather_idx: jax.Array,
                row_idx: jax.Array, valid: jax.Array, top_d: jax.Array,
                top_i: jax.Array, *, share: bool, pq: bool,
                force_pallas: bool = False) -> tuple:
    """One refinement iteration's score + select + merge — THE loop
    body both residencies execute (in-memory traces it inside the
    while_loop; the host loop calls it jitted). (share, pq) dispatch:

      solo raw    gather [B, V*M] rows, fused L2 with cached norms,
                  O(k) topk_merge.
      coop raw    pool the iteration's rows, fused score+select per
                  lane (ops.coop_score_select — on TPU the [B, B*V*M]
                  distance matrix never reaches HBM), dedup merge.
      solo pq     ADC against each lane's LUT (one-hot MXU trick),
                  merge padded row POSITIONS (exact re-rank maps them
                  to ids).
      coop pq     fused ADC score+select per lane
                  (ops.pq_adc_select — on TPU the codes stream
                  through the one-hot MXU contraction tile by tile
                  and the [B, B*V*M] ADC distance matrix never
                  reaches HBM), dedup merge.

    For share=True the caller passes the coop_mask'ed validity (the
    distinct-id precondition); candidates are ids for raw codecs and
    padded row positions for pq — masked slots are -1 in both, which
    is the fused kernels' masking convention.

    Tombstones (ctx.dead, docs/INGEST.md) are folded into validity
    BEFORE candidates are formed: a dead row scores inf / candidate -1
    on every branch of the dispatch, identically in both residencies,
    so a deleted frozen row can never enter any running top-k."""
    k = top_d.shape[1]
    if pool.ndim == 3:
        # the cache's [S, M, cols] slot pool, passed as held: under the
        # jit, merging its two major dims is a bitcast (M a multiple of
        # the sublane tile), where an eager reshape copies the pool
        pool = pool.reshape(-1, pool.shape[-1])
    if ctx.dead is not None:
        valid = valid & ~ctx.dead[row_idx]
    if pq:
        cand = jnp.where(valid, row_idx, -1)
    else:
        cand = jnp.where(valid, ctx.ids[row_idx], -1)
    if share:
        flat = gather_idx.reshape(-1)
        rows = pool[flat]                          # [B*V*M, cols]
        candf = cand.reshape(-1)                   # lane-invariant
        if pq:
            sel_d, sel_i = ops.pq_adc_select(
                rows, ctx.luts, candf, min(2 * k, candf.shape[0]),
                force_pallas=force_pallas)
            return ops.dedup_merge_topk(sel_d, sel_i, top_d, top_i)
        sel_d, sel_i = ops.coop_score_select(
            ctx.qf, rows, ctx.norms[row_idx.reshape(-1)], candf,
            min(2 * k, candf.shape[0]), force_pallas=force_pallas)
        return ops.dedup_merge_topk(sel_d, sel_i, top_d, top_i)
    rows = pool[gather_idx]                        # [B, V*M, cols]
    if pq:
        d = ops.pq_adc_batch(rows, ctx.luts)
    else:
        d = ops.sq_l2(ctx.qf, rows, ctx.norms[row_idx])
    d = jnp.where(valid, d, INF)
    return ops.topk_merge(d, cand, top_d, top_i)


# ---------------------------------------------------------------- stopping
def stop_mask(next_lb, exhausted, bsf, eps_mult, rd_sq):
    """Algorithm 2's stopping predicates (squared-distance space):

        next_lb * (1+eps)^2 > bsf      [Alg.2 line 10/20 pruning]
      | bsf <= (1+eps)^2 * r_delta^2   [Alg.2 line 16 early stop]
      | exhausted                      [rank budget / scanned all]

    Operators only — evaluates identically on device f32 arrays and
    host numpy f32 (both IEEE-754), so the two loop drivers share this
    single definition. next_lb may be +inf (frontier pool exhausted);
    inf * eps_mult stays inf (eps_mult >= 1), never NaN."""
    return (next_lb * eps_mult > bsf) | (bsf <= eps_mult * rd_sq) \
        | exhausted


def leaf_lower_bounds(index, queries: jax.Array, *,
                      force_pallas: bool = False) -> jax.Array:
    """Filter stage: squared lower bound of every leaf for every lane
    ([B, L], the box_mindist kernel over the index's summaries) — the
    one pass whose output the lazy frontier partially selects."""
    q_sum = index.summarize_queries(queries)
    return ops.box_mindist(q_sum, index.box_lo, index.box_hi,
                           index.weights, force_pallas=force_pallas)


# -------------------------------------------------------------- LeafSource
@runtime_checkable
class LeafSource(Protocol):
    """Residency behind the refinement core. ``pq`` selects the
    scoring codec (ADC + re-rank vs fused L2); ``track_width`` is the
    per-lane candidate pool the loop carries (k, or rerank*k for pq);
    ``finalize`` maps the final pool to the reported top-k (identity,
    or the PQ exact re-rank) and returns any extra bytes read."""

    pq: bool

    def query_ctx(self, queries: jax.Array) -> ScoreCtx: ...

    def track_width(self, k: int) -> int: ...

    def gather(self, leaf, ok) -> Gathered: ...

    def score(self, ctx: ScoreCtx, g: Gathered, valid, top_d, top_i,
              *, share: bool) -> tuple: ...

    def finalize(self, ctx: ScoreCtx, top_d, top_i, k: int) -> tuple: ...


class ResidentSource:
    """LeafSource over an HBM-resident FrozenIndex. ``gather`` is pure
    device indexing, so the whole loop stays inside one
    lax.while_loop (search_impl traces these methods inline)."""

    pq = False

    def __init__(self, index, *, force_pallas: bool = False):
        self.index = index
        self.force_pallas = force_pallas
        self.norms = index.row_norms if index.row_norms is not None \
            else ops.row_sq_norms(index.data)

    def query_ctx(self, queries: jax.Array) -> ScoreCtx:
        return ScoreCtx(qf=queries.astype(jnp.float32),
                        ids=self.index.ids, norms=self.norms, luts=None)

    def track_width(self, k: int) -> int:
        return k

    def gather(self, leaf: jax.Array, ok: jax.Array) -> Gathered:
        idx, valid = candidate_layout(
            self.index.offsets, leaf, ok, self.index.max_leaf,
            self.index.data.shape[0] - 1)
        return Gathered(buf=self.index.data, gather_idx=idx,
                        row_idx=idx, valid=valid)

    def score(self, ctx, g, valid, top_d, top_i, *, share):
        return refine_step(ctx, g.buf, g.gather_idx, g.row_idx, valid,
                           top_d, top_i, share=share, pq=False,
                           force_pallas=self.force_pallas)

    def finalize(self, ctx, top_d, top_i, k):
        return top_d, top_i, 0
