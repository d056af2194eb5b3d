"""Jit'd public wrappers around the Pallas kernels.

Each op pads inputs to tile boundaries, dispatches to the Pallas kernel on
TPU (or when forced via ``force_pallas=True``, which uses interpret mode on
CPU) and to the jnp oracle otherwise, then strips padding. The search core
calls these ops exclusively, so the TPU/CPU split lives in one place.

Every dispatch site is wrapped in ``jax.named_scope`` (the ``_scoped``
decorator): the op name lands on the emitted HLO/profiler metadata, so
device traces captured with jax.profiler attribute kernel time to
``repro.ops.<name>`` regions. named_scope is trace-time-only — zero
runtime cost, on or off.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp


def _scoped(fn):
    """Wrap an op in jax.named_scope("repro.ops.<name>")."""

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with jax.named_scope(f"repro.ops.{fn.__name__}"):
            return fn(*a, **kw)

    return wrapped

from . import ref
from .box_mindist import box_mindist_pallas
from .l2_dist import l2_pallas
from .paa import paa_pallas
from .pq_adc import pq_adc_pallas
from .pq_adc_select import pq_adc_select_pallas
from .topk import coop_score_select_pallas


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pad_rows(x: jax.Array, mult: int, value=0.0) -> jax.Array:
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=value)


@_scoped
def paa(x: jax.Array, n_segments: int, *, force_pallas: bool = False,
        tile: int = 256) -> jax.Array:
    """Segment means [N, n] -> [N, l] f32."""
    if force_pallas or on_tpu():
        n = x.shape[0]
        xp = _pad_rows(x, tile)
        out = paa_pallas(xp, n_segments, tile=tile,
                         interpret=not on_tpu())
        return out[:n]
    return ref.ref_paa(x, n_segments)


@_scoped
def box_mindist(
    q: jax.Array, lo: jax.Array, hi: jax.Array, weights: jax.Array,
    *, force_pallas: bool = False, tile_b: int = 128, tile_l: int = 512,
) -> jax.Array:
    """Squared weighted box distances [B, L]."""
    if force_pallas or on_tpu():
        b, l = q.shape[0], lo.shape[0]
        qp = _pad_rows(q, tile_b)
        lop = _pad_rows(lo, tile_l)
        hip = _pad_rows(hi, tile_l)
        out = box_mindist_pallas(
            qp, lop, hip, weights, tile_b=tile_b, tile_l=tile_l,
            interpret=not on_tpu(),
        )
        return out[:b, :l]
    return ref.ref_box_mindist(q, lo, hi, weights)


@_scoped
def l2(
    q: jax.Array, x: jax.Array, *, force_pallas: bool = False,
    tile_b: int = 128, tile_m: int = 256, tile_k: int = 512,
) -> jax.Array:
    """Squared Euclidean distances [B, M] f32."""
    if force_pallas or on_tpu():
        b, m = q.shape[0], x.shape[0]
        n = q.shape[1]
        tile_k = min(tile_k, n)
        if n % tile_k:
            padk = (-n) % tile_k
            q = jnp.pad(q, ((0, 0), (0, padk)))
            x = jnp.pad(x, ((0, 0), (0, padk)))
        qp = _pad_rows(q, tile_b)
        xp = _pad_rows(x, tile_m)
        out = l2_pallas(qp, xp, tile_b=tile_b, tile_m=tile_m,
                        tile_k=tile_k, interpret=not on_tpu())
        return out[:b, :m]
    return ref.ref_l2(q, x)


@_scoped
def pq_adc(
    codes: jax.Array, lut: jax.Array, *, force_pallas: bool = False,
    tile_m: int = 512,
) -> jax.Array:
    """ADC scan distances [M]."""
    if force_pallas or on_tpu():
        m = codes.shape[0]
        cp = _pad_rows(codes, tile_m)
        out = pq_adc_pallas(cp, lut, tile_m=tile_m,
                            interpret=not on_tpu())
        return out[:m]
    return ref.ref_pq_adc(codes, lut)


@_scoped
def pq_adc_batch(
    codes: jax.Array, luts: jax.Array, *, force_pallas: bool = False,
) -> jax.Array:
    """Batched ADC scan: luts [B, m, K] per-query tables; codes [M, m]
    (one shared row set -> every query scores every row, the
    cooperative-gather regime) or [B, M, m] (per-lane rows). -> [B, M].

    TPU path reuses the pq_adc one-hot MXU trick: codes expand to a
    one-hot [*, m*K] tile contracted against the flattened LUTs — for
    shared codes that is ONE [B, m*K] x [m*K, M] matmul scoring every
    gathered row against all query lanes.
    """
    if force_pallas or on_tpu():
        b, m, k = luts.shape
        lf = luts.astype(jnp.float32)
        onehot = jax.nn.one_hot(codes.astype(jnp.int32), k,
                                dtype=jnp.float32)
        if codes.ndim == 2:
            return jax.lax.dot_general(
                lf.reshape(b, m * k),
                onehot.reshape(-1, m * k),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        return jnp.einsum("bmjk,bjk->bm", onehot, lf,
                          preferred_element_type=jnp.float32)
    return ref.ref_pq_adc_batch(codes, luts)


@_scoped
def l2_topk(
    q: jax.Array, x: jax.Array, k: int, **kw
) -> Tuple[jax.Array, jax.Array]:
    """Fused distance + top-k: returns (dists [B,k] asc, ids [B,k])."""
    d = l2(q, x, **kw)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


@_scoped
def row_sq_norms(rows: jax.Array) -> jax.Array:
    """Per-row squared L2 norms [N, n] -> [N] f32.

    THE norm computation of the serving path: FrozenIndex freeze,
    save_index sidecar, LeafStore open and every fallback all call this
    one function so cached-vs-recomputed norms stay bit-identical.
    """
    rf = rows.astype(jnp.float32)
    return jnp.sum(rf * rf, axis=-1)


@_scoped
def sq_l2(q: jax.Array, rows: jax.Array, row_norms: jax.Array
          ) -> jax.Array:
    """Fused squared-L2 with precomputed row norms (f32 accumulation).

    q [B, n]; rows [R, n] -> [B, R] pooled (one MXU matmul scoring
    every row against every lane — the cooperative regime) or rows
    [B, M, n] -> [B, M] per-lane (row_norms [B, M]). The single
    ``astype(f32)`` + norms-passed-in replaces the three copy-pasted
    variants that previously lived in core/search.py and store/ooc.py.

    Both products run at HIGHEST precision: at the default, the TPU
    takes one bf16 pass for the pooled matmul, which left squared
    distances ~0.3 off on v5e (an exact copy of a row came back at
    distance 0.53).
    """
    hi = jax.lax.Precision.HIGHEST
    qf = q.astype(jnp.float32)
    qn = jnp.sum(qf * qf, axis=-1)[:, None]
    rf = rows.astype(jnp.float32)
    rn = row_norms.astype(jnp.float32)
    if rows.ndim == 2:
        cross = jnp.matmul(qf, rf.T, precision=hi)
        return jnp.maximum(qn - 2.0 * cross + rn[None, :], 0.0)
    cross = jnp.einsum("bn,bmn->bm", qf, rf, precision=hi,
                       preferred_element_type=jnp.float32)
    return jnp.maximum(qn - 2.0 * cross + rn, 0.0)


def _select_k_by_d(dists, ids, kk: int):
    """Per-row kk smallest candidates by distance, ties by column.

    lax.top_k prefers the lower index on ties, which is exactly the
    order a stable full sort gives candidates — so the selection drops
    only elements that could never reach the merged top-k.
    Output is sorted ascending (ties column-ascending).
    """
    neg_d, pos = jax.lax.top_k(-dists, kk)
    return -neg_d, jnp.take_along_axis(ids, pos, axis=1)


def _select_k_by_d_id_shared(dists, ids, kk: int):
    """Per-row kk lexicographically-smallest (d, id) pairs when the
    candidate ids are LANE-INVARIANT (ids [R], dists [B, R]) — every
    cooperative call site, since the pooled rows are shared.

    One cheap 1-D argsort of the R ids permutes the candidate COLUMNS
    into id order; a single f32 top_k then breaks distance ties by
    permuted position = by id, which IS the (d, id)-lex selection,
    int32-exact, already in canonical order. One TopK total: XLA:CPU
    rewrites a lone top_k to its fast custom call, but a top_k whose
    operand depends on another top_k is left as a full O(R log R) sort
    (measured ~70x slower at cooperative width), so threshold-style
    two-pass selection is a trap here.
    """
    order = jnp.argsort(ids.astype(jnp.int32))
    d_p = dists[:, order]
    ids_p = ids.astype(jnp.int32)[order]
    neg, pos = jax.lax.top_k(-d_p, kk)
    return -neg, jnp.take(ids_p, pos)


def _select_k_by_d_id(dists, ids, kk: int):
    """Per-row kk lexicographically-smallest (d, id) pairs, sorted —
    the generic [B, M] per-row-ids form (property tests; real callers
    with shared pools use _select_k_by_d_id_shared).

    Two top_k passes: pass 1 finds the kk-th smallest distance (the
    selection threshold); pass 2 re-ranks only the threshold TIES by
    id, so the selected SET matches the full (d, id) sort; a width-kk
    2-key sort canonicalizes the order. Pass-2 keys are f32 (ids exact
    below 2^24; above, float rounding only weakens WHICH of several
    equal-distance candidates crosses the selection boundary — a
    deterministic, guarantee-preserving tie-break, distances
    identical; the final int32 2-key sort keeps the emitted order
    exact regardless).
    """
    ids = ids.astype(jnp.int32)
    neg_d, _ = jax.lax.top_k(-dists, kk)
    thr = -neg_d[:, -1:]  # [B, 1] kk-th smallest distance
    key = jnp.where(
        dists < thr, jnp.float32(jnp.inf),
        jnp.where(dists == thr, -ids.astype(jnp.float32),
                  jnp.float32(-jnp.inf)))
    # repro: allow[jax-topk-on-topk] deliberate trade-off documented above: this is the generic per-row-ids fallback (property tests); real call sites use the single-TopK _select_k_by_d_id_shared
    _, pos = jax.lax.top_k(key, kk)
    sel_d = jnp.take_along_axis(dists, pos, axis=1)
    sel_i = jnp.take_along_axis(ids, pos, axis=1)
    return jax.lax.sort((sel_d, sel_i), num_keys=2)


@_scoped
def bitonic_merge_sorted(da, ia, db, ib):
    """Merge two per-row sorted (ascending) lists: [B,ka]+[B,kb] ->
    [B,ka+kb], the k+k bitonic-merge stage of :func:`topk_merge`.

    Each element is tagged with its concatenation position; compares
    are (d, tag)-lexicographic, so keys are unique and the
    compare-exchange network reproduces the STABLE merge exactly
    (a-list wins distance ties, as in the full-sort oracle). log2(W)
    stages of [B, W] where-swaps, W = ka+kb padded to a power of two.
    """
    b, ka = da.shape
    kb = db.shape[1]
    total = ka + kb
    w = 1 if total == 1 else 1 << (total - 1).bit_length()
    pad = w - total
    tag_a = jnp.broadcast_to(jnp.arange(ka, dtype=jnp.int32), (b, ka))
    tag_b = jnp.broadcast_to(
        jnp.arange(ka, w, dtype=jnp.int32), (b, kb + pad))
    db_p = jnp.pad(db, ((0, 0), (0, pad)), constant_values=jnp.inf)
    ib_p = jnp.pad(ib, ((0, 0), (0, pad)), constant_values=-1)
    # A asc ++ reverse(B asc) = one bitonic sequence in (d, tag)
    d = jnp.concatenate([da, jnp.flip(db_p, axis=1)], axis=1)
    i = jnp.concatenate([ia, jnp.flip(ib_p, axis=1)], axis=1)
    t = jnp.concatenate([tag_a, jnp.flip(tag_b, axis=1)], axis=1)
    step = w // 2
    while step >= 1:
        sh = (b, w // (2 * step), 2, step)
        dr, ir, tr = d.reshape(sh), i.reshape(sh), t.reshape(sh)
        d0, d1 = dr[:, :, 0], dr[:, :, 1]
        i0, i1 = ir[:, :, 0], ir[:, :, 1]
        t0, t1 = tr[:, :, 0], tr[:, :, 1]
        swap = (d1 < d0) | ((d1 == d0) & (t1 < t0))
        d = jnp.stack([jnp.where(swap, d1, d0),
                       jnp.where(swap, d0, d1)], axis=2).reshape(b, w)
        i = jnp.stack([jnp.where(swap, i1, i0),
                       jnp.where(swap, i0, i1)], axis=2).reshape(b, w)
        t = jnp.stack([jnp.where(swap, t1, t0),
                       jnp.where(swap, t0, t1)], axis=2).reshape(b, w)
        step //= 2
    return d[:, :total], i[:, :total]


@_scoped
def topk_merge(dists, ids, top_d, top_i):
    """Merge a candidate batch into running sorted top-k rows.

    Selection formulation (bit-exact to :func:`ref.ref_topk_merge`,
    ties included): lax.top_k picks the k best candidates — O(M log k)
    instead of sorting the full k+M width — then a k+k bitonic merge
    of the two sorted lists keeps per-iteration merge cost O(k log k)
    independent of candidate width (docs/PERF.md)."""
    k = top_d.shape[1]
    kk = min(k, dists.shape[1])
    sel_d, sel_i = _select_k_by_d(dists, ids, kk)
    md, mi = bitonic_merge_sorted(top_d, top_i, sel_d, sel_i)
    return md[:, :k], mi[:, :k]


@_scoped
def dedup_merge_topk(sel_d, sel_i, top_d, top_i):
    """Fold PRE-SELECTED candidates [B, kk] into the running top-k with
    id dedup — the merge half of :func:`topk_merge_unique`, shared with
    the fused cooperative kernel path. Id-dedup runs over the k+kk
    survivors only (two tiny sorts), never the full candidate width;
    the op sequence matches the full-sort oracle so placeholders and
    (d, id) tie order come out identical."""
    k = top_d.shape[1]
    all_d = jnp.concatenate([top_d, sel_d], axis=1)
    all_i = jnp.concatenate([top_i, sel_i.astype(top_i.dtype)], axis=1)
    si, sd = jax.lax.sort((all_i, all_d), num_keys=2)
    dup = jnp.concatenate(
        [jnp.zeros_like(si[:, :1], bool), si[:, 1:] == si[:, :-1]],
        axis=1)
    sd = jnp.where(dup, jnp.float32(jnp.inf), sd)
    si = jnp.where(dup, -1, si)
    new_d, new_i = jax.lax.sort((sd, si), num_keys=1)
    return new_d[:, :k], new_i[:, :k]


@_scoped
def topk_merge_unique(dists, ids, top_d, top_i):
    """topk_merge that keeps each id at most once (best distance).
    Required by the cooperative (share_gathers) scoring paths, where a
    leaf pooled at two iterations is scored twice for every lane.

    Selection formulation (bit-exact to ref.ref_topk_merge_unique):
    select 2k candidates by (d, id) — k fresh winners can hide behind
    at most k duplicates of running entries — then dedup among the
    <=3k survivors only. ``ids`` may be [M] (lane-invariant pool, the
    cooperative call sites: fast single-TopK path) or [B, M] (per-lane
    ids — the engine's cross-shard fold, where each shard's sorted
    top-k merges into the global answer and shard ids are globally
    disjoint). PRECONDITION (call-site invariant, enforced by the
    per-iteration leaf dedup in the shared refinement core
    core/refine.py, and by disjoint shard ranges in the engine fold):
    each real id appears at most once among the candidate columns;
    only the -1 placeholder repeats. Candidate ids duplicating RUNNING
    entries are fine at any distance."""
    k = top_d.shape[1]
    kk = min(2 * k, dists.shape[1])
    if ids.ndim == 1:
        sel_d, sel_i = _select_k_by_d_id_shared(dists, ids, kk)
    else:
        sel_d, sel_i = _select_k_by_d_id(dists, ids, kk)
    return dedup_merge_topk(sel_d, sel_i, top_d, top_i)


@_scoped
def pq_adc_select(
    codes: jax.Array,  # [R, m] pooled code rows (shared across lanes)
    luts: jax.Array,   # [B, m, K] f32 per-lane ADC tables
    ids: jax.Array,    # [R] int32, -1 = masked slot
    kk: int,
    *,
    force_pallas: bool = False,
    tile_b: int = 128,
    tile_r: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Fused cooperative PQ-ADC score+select: per lane, the kk best
    (d, id) candidates from the pooled code rows, without
    materializing the [B, R] ADC distance matrix in HBM on TPU
    (kernels/pq_adc_select.py streams the uint8 codes through the
    one-hot MXU contraction tile by tile and keeps the running
    selection in VMEM). CPU path is the jnp oracle formulation
    (ref_pq_adc_batch + the shared-pool partial selection) — bit-exact
    to the pre-fusion pq_adc_batch + topk_merge_unique corner. Output
    feeds dedup_merge_topk."""
    # kk > R would diverge across backends (the padded Pallas path
    # emits placeholder columns, the oracle's top_k raises) — callers
    # clamp (min(2k, R)); make the contract explicit at trace time
    assert kk <= codes.shape[0], (kk, codes.shape)
    if force_pallas or on_tpu():
        b = luts.shape[0]
        lp = _pad_rows(luts, tile_b)
        cp = _pad_rows(codes.astype(jnp.int32), tile_r)
        ip = _pad_rows(ids.astype(jnp.int32), tile_r, value=-1)[None, :]
        od, oi = pq_adc_select_pallas(
            cp, lp, ip, kk, tile_b=tile_b, tile_r=tile_r,
            interpret=not on_tpu())
        return od[:b], oi[:b]
    d = ref.ref_pq_adc_batch(codes, luts)
    d = jnp.where(ids[None, :] < 0, jnp.float32(jnp.inf), d)
    return _select_k_by_d_id_shared(d, ids, kk)


@_scoped
def coop_score_select(
    q: jax.Array,          # [B, n] f32 queries
    rows: jax.Array,       # [R, n] pooled rows (index/payload dtype)
    row_norms: jax.Array,  # [R] f32 cached squared norms
    ids: jax.Array,        # [R] int32, -1 = masked slot
    kk: int,
    *,
    force_pallas: bool = False,
    tile_b: int = 128,
    tile_r: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """Fused cooperative score+select: per lane, the kk best (d, id)
    candidates from the pooled rows, without materializing the [B, R]
    distance matrix in HBM on TPU (kernels/topk.py tiles R and keeps
    the running selection in VMEM). CPU path is the jnp oracle
    (sq_l2 + partial selection). Output feeds dedup_merge_topk."""
    # same kk <= R contract as pq_adc_select (backend divergence
    # otherwise); all call sites clamp kk = min(2k, R)
    assert kk <= rows.shape[0], (kk, rows.shape)
    if force_pallas or on_tpu():
        b = q.shape[0]
        qp = _pad_rows(q, tile_b)
        rp = _pad_rows(rows, tile_r)
        rn_p = _pad_rows(row_norms, tile_r)[None, :]
        ip = _pad_rows(ids.astype(jnp.int32), tile_r, value=-1)[None, :]
        od, oi = coop_score_select_pallas(
            qp, rp, rn_p, ip, kk, tile_b=tile_b, tile_r=tile_r,
            interpret=not on_tpu())
        return od[:b], oi[:b]
    d = sq_l2(q.astype(jnp.float32), rows, row_norms)
    d = jnp.where(ids[None, :] < 0, jnp.float32(jnp.inf), d)
    return _select_k_by_d_id_shared(d, ids, kk)
