"""The plain reference: brute-force k-NN over the whole collection.

It imports nothing of the program and takes nothing the program made:
it scores every row of the collection (made again from the seed) against
each query on the device, keeps ``CANDIDATES`` per query, and ranks
those on the host in float64 from direct differences. Only the ranking
in float64 is the reference; the device pass just narrows the field.
Over a collection sharded by rows, each device scores its own rows and
the parts' candidates are merged before the ranking: the kc smallest of
the whole lie among the kc smallest of each part.

The device pass is an exact top-k of its own scores, in blocks of rows:
per group of ``GROUP`` rows the group minimum, the ``kc`` groups with
the smallest minima (every one of the kc smallest scores lies in one of
them), then the kc smallest scores of those groups.

``precision="high"`` computes the cross products as three bfloat16
passes (hi*hi + hi*lo + lo*hi, the TPU's ``Precision.HIGH``) spelled
out, so that the lower-precision control reads the same on any backend.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

CANDIDATES = 32
GROUP = 256
ROW_BLOCK = 1 << 18
QUERY_BLOCK = 128


def _bf16_split(x):
    """x as hi + lo, each rounded to bfloat16 but kept in float32.
    ``reduce_precision`` rounds where a float32 -> bfloat16 -> float32
    round trip may be dropped by the compiler as excess precision."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _cross(q, x, precision: str):
    """[Q, R] q . x^T in float32 at ``highest`` or ``high``. The three
    passes of ``high`` are one batched product over stacked halves
    (hi.hi, hi.lo, lo.hi), summed after: written as three products, the
    chip's compiler may fold them back into fewer."""
    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return jnp.matmul(q, x.T, precision=hp)
    qh, ql = _bf16_split(q)
    xh, xl = _bf16_split(x)
    parts = jnp.einsum("pqn,prn->pqr", jnp.stack([qh, qh, ql]),
                       jnp.stack([xh, xl, xh]), precision=hp)
    return parts.sum(axis=0)


def _block_topk(d2, ids, kc: int):
    """kc smallest of d2 [Q, R] with their ids; R is a power of two of
    at least kc."""
    q, r = d2.shape
    size = GROUP
    while r // size < kc:
        size //= 2
    g = d2.reshape(q, r // size, size)
    _, grp = jax.lax.top_k(-g.min(axis=2), kc)              # [Q, kc]
    cand = jnp.take_along_axis(g, grp[:, :, None], axis=1)  # [Q, kc, size]
    cid = (grp[:, :, None] * size
           + jnp.arange(size)[None, None, :]).reshape(q, -1)
    neg, pos = jax.lax.top_k(-cand.reshape(q, -1), kc)
    return -neg, jnp.take(ids, jnp.take_along_axis(cid, pos, axis=1))


@functools.partial(jax.jit, static_argnames=("kc", "precision"))
def nearest(data, queries, kc: int, precision: str = "highest"):
    """(d2 [Q, kc], ids [Q, kc]): the kc rows of ``data`` [N, n] with the
    smallest squared distance to each query, as computed at
    ``precision``; N is a power of two of at least kc, or a multiple of
    ROW_BLOCK."""
    n_rows = data.shape[0]
    rb = min(ROW_BLOCK, n_rows)
    if n_rows % rb or rb & (rb - 1) or rb < kc:
        raise ValueError(f"{n_rows} rows do not split into row blocks")
    qn = jnp.sum(queries * queries, axis=1)[:, None]
    best_d = jnp.full((queries.shape[0], kc), jnp.inf, jnp.float32)
    best_i = jnp.full((queries.shape[0], kc), -1, jnp.int32)

    def body(b, carry):
        bd, bi = carry
        lo = b * rb
        x = jax.lax.dynamic_slice_in_dim(data, lo, rb)
        xn = jnp.sum(x * x, axis=1)[None, :]
        d2 = jnp.maximum(qn - 2.0 * _cross(queries, x, precision) + xn, 0.0)
        ids = lo + jnp.arange(rb, dtype=jnp.int32)
        d, i = _block_topk(d2, ids, kc)
        neg, pos = jax.lax.top_k(-jnp.concatenate([bd, d], axis=1), kc)
        return -neg, jnp.take_along_axis(jnp.concatenate([bi, i], axis=1),
                                         pos, axis=1)

    return jax.lax.fori_loop(0, n_rows // rb, body, (best_d, best_i))


def sharded(data) -> bool:
    """Whether ``data`` is spread over more than one device."""
    return len(data.sharding.device_set) > 1


def sharded_nearest(data, queries: np.ndarray, kc: int, precision: str):
    """:func:`nearest` over ``data`` sharded by rows: each device scores
    its own rows, its ids offset by its first row, and the kc smallest
    of the parts' candidates are kept (ties by id). Host arrays (d2
    [Q, kc], ids [Q, kc])."""
    out = [(s.index[0].start,
            nearest(s.data, jax.device_put(queries, s.device), kc,
                    precision))
           for s in data.addressable_shards]
    d2 = np.concatenate([np.asarray(d) for _, (d, _) in out], axis=1)
    ids = np.concatenate([np.asarray(i) + lo for lo, (_, i) in out],
                         axis=1)
    order = np.lexsort((ids, d2), axis=1)[:, :kc]
    return (np.take_along_axis(d2, order, 1),
            np.take_along_axis(ids, order, 1))


def candidates(data, queries: np.ndarray, kc: int) -> np.ndarray:
    """ids [Q, kc] of :func:`nearest` at ``highest``, over query blocks
    of QUERY_BLOCK (the last one padded), so that the scores of a block
    fit beside the collection. Over a collection sharded by rows, each
    device scores its own rows (:func:`sharded_nearest`)."""
    out = []
    for lo in range(0, queries.shape[0], QUERY_BLOCK):
        qb = queries[lo:lo + QUERY_BLOCK]
        pad = QUERY_BLOCK - qb.shape[0]
        if pad:
            qb = np.concatenate([qb, np.repeat(qb[-1:], pad, 0)])
        if sharded(data):
            _, ids = sharded_nearest(data, qb, kc, "highest")
        else:
            _, ids = nearest(data, jnp.asarray(qb), kc, "highest")
        out.append(np.asarray(ids)[:QUERY_BLOCK - pad])
    return np.concatenate(out)


def true_sq(data: np.ndarray, query: np.ndarray, ids: np.ndarray):
    """float64 squared distances of ``query`` to rows ``ids``, from the
    differences."""
    diff = data[ids].astype(np.float64) - query.astype(np.float64)
    return np.einsum("...n,...n->...", diff, diff)


class TopK(NamedTuple):
    ids: np.ndarray   # [Q, k] exact k-NN, ties by id
    d2: np.ndarray    # [Q, k] float64 squared distances, ascending


def reference_topk(data_dev, data_host: np.ndarray, queries: np.ndarray,
                   k: int) -> TopK:
    """Exact k-NN of each query: device candidates at ``highest``,
    ranked in float64 on the host."""
    cand = candidates(data_dev, queries, CANDIDATES)
    d2 = np.stack([true_sq(data_host, q, c) for q, c in zip(queries, cand)])
    order = np.lexsort((cand, d2), axis=1)[:, :k]
    return TopK(ids=np.take_along_axis(cand, order, 1),
                d2=np.take_along_axis(d2, order, 1))


class Answer(NamedTuple):
    """What an engine's ``query`` returns, as far as the front reads it."""
    dists: jax.Array
    ids: jax.Array
    leaves_visited: jax.Array
    rows_scanned: jax.Array
    lb_computed: jax.Array
    stats: Optional[object] = None


class ReferenceEngine:
    """The reference put in the engine's place, at ``precision``: every
    query brute-forced over the collection, each device over its own
    rows where the collection is sharded. With ``precision="high"`` it
    is the lower-precision control that ``correct`` has to reject."""

    def __init__(self, data_dev, precision: str):
        self.data = data_dev
        self.precision = precision

    def query(self, queries, k: int, g=None, **_kw) -> Answer:
        if sharded(self.data):
            d2, ids = sharded_nearest(self.data,
                                      np.asarray(queries, np.float32), k,
                                      self.precision)
            d2, ids = jnp.asarray(d2), jnp.asarray(ids)
        else:
            d2, ids = nearest(self.data, jnp.asarray(queries, jnp.float32),
                              k, self.precision)
        b, n = queries.shape[0], self.data.shape[0]
        return Answer(dists=jnp.sqrt(d2), ids=ids,
                      leaves_visited=jnp.zeros(b, jnp.int32),
                      rows_scanned=jnp.full(b, n, jnp.int32),
                      lb_computed=jnp.zeros((), jnp.int32))
