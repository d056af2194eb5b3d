"""The paper's synthetic collection and its queries, made from the seed.

Copied from the program's generators (``repro.data.randomwalk.
generate_device`` and ``repro.data.queries.noisy_queries``) so that a
change to the program cannot move the yardstick: z-normalized random
walks (cumulative sums of N(0, 1) steps), and queries that are series of
the collection with additive Gaussian noise of graded size (Zoumpatianos
et al., as in the paper's section 4).
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def seed_words(seed: int) -> np.ndarray:
    """Any non-negative seed up to 64 bits as two uint32 words, passed
    to the generator as data so that every seed runs one compiled
    program."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    return np.array([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


ROW_BLOCK = 1 << 18


def _key(root: int, words: jax.Array):
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(root),
                                                 words[0]), words[1])


def _walk_block(key, b, rb: int, length: int):
    """Block ``b`` of the walks: ``rb`` z-normalized walks from its own
    key."""
    steps = jax.random.normal(jax.random.fold_in(key, b), (rb, length),
                              jnp.float32)
    walk = jnp.cumsum(steps, axis=1)
    mu = walk.mean(axis=1, keepdims=True)
    sd = walk.std(axis=1, keepdims=True) + 1e-9
    return (walk - mu) / sd


def _order(words: jax.Array, rows: int):
    """The run's order of the walks: ``perm[i]`` is the walk row i
    holds, ``where`` its inverse."""
    perm = jax.random.permutation(_key(1, words), rows)
    where = jnp.zeros(rows, jnp.int32).at[perm].set(
        jnp.arange(rows, dtype=jnp.int32))
    return perm, where


@functools.partial(jax.jit, static_argnames=("rows", "length"))
def random_walks(words: jax.Array, rows: int, length: int):
    """[rows, length] f32 z-normalized random walks on the device, in
    one call, and their global standard deviation (the noise scale).
    Made in blocks of ROW_BLOCK rows, each from its own key, so that
    the call holds little beyond its output."""
    key = _key(0, words)
    rb = min(ROW_BLOCK, rows)
    if rows % rb:
        raise ValueError(f"{rows} rows are not a multiple of {rb}")

    def block(b, carry):
        out, sq = carry
        walk = _walk_block(key, b, rb, length)
        return (jax.lax.dynamic_update_slice_in_dim(out, walk, b * rb, 0),
                sq + jnp.sum(walk * walk))

    out, sq = jax.lax.fori_loop(
        0, rows // rb, block,
        (jnp.zeros((rows, length), jnp.float32), jnp.zeros((), jnp.float32)))
    # every row has mean 0, so the collection's variance is its mean square
    return out, jnp.sqrt(sq / (rows * length))


@functools.partial(jax.jit, static_argnames=("rows", "length"))
def collection(base_words: jax.Array, words: jax.Array, rows: int,
               length: int):
    """The configuration's collection for one run: the random walks of
    ``base_words`` (the configuration's own seed) in an order drawn from
    ``words`` (the run's seed), their standard deviation, and where each
    walk went (``where[i]`` is the row that holds walk i).

    Every run serves the same set of series, so the index built over
    them has the same leaves and every compiled shape is the same from
    seed to seed (the dstree's median splits depend on the set, not its
    order); what the run's seed changes is the rows' ids and their order
    within leaves and on disk. Holds twice the collection on the device
    while it runs."""
    walks, scale = random_walks(base_words, rows, length)
    perm, where = _order(words, rows)
    return jnp.take(walks, perm, axis=0), scale, where


def sharded_collection(base_words: jax.Array, words: jax.Array, rows: int,
                       length: int, devices: Sequence):
    """:func:`collection`'s rows, scale and ``where`` with the rows
    sharded in order over ``devices``: device d holds rows [d * rows /
    chips, (d + 1) * rows / chips). Each device makes every block of
    walks in turn and keeps the walks its own rows hold, so it holds its
    share of the collection and one block beside it; the scale sums the
    blocks' squares in :func:`random_walks`' order."""
    chips = len(devices)
    if rows % (ROW_BLOCK * chips):
        raise ValueError(
            f"{rows} rows cannot be sharded over {chips} chips: the rows "
            f"must be a multiple of ROW_BLOCK x chips = "
            f"{ROW_BLOCK * chips}")
    mesh = Mesh(np.asarray(devices), ("rows",))
    return _sharded_collection(base_words, words, rows, length, mesh)


@functools.partial(jax.jit, static_argnames=("rows", "length", "mesh"))
def _sharded_collection(base_words, words, rows: int, length: int, mesh):
    local, rb = rows // mesh.size, ROW_BLOCK

    def part(base_words, words):
        key = _key(0, base_words)
        _, where = _order(words, rows)
        first = jax.lax.axis_index("rows") * local

        def block(b, carry):
            out, sq = carry
            walk = _walk_block(key, b, rb, length)
            row = jax.lax.dynamic_slice_in_dim(where, b * rb, rb) - first
            # another device's rows go to distinct rows past the end,
            # which the scatter drops
            row = jnp.where((row >= 0) & (row < local), row,
                            local + jnp.arange(rb, dtype=jnp.int32))
            return (out.at[row].set(walk, mode="drop", unique_indices=True),
                    sq + jnp.sum(walk * walk))

        out, sq = jax.lax.fori_loop(
            0, rows // rb, block,
            (jnp.zeros((local, length), jnp.float32),
             jnp.zeros((), jnp.float32)))
        # a float: rows x length may pass 2**31
        return out, jnp.sqrt(sq / float(rows * length)), where

    return jax.shard_map(part, mesh=mesh, in_specs=(P(), P()),
                         out_specs=(P("rows"), P(), P()),
                         check_vma=False)(base_words, words)


def query_pool(data: np.ndarray, where: np.ndarray, scale: float, size: int,
               noise_levels: Sequence[float], seed: int) -> np.ndarray:
    """[size, n] f32: distinct walks of the collection drawn from ``seed``
    (the configuration's), with noise of ``noise_levels[i % len]`` x
    ``scale`` added to query i. ``where`` is :func:`collection`'s, so the
    pool is the same whatever order the run's seed gave the rows."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    idx = np.sort(rng.choice(data.shape[0], size, replace=False))
    q = data[where[idx]].astype(np.float32)
    levels = np.asarray(noise_levels, np.float32)[
        np.arange(size) % len(noise_levels)]
    q += (rng.standard_normal(q.shape, np.float32)
          * (levels * np.float32(scale))[:, None])
    return q
