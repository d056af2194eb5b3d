"""Pallas TPU kernel: PAA summarization (segment means).

The build-time hot loop of iSAX/DSTree indexing: every series in the
collection is reduced to l segment means. One grid step processes a tile
of TN series resident in VMEM and contracts it on the MXU against a
constant [n, l] averaging matrix (1/w inside segment j's columns, 0
elsewhere). Mosaic cannot reshape the lane dimension into (l, w), so
the segment reduction is a matmul instead of a reshape + mean.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _paa_kernel(x_ref, out_ref, *, n_segments: int):
    x = x_ref[...].astype(jnp.float32)  # [TN, n]
    n = x.shape[1]
    w = n // n_segments
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n_segments), 0)
    seg = jax.lax.broadcasted_iota(jnp.int32, (n, n_segments), 1)
    avg = jnp.where(col // w == seg, jnp.float32(1.0 / w),
                    jnp.float32(0.0))                 # [n, l]
    out_ref[...] = jax.lax.dot_general(
        x, avg, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_segments", "tile",
                                             "interpret"))
def paa_pallas(
    x: jax.Array, n_segments: int, *, tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x [N, n] -> [N, l] f32 segment means. N must divide by `tile`
    (ops.py pads)."""
    n_rows, n = x.shape
    assert n % n_segments == 0
    assert n_rows % tile == 0, (n_rows, tile)
    grid = (n_rows // tile,)
    return pl.pallas_call(
        functools.partial(_paa_kernel, n_segments=n_segments),
        grid=grid,
        in_specs=[pl.BlockSpec((tile, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, n_segments), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, n_segments), jnp.float32),
        interpret=interpret,
    )(x)
