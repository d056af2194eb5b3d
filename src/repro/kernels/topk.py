"""Pallas TPU kernel: fused cooperative score + top-k select.

The cooperative (share_gathers) refinement step scores every pooled
candidate row against every query lane. Done naively that materializes
a [B, R] = [B, B*V*M] distance matrix in HBM each iteration, only for
the merge to keep k << R entries per lane. This kernel fuses the two:
the pool dimension R is tiled, each [TB, TR] distance tile lives only
in VMEM, and a running per-lane selection of the kk lexicographically
smallest (d, id) pairs is carried in the output block across R steps —
TPU never writes the distance matrix out (DESIGN ref: docs/PERF.md).

Selection inside the kernel is kk rounds of lexicographic min-extraction
over the [TB, kk + TR] concat of the running selection and the tile
(VPU reductions + where-masks only — no sort network, no gathers), which
keeps every op Pallas-TPU friendly. Extracted slots are remasked to the
(inf, -1) placeholder, so exhausted tiles emit exactly the placeholder
the jnp oracle (ref.ref_coop_score_select) emits. Precondition (as for
ops.topk_merge_unique): real ids are distinct within the pool.

Row norms and ids arrive as [1, R] lane-major rows: a [TR, 1] column
block would need a sublane-to-lane relayout per tile, which on v5e
took the whole 16 MiB of scoped VMEM at TB=128, TR=256.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_I32_MAX = 2**31 - 1


def lex_min_select(cur_d: jax.Array, cur_i: jax.Array, kk: int) -> tuple:
    """kk rounds of lexicographic (d, id) min-extraction over a
    [TB, W] candidate block — the in-VMEM selection stage shared by
    every fused score+select kernel (this module's raw-L2 kernel and
    kernels/pq_adc_select.py's ADC kernel). VPU reductions +
    where-masks only: no sort network, no gathers. Extracted slots are
    remasked to the (inf, -1) placeholder, so exhausted blocks emit
    exactly the placeholder the jnp oracles emit.

    The rounds run in a ``fori_loop`` that writes round j's pair into
    column j of the carried [TB, kk] outputs. Unrolled in Python, the
    kk = 200 kernels took ~40 s each to compile for v5e; the loop
    compiles in under a second and selects the same pairs."""
    tb = cur_d.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, kk), 1)

    def extract(j, carry):
        cur_d, cur_i, out_d, out_i = carry
        bd = jnp.min(cur_d, axis=1, keepdims=True)        # [TB, 1]
        tie = jnp.where(cur_d == bd, cur_i, jnp.int32(_I32_MAX))
        bi = jnp.min(tie, axis=1, keepdims=True)          # [TB, 1]
        out_d = jnp.where(col == j, bd, out_d)
        out_i = jnp.where(col == j, bi, out_i)
        hit = (cur_d == bd) & (cur_i == bi)
        return (jnp.where(hit, jnp.inf, cur_d),
                jnp.where(hit, -1, cur_i), out_d, out_i)

    init = (cur_d, cur_i, jnp.full((tb, kk), jnp.inf, jnp.float32),
            jnp.full((tb, kk), -1, jnp.int32))
    _, _, out_d, out_i = jax.lax.fori_loop(0, kk, extract, init)
    return out_d, out_i


def _coop_topk_kernel(q_ref, rows_ref, rn_ref, ids_ref, outd_ref,
                      outi_ref, *, kk: int):
    rstep = pl.program_id(1)

    @pl.when(rstep == 0)
    def _init():
        outd_ref[...] = jnp.full_like(outd_ref, jnp.inf)
        outi_ref[...] = jnp.full_like(outi_ref, -1)

    q = q_ref[...].astype(jnp.float32)        # [TB, n]
    rows = rows_ref[...].astype(jnp.float32)  # [TR, n]
    rn = rn_ref[...].astype(jnp.float32)      # [1, TR]
    idv = ids_ref[...]                        # [1, TR] int32

    qn = jnp.sum(q * q, axis=1, keepdims=True)            # [TB, 1]
    # HIGHEST: Mosaic's default f32 dot rounds to bf16, which put the
    # squared distances ~0.3 off on v5e (|x|^2 = 256 rows)
    cross = jax.lax.dot_general(
        q, rows, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # [TB, TR]
    d = jnp.maximum(qn - 2.0 * cross + rn, 0.0)
    d = jnp.where(idv < 0, jnp.inf, d)
    idm = jnp.broadcast_to(idv, d.shape)

    # running selection ++ tile, then kk lex-min extractions
    cur_d = jnp.concatenate([outd_ref[...], d], axis=1)
    cur_i = jnp.concatenate([outi_ref[...], idm], axis=1)
    outd_ref[...], outi_ref[...] = lex_min_select(cur_d, cur_i, kk)


@functools.partial(jax.jit,
                   static_argnames=("kk", "tile_b", "tile_r",
                                    "interpret"))
def coop_score_select_pallas(
    q: jax.Array,          # [B, n] f32
    rows: jax.Array,       # [R, n] payload dtype
    row_norms: jax.Array,  # [1, R] f32
    ids: jax.Array,        # [1, R] int32, -1 = masked
    kk: int,
    *,
    tile_b: int = 128,
    tile_r: int = 256,
    interpret: bool = False,
) -> tuple:
    b, n = q.shape
    r = rows.shape[0]
    assert b % tile_b == 0 and r % tile_r == 0, (b, r, tile_b, tile_r)
    grid = (b // tile_b, r // tile_r)  # R innermost: sequential carry
    return pl.pallas_call(
        functools.partial(_coop_topk_kernel, kk=kk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, n), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_r, n), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tile_r), lambda i, j: (0, j)),
            pl.BlockSpec((1, tile_r), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tile_b, kk), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_b, kk), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kk), jnp.float32),
            jax.ShapeDtypeStruct((b, kk), jnp.int32),
        ],
        interpret=interpret,
    )(q, rows, row_norms, ids.astype(jnp.int32))
