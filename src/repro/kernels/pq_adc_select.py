"""Pallas TPU kernel: fused PQ-ADC scoring + top-k select.

The cooperative pq refinement step scores every pooled uint8 code row
against every query lane's ADC table. Done as two ops (the
`ops.pq_adc_batch` one-hot matmul, then the merge) that materializes a
[B, R] = [B, B*V*M] f32 distance matrix in HBM each iteration — the
exact memory-bandwidth cost the PQ codec exists to avoid; the raw
(f32/bf16) cooperative path stopped paying it in PR 3
(kernels/topk.py). This kernel closes the pq corner: the pool
dimension R is tiled, each code tile is expanded to a one-hot matrix
and contracted against the flattened per-lane LUTs on the MXU
(`luts.flat[TB, m*K] @ onehot[TR, m*K].T` — the kernels/pq_adc.py
trick, batched over lanes), the [TB, TR] ADC distance tile lives only
in VMEM, and a running per-lane selection of the kk lexicographically
smallest (d, id) pairs is carried in the output block across R steps.
uint8 codes stream through VMEM once; per-iteration pq memory drops
from O(B^2*V*M) to O(B*k) (memory math in docs/PERF.md §4).

Selection is the shared ``kernels.topk.lex_min_select`` (kk rounds of
lex min-extraction, VPU reductions + where-masks only). Precondition
(as for ops.topk_merge_unique): real ids are distinct within the
pool; only the -1 placeholder repeats, and placeholder slots emit
exactly the (inf, -1) pairs the jnp oracle (ref.ref_pq_adc_select)
emits. VMEM budget at the default tiles (TB=128, TR=256, m=16,
K=256): one-hot tile 256x4096 f32 = 4 MiB + LUT tile 128x4096 f32 =
2 MiB, within the ~16 MB/core budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .topk import lex_min_select


def _pq_select_kernel(luts_ref, codes_ref, ids_ref, outd_ref,
                      outi_ref, *, kk: int, n_k: int):
    rstep = pl.program_id(1)

    @pl.when(rstep == 0)
    def _init():
        outd_ref[...] = jnp.full_like(outd_ref, jnp.inf)
        outi_ref[...] = jnp.full_like(outi_ref, -1)

    luts = luts_ref[...].astype(jnp.float32)  # [TB, m*K]
    codes = codes_ref[...]                    # [TR, m] int32
    idv = ids_ref[...]                        # [1, TR] int32
    tr, m = codes.shape

    # one-hot MXU ADC: d[b, i] = sum_j luts[b, j, codes[i, j]]
    sym = jax.lax.broadcasted_iota(jnp.int32, (tr, m, n_k), 2)
    onehot = (codes[:, :, None] == sym).astype(jnp.float32)
    d = jax.lax.dot_general(
        luts, onehot.reshape(tr, m * n_k), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [TB, TR]
    d = jnp.where(idv < 0, jnp.inf, d)
    idm = jnp.broadcast_to(idv, d.shape)

    # running selection ++ tile, then kk lex-min extractions
    cur_d = jnp.concatenate([outd_ref[...], d], axis=1)
    cur_i = jnp.concatenate([outi_ref[...], idm], axis=1)
    outd_ref[...], outi_ref[...] = lex_min_select(cur_d, cur_i, kk)


@functools.partial(jax.jit,
                   static_argnames=("kk", "tile_b", "tile_r",
                                    "interpret"))
def pq_adc_select_pallas(
    codes: jax.Array,  # [R, m] int32 pooled code rows
    luts: jax.Array,   # [B, m, K] f32 per-lane ADC tables
    ids: jax.Array,    # [1, R] int32 candidate ids, -1 = masked
    kk: int,
    *,
    tile_b: int = 128,
    tile_r: int = 256,
    interpret: bool = False,
) -> tuple:
    b, m, k = luts.shape
    r = codes.shape[0]
    assert b % tile_b == 0 and r % tile_r == 0, (b, r, tile_b, tile_r)
    grid = (b // tile_b, r // tile_r)  # R innermost: sequential carry
    return pl.pallas_call(
        functools.partial(_pq_select_kernel, kk=kk, n_k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_b, m * k), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_r, m), lambda i, j: (j, 0)),
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
    )(luts.astype(jnp.float32).reshape(b, m * k),
      codes.astype(jnp.int32), ids.astype(jnp.int32))
