"""Kernel microbenchmarks: µs/call for each hot-spot op.

On this CPU container the timed path is the jnp oracle (the production
XLA:CPU path); Pallas timings are meaningful only on TPU — interpret
mode is correctness-only. Both facts are recorded in the CSV note."""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

from .common import csv_line, emit, timeit

K = 10          # top-k width of every merge case (paper default)
LEAF_M = 64     # rows per visited leaf in the merge widths


def run(scale: str = "default", out_dir=None) -> List[dict]:
    rng = np.random.default_rng(0)
    sizes = {"small": (64, 2048), "default": (128, 8192),
             "large": (256, 32768)}[scale]
    b, m = sizes
    n = 256
    q = jnp.asarray(rng.normal(size=(b, n)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(m, n)), jnp.float32)
    lo = jnp.asarray(rng.normal(size=(m, 32)) - 1, jnp.float32)
    hi = lo + 0.5
    qs = jnp.asarray(rng.normal(size=(b, 32)), jnp.float32)
    w = jnp.ones((32,), jnp.float32)
    codes = jnp.asarray(rng.integers(0, 256, (m, 16)), jnp.int32)
    lut = jnp.asarray(rng.uniform(size=(16, 256)), jnp.float32)
    luts = jnp.asarray(rng.uniform(size=(b, 16, 256)), jnp.float32)
    codes_coop = jnp.asarray(
        rng.integers(0, 256, (b * LEAF_M, 16)), jnp.int32)

    # merge operands at the refinement loop's real widths: the solo
    # candidate block is k + V*M per lane; the cooperative block is
    # k + B*V*M (every lane scores the whole pool). Pool ids are
    # lane-invariant, exactly like the share_gathers call sites.
    solo_w = LEAF_M
    coop_w = b * LEAF_M
    d_solo = jnp.asarray(rng.uniform(size=(b, solo_w)), jnp.float32)
    i_solo = jnp.asarray(
        rng.integers(0, 4 * m, (b, solo_w)), jnp.int32)
    d_coop = jnp.asarray(rng.uniform(size=(b, coop_w)), jnp.float32)
    i_coop1 = jnp.asarray(rng.permutation(4 * coop_w)[:coop_w],
                          jnp.int32)
    i_coop2 = jnp.broadcast_to(i_coop1[None], (b, coop_w))
    top_d = jnp.sort(jnp.asarray(rng.uniform(size=(b, K)), jnp.float32),
                     axis=1)
    top_i = jnp.asarray(10 * coop_w + np.arange(b * K).reshape(b, K),
                        jnp.int32)

    # every case is a (fn, operands) pair jitted with the operands as
    # RUNTIME arguments — closing over device arrays would inline them
    # as constants and XLA constant-folds whole sorts away (the ref
    # merge baselines then time as ~0 after a 40s+ compile)
    cases = {
        "paa": (lambda a: ops.paa(a, 16), (x,)),
        "box_mindist": (ops.box_mindist, (qs, lo, hi, w)),
        "l2": (ops.l2, (q, x)),
        "l2_topk": (lambda a, c: ops.l2_topk(a, c, K), (q, x)),
        "pq_adc": (ops.pq_adc, (codes, lut)),
        "pq_adc_batch": (ops.pq_adc_batch, (codes, luts)),
        # fused cooperative pq selection vs its full-materialization
        # oracle, at the real cooperative pool width k + B*V*M
        "pq_adc_select": (
            lambda c, l, i: ops.pq_adc_select(c, l, i, 2 * K),
            (codes_coop, luts, i_coop1)),
        "pq_adc_select_materialize_ref": (
            lambda c, l, i: ref.ref_pq_adc_select(c, l, i, 2 * K),
            (codes_coop, luts, i_coop1)),
        "topk_merge": (ops.topk_merge, (d_solo, i_solo, top_d, top_i)),
        "topk_merge_sort_ref": (ref.ref_topk_merge,
                                (d_solo, i_solo, top_d, top_i)),
        "topk_merge_unique_coop": (ops.topk_merge_unique,
                                   (d_coop, i_coop1, top_d, top_i)),
        "topk_merge_unique_sort_ref_coop":
            (ref.ref_topk_merge_unique, (d_coop, i_coop2, top_d, top_i)),
    }
    widths = {
        "pq_adc_batch": f"b={b};m_rows={m};pq_m=16",
        "pq_adc_select": f"b={b};pool={coop_w};pq_m=16;kk={2 * K}",
        "pq_adc_select_materialize_ref":
            f"b={b};pool={coop_w};pq_m=16;kk={2 * K}",
        "topk_merge": f"b={b};width=k+{solo_w}",
        "topk_merge_sort_ref": f"b={b};width=k+{solo_w}",
        "topk_merge_unique_coop": f"b={b};width=k+{coop_w}",
        "topk_merge_unique_sort_ref_coop": f"b={b};width=k+{coop_w}",
    }
    rows: List[dict] = []
    timed = {}
    for name, (fn, operands) in cases.items():
        jitted = jax.jit(fn)
        # default-arg binding: the thunk must close over THIS
        # iteration's jitted/operands, not the loop variables (B023)
        sec = timeit(lambda jf=jitted, args=operands: jf(*args),
                     repeats=5)
        timed[name] = sec
        rows.append({"bench": "kernels", "kernel": name,
                     "us_per_call": sec * 1e6,
                     "note": "XLA:CPU oracle path; Pallas validated in "
                             "interpret mode (tests/test_kernels.py)"})
        print(csv_line(f"kernel/{name}", sec * 1e6,
                       widths.get(name, f"b={b};m={m};n={n}")))
    # selection-vs-full-sort speedups (ISSUE 3 + ISSUE 5 acceptance)
    for new, old in (("topk_merge", "topk_merge_sort_ref"),
                     ("topk_merge_unique_coop",
                      "topk_merge_unique_sort_ref_coop"),
                     ("pq_adc_select", "pq_adc_select_materialize_ref")):
        speedup = timed[old] / timed[new]
        rows.append({"bench": "kernels", "kernel": f"{new}_speedup",
                     "speedup_vs_full_sort": speedup,
                     "us_new": timed[new] * 1e6,
                     "us_old": timed[old] * 1e6})
        print(csv_line(f"kernel/{new}_speedup", timed[new] * 1e6,
                       f"x{speedup:.1f}_vs_full_sort"))
    rows.append(_pq_fused_memory_row(codes_coop, luts, i_coop1, b,
                                     coop_w))
    emit(rows, out_dir, "bench_kernels")
    return rows


def _pq_fused_memory_row(codes_coop, luts, ids, b: int,
                         coop_w: int) -> dict:
    """The ISSUE 5 peak-memory assertion, run as part of the bench so
    the snapshot gate catches a regression to materializing: lower the
    fused kernel (interpret on CPU — the same tiling the TPU path
    uses) and the full-materialization oracle over identical
    cooperative-width operands, assert the [B, R] ADC distance matrix
    appears ONLY in the oracle's optimized HLO, and report both
    compiled temp footprints."""
    kk = 2 * K
    fused = jax.jit(lambda c, l, i: ops.pq_adc_select(
        c, l, i, kk, force_pallas=True))
    mat = jax.jit(lambda c, l, i: ref.ref_pq_adc_select(c, l, i, kk))
    fc = fused.lower(codes_coop, luts, ids).compile()
    mc = mat.lower(codes_coop, luts, ids).compile()
    # HLO shape-signature check at a FIXED pool width chosen so the
    # [B, R] matrix shape cannot collide with any legitimate operand
    # shape (at some bench scales R == m*K, the flattened-LUT width)
    rng = np.random.default_rng(1)
    b_chk, r_chk = 16, 768
    codes_chk = jnp.asarray(rng.integers(0, 256, (r_chk, 16)),
                            jnp.int32)
    luts_chk = jnp.asarray(rng.uniform(size=(b_chk, 16, 256)),
                           jnp.float32)
    ids_chk = jnp.asarray(np.arange(r_chk), jnp.int32)
    ftxt = fused.lower(codes_chk, luts_chk, ids_chk).compile().as_text()
    mtxt = mat.lower(codes_chk, luts_chk, ids_chk).compile().as_text()
    sigs = {f"f32[{b_chk},{r_chk}]", f"f32[128,{r_chk}]"}  # 128: lane pad
    assert not any(s in ftxt for s in sigs), (
        "fused pq_adc_select materializes the [B, R] ADC matrix")
    assert f"f32[{b_chk},{r_chk}]" in mtxt, (
        "materializing baseline no longer materializes — assertion "
        "lost its teeth; update the bench")
    row = {"bench": "kernels", "kernel": "pq_adc_select_memory",
           "materializes_full_matrix": False,
           "full_matrix_bytes_avoided": 4 * b * coop_w,
           "temp_bytes_fused_interpret":
               int(fc.memory_analysis().temp_size_in_bytes),
           "temp_bytes_materializing":
               int(mc.memory_analysis().temp_size_in_bytes)}
    print(csv_line("kernel/pq_adc_select_memory",
                   row["full_matrix_bytes_avoided"],
                   "full_matrix_bytes_avoided"))
    return row
