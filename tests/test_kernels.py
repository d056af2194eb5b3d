"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes (incl. non-tile-multiples) and dtypes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.tier1

RNG = np.random.default_rng(42)


def rand(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


@pytest.mark.parametrize("n_rows", [1, 7, 256, 300])
@pytest.mark.parametrize("n,l", [(64, 16), (256, 16), (96, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paa_matches_ref(n_rows, n, l, dtype):
    x = rand((n_rows, n), dtype)
    got = ops.paa(x, l, force_pallas=True, tile=64)
    want = ref.ref_paa(x, l)
    np.testing.assert_allclose(got, want, atol=2e-2 if
                               dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("n", [128, 256])
def test_paa_averaging_matmul_matches_ref(n):
    """The PAA kernel contracts each tile against an [n, l] averaging
    matrix (Mosaic cannot reshape the lane dimension into (l, w));
    at the paper's lengths it must still give ref_paa's means."""
    x = rand((300, n))
    got = ops.paa(x, 16, force_pallas=True, tile=64)
    np.testing.assert_allclose(got, ref.ref_paa(x, 16), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("b,L,d", [(1, 3, 16), (5, 100, 32), (128, 512, 16),
                                   (9, 700, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_box_mindist_matches_ref(b, L, d, dtype):
    q = rand((b, d), dtype)
    lo = rand((L, d), dtype) - 1.0
    hi = lo + jnp.abs(rand((L, d), dtype))
    w = jnp.abs(rand((d,), jnp.float32)) + 0.5
    got = ops.box_mindist(q, lo, hi, w, force_pallas=True,
                          tile_b=8, tile_l=64)
    want = ref.ref_box_mindist(q, lo, hi, w)
    tol = 1e-1 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("b,m,n", [(1, 1, 32), (4, 100, 256),
                                   (130, 257, 100), (8, 64, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_l2_matches_ref(b, m, n, dtype):
    q = rand((b, n), dtype)
    x = rand((m, n), dtype)
    got = ops.l2(q, x, force_pallas=True, tile_b=8, tile_m=64, tile_k=128)
    want = ref.ref_l2(q, x)
    tol = 5e-1 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


def test_l2_padding_never_contaminates():
    """Padded rows/cols must not alter real outputs."""
    q = rand((3, 50))
    x = rand((17, 50))
    got = ops.l2(q, x, force_pallas=True, tile_b=8, tile_m=16, tile_k=64)
    want = ref.ref_l2(q, x)
    np.testing.assert_allclose(got, want, atol=1e-3)


@pytest.mark.parametrize("m_rows,m,k", [(10, 8, 16), (512, 16, 256),
                                        (1000, 4, 64)])
def test_pq_adc_matches_ref(m_rows, m, k):
    codes = jnp.asarray(RNG.integers(0, k, size=(m_rows, m)), jnp.int32)
    lut = jnp.asarray(RNG.uniform(size=(m, k)), jnp.float32)
    got = ops.pq_adc(codes, lut, force_pallas=True, tile_m=128)
    want = ref.ref_pq_adc(codes, lut)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_l2_topk_agrees_with_sort():
    q = rand((4, 64))
    x = rand((200, 64))
    d, i = ops.l2_topk(q, x, 10)
    full = ref.ref_l2(q, x)
    want = jnp.sort(full, axis=1)[:, :10]
    np.testing.assert_allclose(d, want, atol=1e-4)


def test_topk_merge_equals_global_sort():
    d1 = rand((3, 20))
    i1 = jnp.arange(60).reshape(3, 20)
    top_d = jnp.full((3, 5), jnp.inf)
    top_i = jnp.full((3, 5), -1, jnp.int32)
    md, mi = ops.topk_merge(d1, i1, top_d, top_i)
    np.testing.assert_allclose(md, jnp.sort(d1, axis=1)[:, :5], atol=0)


# ------------------------------------- interpret-mode coverage registry
# CI runners have no TPU: interpret mode is the ONLY execution of the
# Pallas kernel bodies there, so EVERY kernel in src/repro/kernels/
# must appear in this registry with an interpret-vs-oracle case. The
# meta test below enumerates the package's ``*_pallas`` entry points
# and fails when a new kernel module lands without one — the sweep
# itself re-runs each case at small shapes (the richer per-kernel
# sweeps live above and in tests/test_topk_select.py).


def _case_paa():
    x = rand((96, 64))
    return ops.paa(x, 8, force_pallas=True, tile=32), ref.ref_paa(x, 8)


def _case_box_mindist():
    q, lo = rand((9, 16)), rand((70, 16)) - 1.0
    hi = lo + jnp.abs(rand((70, 16)))
    w = jnp.abs(rand((16,), jnp.float32)) + 0.5
    return (ops.box_mindist(q, lo, hi, w, force_pallas=True, tile_b=8,
                            tile_l=32),
            ref.ref_box_mindist(q, lo, hi, w))


def _case_l2():
    q, x = rand((5, 96)), rand((67, 96))
    return (ops.l2(q, x, force_pallas=True, tile_b=8, tile_m=32,
                   tile_k=32),
            ref.ref_l2(q, x))


def _case_pq_adc():
    codes = jnp.asarray(RNG.integers(0, 32, size=(200, 8)), jnp.int32)
    lut = jnp.asarray(RNG.uniform(size=(8, 32)), jnp.float32)
    return (ops.pq_adc(codes, lut, force_pallas=True, tile_m=64),
            ref.ref_pq_adc(codes, lut))


def _case_coop_score_select():
    q, rows = rand((5, 32)), rand((96, 32))
    rn = ops.row_sq_norms(rows)
    ids = jnp.asarray(np.arange(96), jnp.int32)
    got = ops.coop_score_select(q, rows, rn, ids, 7,
                                force_pallas=True, tile_b=8, tile_r=32)
    want = ref.ref_coop_score_select(q, rows, rn, ids, 7)
    return jnp.concatenate([got[0], got[1].astype(jnp.float32)], 1), \
        jnp.concatenate([want[0], want[1].astype(jnp.float32)], 1)


def _case_pq_adc_select():
    codes = jnp.asarray(RNG.integers(0, 16, size=(96, 8)), jnp.int32)
    luts = jnp.asarray(RNG.uniform(size=(5, 8, 16)), jnp.float32)
    ids = jnp.asarray(np.arange(96), jnp.int32)
    got = ops.pq_adc_select(codes, luts, ids, 7, force_pallas=True,
                            tile_b=8, tile_r=32)
    want = ref.ref_pq_adc_select(codes, luts, ids, 7)
    return jnp.concatenate([got[0], got[1].astype(jnp.float32)], 1), \
        jnp.concatenate([want[0], want[1].astype(jnp.float32)], 1)


INTERPRET_CASES = {
    "paa_pallas": _case_paa,
    "box_mindist_pallas": _case_box_mindist,
    "l2_pallas": _case_l2,
    "pq_adc_pallas": _case_pq_adc,
    "coop_score_select_pallas": _case_coop_score_select,
    "pq_adc_select_pallas": _case_pq_adc_select,
}


@pytest.mark.parametrize("name", sorted(INTERPRET_CASES))
def test_interpret_mode_parity(name):
    got, want = INTERPRET_CASES[name]()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


def test_every_pallas_kernel_has_interpret_coverage():
    """Enumerate every ``*_pallas`` entry point exported by the kernel
    modules under repro.kernels; each must have an INTERPRET_CASES
    entry so CPU-only CI still executes its kernel body."""
    import importlib
    import pkgutil

    import repro.kernels as kpkg

    found = set()
    for info in pkgutil.iter_modules(kpkg.__path__):
        mod = importlib.import_module(f"repro.kernels.{info.name}")
        found |= {n for n in dir(mod)
                  if n.endswith("_pallas") and callable(getattr(mod, n))}
    assert found, "kernel package exports no *_pallas entry points?"
    missing = found - set(INTERPRET_CASES)
    assert not missing, (
        "Pallas kernels without an interpret-mode parity case: "
        f"{sorted(missing)} — add them to INTERPRET_CASES")
