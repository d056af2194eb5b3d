"""Compile every main-path Pallas kernel for a TPU v5e at real widths.

Nothing runs: each kernel is lowered and compiled for one chip of a
described (not attached) ``v5e:2x2`` topology, which is where Mosaic
refuses layouts and VMEM budgets that interpret mode accepts. Widths
are the serving path's: a 32-query batch padded to the 128-lane tile,
series length 256, 8,192 dstree leaves with 16 EAPCA dims, and the
cooperative pool of one iteration (32 lanes x 512 rows = 16,384 rows)
at k = 10 and 100 (kk = 2k candidates per lane).

The topology is described inside a module-scoped fixture, never at
import: only the xdist worker that runs this file loads the TPU
library, and every worker collects the same tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.box_mindist import box_mindist_pallas
from repro.kernels.l2_dist import l2_pallas
from repro.kernels.paa import paa_pallas
from repro.kernels.pq_adc_select import pq_adc_select_pallas
from repro.kernels.topk import coop_score_select_pallas

pytestmark = pytest.mark.tier1

B, N_LEN, LEAVES, DIMS, POOL = 128, 256, 8192, 16, 16384
PQ_M, PQ_K = 16, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


CASES = {
    "paa": (lambda x: paa_pallas(x, 16),
            [((4096, N_LEN), jnp.float32)]),
    "box_mindist": (
        lambda q, lo, hi, w: box_mindist_pallas(q, lo, hi, w),
        [((B, DIMS), jnp.float32), ((LEAVES, DIMS), jnp.float32),
         ((LEAVES, DIMS), jnp.float32), ((DIMS,), jnp.float32)]),
    "l2": (lambda q, x: l2_pallas(q, x),
           [((B, N_LEN), jnp.float32), ((4096, N_LEN), jnp.float32)]),
}
for _dt in (jnp.float32, jnp.bfloat16):
    for _kk in (20, 200):
        CASES[f"coop_score_select-{jnp.dtype(_dt).name}-kk{_kk}"] = (
            lambda q, r, rn, i, kk=_kk: coop_score_select_pallas(
                q, r, rn, i, kk),
            [((B, N_LEN), jnp.float32), ((POOL, N_LEN), _dt),
             ((1, POOL), jnp.float32), ((1, POOL), jnp.int32)])
for _kk in (20, 200):
    CASES[f"pq_adc_select-kk{_kk}"] = (
        lambda c, lut, i, kk=_kk: pq_adc_select_pallas(c, lut, i, kk),
        [((POOL, PQ_M), jnp.int32), ((B, PQ_M, PQ_K), jnp.float32),
         ((1, POOL), jnp.int32)])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes = CASES[name]
    hlo = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel emitted"


@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spilled_step_reads_slot_pool_uncopied(dtype, share, one_chip,
                                               no_persistent_cache):
    """The spilled scoring step is handed the leaf cache's [S, M, C]
    slot pool as the cache holds it. At the real pool (1,024 slots of
    512 rows of 256) the compiled step reads it as [S*M, C] through a
    bitcast of the v5e's tiled layout, and copies no pool."""
    import re

    from repro.core.refine import ScoreCtx
    from repro.store.ooc import _refine_step

    slots, m, rows = 1024, 512, 4194304

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    ctx = ScoreCtx(qf=sds((32, N_LEN), jnp.float32),
                   ids=sds((rows,), jnp.int32),
                   norms=sds((rows,), jnp.float32), luts=None)
    idx = sds((32, m), jnp.int32)
    hlo = _refine_step.lower(
        ctx, sds((slots, m, N_LEN), dtype), idx, idx,
        sds((32, m), jnp.bool_), sds((32, 10), jnp.float32),
        sds((32, 10), jnp.int32), share=share, pq=False,
    ).compile().as_text()
    pool = re.search(r"(%pool[.\d]*) = \S+ parameter\(", hlo).group(1)
    uses = [ln for ln in hlo.splitlines()
            if re.search(re.escape(pool) + r"[,)]", ln)
            and "parameter(" not in ln]
    assert uses and all(" bitcast(" in ln for ln in uses), uses
    assert f"[{slots * m},{N_LEN}]" in uses[0]
