"""Out-of-core storage tier (repro.store): round-trip bit-exactness and
search parity. The load-bearing claim is that search_ooc is the SAME
algorithm as the in-memory search — identical visit order and stopping
predicates, only residency differs — so every assertion here is exact
equality, not tolerance. Lossy codecs (format v2) keep that bar where
it is keepable: bf16 ooc is bit-exact vs in-memory search over the
bfloat16 index; pq is held to the paper's guarantee checks after the
exact re-rank."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IndexSpec, StoreSpec
from repro.core import guarantees as G
from repro.core import search as S
from repro.core.engine import DistributedEngine
from repro.core.index import FrozenIndex
from repro.core.indexes import dstree, isax, vafile
from repro.store import (DeviceLeafCache, LeafPrefetcher, LeafStore,
                         StoreFormatDeprecationWarning)

pytestmark = pytest.mark.tier1


@pytest.fixture(scope="module")
def walk_data_mod(walk_data):
    return walk_data


@pytest.fixture(scope="module")
def queries_mod(walk_queries):
    return jnp.asarray(walk_queries)


@pytest.fixture(scope="module")
def built(walk_data_mod):
    return dstree.build(walk_data_mod, leaf_cap=32)


def assert_same(ref, got):
    np.testing.assert_array_equal(np.asarray(ref.ids),
                                  np.asarray(got.ids))
    np.testing.assert_array_equal(np.asarray(ref.dists),
                                  np.asarray(got.dists))
    np.testing.assert_array_equal(np.asarray(ref.leaves_visited),
                                  np.asarray(got.leaves_visited))
    np.testing.assert_array_equal(np.asarray(ref.rows_scanned),
                                  np.asarray(got.rows_scanned))


def test_save_load_round_trip_bit_exact(built, tmp_path):
    d = built.save(str(tmp_path / "idx"))
    full = FrozenIndex.load(d)
    for fld in ("box_lo", "box_hi", "weights", "offsets", "data", "ids"):
        np.testing.assert_array_equal(
            np.asarray(getattr(built, fld)),
            np.asarray(getattr(full, fld)), err_msg=fld)
    np.testing.assert_array_equal(np.asarray(built.hist.edges),
                                  np.asarray(full.hist.edges))
    for fld in ("kind", "summary", "n_summary", "max_leaf", "n_total",
                "series_len"):
        assert getattr(built, fld) == getattr(full, fld), fld


def test_bf16_payload_round_trip(walk_data_mod, tmp_path):
    ix = dstree.build(walk_data_mod, leaf_cap=32,
                      data_dtype=jnp.bfloat16)
    d = ix.save(str(tmp_path / "bf16"))
    full = FrozenIndex.load(d)
    assert full.data.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(ix.data),
                                  np.asarray(full.data))


def test_summaries_load_keeps_raw_data_off_device(built, tmp_path):
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    assert isinstance(store, LeafStore)
    assert store.resident.data.shape[0] == 0         # placeholder only
    assert isinstance(store.mmap, np.memmap)
    assert store.mmap.shape[0] == np.asarray(built.data).shape[0]


@pytest.mark.parametrize(
    "delta,epsilon,nprobe",
    [(1.0, 0.0, None),      # exact
     (1.0, 1.0, None),      # epsilon-approximate
     (0.99, 1.0, None),     # delta-epsilon
     (1.0, 0.0, 4)])        # ng(nprobe)
def test_ooc_matches_in_memory_small_cache(built, queries_mod, tmp_path,
                                           delta, epsilon, nprobe):
    """Cache (6 leaves) far smaller than the working set (16 leaves)."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    g = G.Guarantee(delta=delta, epsilon=epsilon, nprobe=nprobe)
    ref = S.search(built, queries_mod, 5, g)
    ooc = S.search_ooc(store, queries_mod, 5, g, cache_leaves=6)
    assert_same(ref, ooc.result)
    assert ooc.stats["bytes_read"] > 0
    assert ooc.stats["misses"] > 0


def test_ooc_matches_for_vafile_visit_batch(walk_data_mod, queries_mod,
                                            tmp_path):
    """VA+file: a 'leaf' is a single series, visit_batch=64 per hop."""
    va = vafile.build(walk_data_mod)
    store = FrozenIndex.load(va.save(str(tmp_path / "va")),
                             resident="summaries")
    ref = S.search(va, queries_mod, 5, G.epsilon(1.0), visit_batch=64)
    ooc = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                       visit_batch=64, cache_leaves=400)
    assert_same(ref, ooc.result)


def test_ooc_matches_for_isax(walk_data_mod, queries_mod, tmp_path):
    ix = isax.build(walk_data_mod, leaf_cap=32)
    store = FrozenIndex.load(ix.save(str(tmp_path / "isax")),
                             resident="summaries")
    ref = S.search(ix, queries_mod, 5)
    ooc = S.search_ooc(store, queries_mod, 5,
                       cache_leaves=max(ix.num_leaves // 4, 6))
    assert_same(ref, ooc.result)


def test_warm_cache_hits_and_fewer_reads(built, queries_mod, tmp_path):
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=store.num_leaves)
    cold = S.search_ooc(store, queries_mod, 5, cache=cache)
    cache.reset_counters()
    warm = S.search_ooc(store, queries_mod, 5, cache=cache)
    assert_same(cold.result, warm.result)
    assert warm.stats["bytes_read"] == 0       # fully cache-resident
    assert warm.stats["hit_rate"] == 1.0
    assert cold.stats["bytes_read"] > 0


def test_cache_eviction_counters_and_capacity(built, tmp_path):
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=4)
    L = store.num_leaves
    cache.get_slots(list(range(4)))
    assert cache.misses == 4 and cache.hits == 0
    cache.get_slots([0, 1])                    # resident -> hits
    assert cache.hits == 2
    for lf in range(4, L):                     # forces eviction
        cache.get_slots([lf])
    assert cache.misses == L
    assert cache.slots.shape[0] == 4           # pool never grows
    assert len(cache.slot_of) <= 4
    # evicted leaves must re-read
    before = cache.bytes_read
    cache.get_slots([0])
    assert cache.bytes_read > before


def test_prefetcher_stages_and_takes(built, tmp_path):
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    with LeafPrefetcher(store) as pf:
        pf.schedule([0, 1, 2])
        import time
        deadline = time.time() + 5.0
        got = None
        while got is None and time.time() < deadline:
            got = pf.take(1)
            if got is None:
                time.sleep(0.01)
        assert got is not None
        np.testing.assert_array_equal(got, store.read_leaf(1))
        assert pf.take(1) is None              # popped exactly once


def test_prefetcher_death_is_counted(built, tmp_path):
    """A reader thread that dies on an I/O error leaves the cache on
    demand reads only; the store.prefetch.died counter says so."""
    from repro import obs

    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")

    def broken(leaf):
        raise OSError("disk gone")

    store.read_leaf = broken
    pf = LeafPrefetcher(store)
    died = obs.REGISTRY.counter("store.prefetch.died", prefetch=pf.name)
    try:
        pf.schedule([0])
        deadline = time.time() + 5.0
        while died.value == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert died.value == 1
        assert pf.take(0) is None
    finally:
        pf.close()


# ---------------------------------------------------------------- v2 codecs


@pytest.fixture(scope="module")
def pq_store_dir(built, tmp_path_factory):
    d = tmp_path_factory.mktemp("pq_store")
    return built.save(str(d / "pq"), codec="pq")


@pytest.mark.parametrize("codec", ["f32", "bf16"])
@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("delta,epsilon", [(1.0, 0.0), (0.99, 1.0)])
def test_ooc_codec_parity_bit_exact(built, queries_mod, tmp_path,
                                    codec, share, delta, epsilon):
    """f32/bf16 ooc == in-memory search over the decoded index, bit
    exact, for both the per-lane and the cooperative scoring path."""
    d = built.save(str(tmp_path / codec), codec=codec)
    full = FrozenIndex.load(d)
    if codec == "bf16":
        assert full.data.dtype == jnp.bfloat16
    store = FrozenIndex.load(d, resident="summaries")
    g = G.Guarantee(delta=delta, epsilon=epsilon)
    ref = S.search(full, queries_mod, 5, g, share_gathers=share)
    ooc = S.search_ooc(store, queries_mod, 5, g,
                       share_gathers=share, cache_leaves=6)
    assert_same(ref, ooc.result)
    assert ooc.stats["codec"] == codec
    assert ooc.stats["share_gathers"] is share


@pytest.mark.parametrize("share", [False, True])
@pytest.mark.parametrize("delta,epsilon", [(1.0, 1.0), (0.99, 1.0)])
def test_ooc_pq_guarantee_with_exact_rerank(
        walk_data_mod, queries_mod, pq_store_dir, share, delta,
        epsilon):
    """pq + exact re-rank must satisfy the epsilon / delta-epsilon
    guarantee checks (Definition 5) against brute force — the reported
    distances are EXACT for the returned neighbors, so the (1+eps)
    bound is checkable directly."""
    store = FrozenIndex.load(pq_store_dir, resident="summaries")
    assert store.codec == "pq" and store.codebook is not None
    bf = S.brute_force(queries_mod, jnp.asarray(walk_data_mod), 5)
    ooc = S.search_ooc(store, queries_mod, 5,
                       G.Guarantee(delta=delta, epsilon=epsilon),
                       share_gathers=share, cache_leaves=6)
    ok = (np.asarray(ooc.result.dists)
          <= (1 + epsilon) * np.asarray(bf.dists) * (1 + 1e-4) + 1e-4)
    if delta == 1.0:
        assert ok.all()
    else:
        assert ok.mean() >= 0.9
    assert ooc.stats["bytes_read_rerank"] > 0


def test_pq_exact_guarantee_request_warns(queries_mod, pq_store_dir):
    """epsilon=0 (exact) cannot be honored over the lossy pq payload —
    the ADC kth-best can prune the true neighbor's leaf early — so
    asking for it must warn (nprobe / epsilon>0 requests must not)."""
    store = FrozenIndex.load(pq_store_dir, resident="summaries")
    with pytest.warns(UserWarning, match="cannot honor the exact"):
        S.search_ooc(store, queries_mod, 5, cache_leaves=6)
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error", UserWarning)
        S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                     cache_leaves=6)
        S.search_ooc(store, queries_mod, 5, G.ng(4), cache_leaves=6)


def test_dataset_nbytes_is_codec_invariant(built, tmp_path,
                                           pq_store_dir):
    """stats['dataset_bytes'] must mean the RAW collection for every
    codec, not the encoded payload, or %-data metrics skew 2x/64x."""
    raw = np.asarray(built.data).nbytes
    for codec in ("f32", "bf16"):
        d = built.save(str(tmp_path / f"dn_{codec}"), codec=codec)
        store = FrozenIndex.load(d, resident="summaries")
        assert store.dataset_nbytes == raw, codec
    store = FrozenIndex.load(pq_store_dir, resident="summaries")
    assert store.dataset_nbytes == raw


def test_pq_resident_full_round_trip_bit_exact(built, pq_store_dir):
    """codec="pq" keeps exact.bin, so resident="full" reconstitutes the
    original index bit-exactly despite the lossy refinement payload."""
    full = FrozenIndex.load(pq_store_dir)
    np.testing.assert_array_equal(np.asarray(built.data),
                                  np.asarray(full.data))
    np.testing.assert_array_equal(np.asarray(built.ids),
                                  np.asarray(full.ids))


def test_codec_payload_sizes_and_bytes_read(built, queries_mod,
                                            tmp_path, pq_store_dir):
    """The bytes-read currency: bf16 payload is exactly half of f32,
    pq codes far smaller still, and search_ooc bytes_read shrinks
    accordingly (the ISSUE's ~2x / ~8-16x targets at this scale)."""
    reads = {}
    payload = {}
    for codec in ("f32", "bf16", "pq"):
        d = pq_store_dir if codec == "pq" else \
            built.save(str(tmp_path / codec), codec=codec)
        payload[codec] = os.path.getsize(os.path.join(d, "data.bin"))
        store = FrozenIndex.load(d, resident="summaries")
        ooc = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                           cache_leaves=6)
        reads[codec] = ooc.stats["bytes_read"]
    assert payload["bf16"] * 2 == payload["f32"]
    assert payload["pq"] * 8 <= payload["f32"]
    assert reads["bf16"] <= 0.55 * reads["f32"]
    assert reads["pq"] <= 0.5 * reads["f32"]


def test_share_gathers_never_reads_more(built, queries_mod, tmp_path):
    """Cooperative scoring only tightens each lane's bsf, so it can
    only stop earlier — bytes_read must not grow."""
    d = built.save(str(tmp_path / "coop"))
    store = FrozenIndex.load(d, resident="summaries")
    solo = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                        cache_leaves=6, prefetch=False)
    coop = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                        cache_leaves=6, prefetch=False,
                        share_gathers=True)
    assert coop.stats["bytes_read"] <= solo.stats["bytes_read"]


def test_share_gathers_returns_distinct_ids(built, queries_mod,
                                            tmp_path):
    """Regression: a leaf pooled at two iterations is scored twice for
    every lane; without the dedup merge the top-k collapses to
    duplicate ids AND the kth-best shrinks below the true kth distinct
    distance (pruning too early). Both cooperative paths must return
    k distinct neighbors."""
    d = built.save(str(tmp_path / "dedup"))
    store = FrozenIndex.load(d, resident="summaries")
    ooc = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                       cache_leaves=6, share_gathers=True)
    ref = S.search(built, queries_mod, 5, G.epsilon(1.0),
                   share_gathers=True)
    for ids in (np.asarray(ooc.result.ids), np.asarray(ref.ids)):
        for row in ids:
            real = row[row >= 0]
            assert len(np.unique(real)) == len(real), row


def test_prefetch_false_disables_attached_prefetcher(
        built, queries_mod, tmp_path):
    """Regression: prefetch=False must suppress speculative scheduling
    even when the caller-supplied cache has a prefetcher attached —
    the flag exists to measure pure demand-path reads."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    pf = LeafPrefetcher(store)
    cache = DeviceLeafCache(store, capacity_leaves=6, prefetcher=pf)
    try:
        out = S.search_ooc(store, queries_mod, 5, cache=cache,
                           prefetch=False)
        assert out.stats["prefetch_bytes_read"] == 0
        assert pf.leaves_read == 0
        assert out.stats["bytes_read"] == out.stats["bytes_read_sync"]
    finally:
        pf.close()


def test_scatter_fill_traces_are_bucketed(built, tmp_path):
    """Miss batches pad to the next power of two, so the jitted scatter
    compiles O(log capacity) variants, not one per miss count."""
    from repro.store.cache import _scatter_fill
    if not hasattr(_scatter_fill, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=16)
    L = store.num_leaves                       # 16 for this fixture
    cache.get_slots(list(range(5)))            # 5 misses -> pad 8
    before = _scatter_fill._cache_size()
    cache.get_slots(list(range(5, 11)))        # 6 misses -> pad 8
    cache.get_slots(list(range(11, min(L, 18))))  # 5-7 misses -> pad 8
    assert _scatter_fill._cache_size() == before


def test_pq_rerank_distance_is_exact_at_zero(walk_data_mod, tmp_path):
    """The re-rank uses the direct difference form: a query identical
    to a stored series must come back at distance exactly 0.0 (the
    expanded |q|^2-2qx+|x|^2 form loses ~1e-3 to cancellation here)."""
    ix = dstree.build(walk_data_mod, leaf_cap=32)
    d = ix.save(str(tmp_path / "pq0"), codec="pq")
    store = FrozenIndex.load(d, resident="summaries")
    q = jnp.asarray(walk_data_mod[:4])         # exact stored rows
    ooc = S.search_ooc(store, q, 5, G.epsilon(1.0))
    ids = np.asarray(ooc.result.ids)
    dists = np.asarray(ooc.result.dists)
    for lane in range(4):
        hit = np.where(ids[lane] == lane)[0]
        assert hit.size == 1, (lane, ids[lane])
        assert dists[lane, hit[0]] == 0.0


def test_engine_spill_codec_threads_through(walk_data_mod, tmp_path):
    mesh = jax.make_mesh((1,), ("data",))
    eng = DistributedEngine(mesh, method="dstree")
    eng.build(walk_data_mod, index=IndexSpec("dstree", leaf_cap=32),
              store=StoreSpec(spill_dir=str(tmp_path), codec="bf16"))
    store = FrozenIndex.load(eng.shard_dirs[0], resident="summaries")
    assert store.codec == "bf16"
    assert store.mmap.dtype == jnp.bfloat16


def test_v1_store_reads_with_deprecation_warning(built, tmp_path):
    """v1 read-compat: a pre-codec artifact loads as codec="f32" but
    warns (scripts/verify.sh escalates the warning to an error so the
    repo itself never regenerates v1 stores)."""
    d = built.save(str(tmp_path / "v1"))
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = 1
    for key in ("codec", "payload_dtype", "payload_cols"):
        meta.pop(key, None)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.warns(StoreFormatDeprecationWarning):
        store = FrozenIndex.load(d, resident="summaries")
    assert store.codec == "f32"
    assert store.payload_cols == built.series_len


def test_newer_format_version_is_an_explicit_error(built, tmp_path):
    d = built.save(str(tmp_path / "vfuture"))
    meta_path = os.path.join(d, "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["format_version"] = 99
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    with pytest.raises(ValueError, match="newer"):
        FrozenIndex.load(d)


# ------------------------------------------------------- satellite bugfixes


def test_fill_reuses_device_pool_buffer(built, tmp_path):
    """Regression: the _fill scatter must donate the slot pool so the
    device buffer is updated in place (O(misses) per iteration), not
    copied wholesale (O(capacity * max_leaf * n))."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=8)
    cache.get_slots([0, 1])          # compile+donate path for 2 misses
    ptr = cache.slots.unsafe_buffer_pointer()
    cache.get_slots([2, 3])
    assert cache.slots.unsafe_buffer_pointer() == ptr
    cache.get_slots([4])             # different miss count: new trace
    cache.get_slots([5])
    assert cache.slots.unsafe_buffer_pointer() == ptr


@pytest.mark.parametrize("codec", ["f32", "bf16", "pq"])
def test_spilled_gather_compiles_layout_and_scores_slot_pool(
        built, queries_mod, tmp_path, monkeypatch, codec):
    """The spilled gather lays out its window in one compiled call
    (candidate_layout sees tracers, never concrete arrays) and scoring
    reads the cache's own [S, M, C] slot buffer, not a flattened copy
    of it made every iteration."""
    from repro.core import refine
    from repro.store import ooc
    store = FrozenIndex.load(built.save(str(tmp_path / codec), codec=codec),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=6)
    traced, pools = [], []
    real_layout, real_step = refine.candidate_layout, ooc._refine_step

    def layout(offsets, leaf, ok, *a):
        traced.append(isinstance(leaf, jax.core.Tracer)
                      and isinstance(ok, jax.core.Tracer))
        return real_layout(offsets, leaf, ok, *a)

    def step(ctx, pool, *a, **kw):
        pools.append((pool is cache.slots, pool.shape,
                      pool.unsafe_buffer_pointer()
                      == cache.slots.unsafe_buffer_pointer()))
        return real_step(ctx, pool, *a, **kw)

    monkeypatch.setattr(refine, "candidate_layout", layout)
    monkeypatch.setattr(ooc, "_refine_step", step)
    ooc._slot_layout.clear_cache()   # trace anew, through the wrapper
    try:
        out = S.search_ooc(store, queries_mod, 5, G.epsilon(1.0),
                           cache=cache)
    finally:
        ooc._slot_layout.clear_cache()
    assert traced and all(traced)
    assert len(pools) == out.stats["iterations"]
    want = (store.max_leaf, store.payload_cols)
    for same, shape, same_ptr in pools:
        assert same and same_ptr
        assert shape == (cache.capacity,) + want


@pytest.mark.parametrize("codec", ["f32", "bf16", "pq"])
def test_spilled_search_bit_exact_across_batch_shapes(
        built, queries_mod, tmp_path, codec):
    """A spilled search gives the same ids and distances, bit for bit,
    before and after a gather at a second batch shape (its own layout
    and scoring programs) on the same warm cache; the lossless codecs
    also match the in-memory search at both shapes."""
    d = built.save(str(tmp_path / codec), codec=codec)
    store = FrozenIndex.load(d, resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=6)
    g = G.epsilon(1.0)
    small = queries_mod[:3]
    first = S.search_ooc(store, queries_mod, 5, g, cache=cache).result
    second = S.search_ooc(store, small, 5, g, cache=cache).result
    again = S.search_ooc(store, queries_mod, 5, g, cache=cache).result
    assert_same(first, again)
    if codec != "pq":
        full = FrozenIndex.load(d)
        assert_same(S.search(full, queries_mod, 5, g), again)
        assert_same(S.search(full, small, 5, g), second)


def test_prefetcher_reset_counters_quiesces(built, tmp_path):
    """Regression: a cold-pass read still in flight must not land its
    bytes AFTER reset_counters zeroes them (the straggler race that
    polluted warm-run stats in bench_query_disk)."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    with LeafPrefetcher(store) as pf:
        pf.schedule(list(range(store.num_leaves)))
        pf.reset_counters()          # drops the queue, waits in-flight
        t0 = time.monotonic()
        while time.monotonic() - t0 < 0.3:
            assert pf.bytes_read == 0 and pf.leaves_read == 0
            time.sleep(0.02)
        # counters still work for reads scheduled AFTER the reset
        pf.schedule([0])
        deadline = time.monotonic() + 5.0
        while pf.take(0, timeout=0.1) is None \
                and time.monotonic() < deadline:
            pass
        assert pf.bytes_read == store.leaf_nbytes(0)
        assert pf.leaves_read == 1


def test_per_request_hit_counting_with_duplicates(built, tmp_path):
    """Pin the get_slots accounting semantics: every occurrence served
    without a disk read is a hit; misses count distinct reads; the
    distinct view is reported alongside."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    cache = DeviceLeafCache(store, capacity_leaves=8)
    # 4 lanes share leaf 0, 2 request leaf 1: two reads, four dup hits
    slots = cache.get_slots([0, 0, 1, 0, 0, 1])
    assert cache.misses == 2
    assert cache.hits == 4            # per-request: dups are hits
    assert cache.hits_distinct == 0   # nothing resident at batch start
    assert slots[0] == slots[1] == slots[3] == slots[4]
    # resident leaves: every occurrence is a hit, one distinct each
    cache.get_slots([0, 1, 0])
    assert cache.hits == 7 and cache.hits_distinct == 2
    st = cache.stats()
    assert st["hit_rate"] == pytest.approx(7 / 9)
    assert st["hit_rate_distinct"] == pytest.approx(2 / 4)


def test_warm_cache_with_attached_prefetcher_stats(built, queries_mod,
                                                   tmp_path):
    """Caller-supplied cache with its OWN prefetcher: the stats fold-in
    must route through cache.bytes_read (no double count), and a warm
    pass — after the quiescing reset — reads nothing."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    pf = LeafPrefetcher(store)
    cache = DeviceLeafCache(store, capacity_leaves=store.num_leaves,
                            prefetcher=pf)
    try:
        cold = S.search_ooc(store, queries_mod, 5, cache=cache)
        assert cache.prefetcher is pf       # not detached
        assert cold.stats["bytes_read"] == \
            cold.stats["bytes_read_sync"] \
            + cold.stats["prefetch_bytes_read"]
        cache.reset_counters()
        warm = S.search_ooc(store, queries_mod, 5, cache=cache)
        assert_same(cold.result, warm.result)
        assert warm.stats["bytes_read"] == 0
        assert warm.stats["prefetch_bytes_read"] == 0
        assert warm.stats["hit_rate"] == 1.0
    finally:
        pf.close()


def test_read_leaf_out_reuse_zeroes_tail(built, tmp_path):
    """A reused out= buffer must not leak rows from a larger leaf that
    previously occupied it."""
    store = FrozenIndex.load(built.save(str(tmp_path / "idx")),
                             resident="summaries")
    sizes = store.offsets_h[1:] - store.offsets_h[:-1]
    big = int(np.argmax(sizes))
    small = int(np.argmin(np.where(sizes > 0, sizes, sizes.max())))
    buf = store.read_leaf(big)
    buf[:] = 7                       # poison: simulate stale rows
    out = store.read_leaf(small, out=buf)
    assert out is buf
    ssz = store.leaf_size(small)
    np.testing.assert_array_equal(
        out[:ssz], store.mmap[store.offsets_h[small]:
                              store.offsets_h[small] + ssz])
    assert not np.any(out[ssz:])     # tail fully zeroed


def test_engine_spill_round_trip(walk_data_mod, queries_mod, tmp_path):
    mesh = jax.make_mesh((1,), ("data",))
    eng = DistributedEngine(mesh, method="dstree")
    eng.build(walk_data_mod, index=IndexSpec("dstree", leaf_cap=32),
              store=StoreSpec(spill_dir=str(tmp_path)))
    assert eng.shard_dirs is not None and len(eng.shard_dirs) == 1
    store = FrozenIndex.load(eng.shard_dirs[0], resident="summaries")
    assert store.meta["n_total"] == walk_data_mod.shape[0]
    ref = S.brute_force(queries_mod, jnp.asarray(walk_data_mod), 5)
    ooc = S.search_ooc(store, queries_mod, 5, cache_leaves=6)
    np.testing.assert_array_equal(np.asarray(ref.ids),
                                  np.asarray(ooc.result.ids))
    # brute_force uses the fused l2 kernel; tolerance covers the f32
    # summation-order difference vs the refinement einsum
    np.testing.assert_allclose(np.asarray(ref.dists),
                               np.asarray(ooc.result.dists),
                               rtol=1e-4, atol=1e-4)
