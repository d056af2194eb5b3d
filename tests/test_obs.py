"""Observability layer (repro.obs): tracer, metrics registry, OocStats
schema, and the span-vs-counter no-drift contract on a real query.

The histogram quantile property test pins the documented resolution
claim: any quantile is within one log bucket (factor GROWTH ~ 1.09) of
the true sample quantile at the same rank convention
(numpy.quantile(..., method="lower")), and exactly inside [min, max].
"""

import json
import threading
import time

import numpy as np
import pytest
from _hyp import given, settings, st

from repro import obs
from repro.core import guarantees as G
from repro.core import search as S
from repro.core.index import FrozenIndex
from repro.core.indexes import dstree
from repro.obs import GROWTH, Histogram, MetricsRegistry, OocStats

pytestmark = pytest.mark.tier1

SETTINGS = dict(max_examples=40, deadline=None)


@pytest.fixture
def traced():
    """Enable tracing for one test, restore + clear afterwards."""
    obs.clear()
    obs.enable()
    yield obs.tracer()
    obs.disable()
    obs.clear()


# ------------------------------------------------------------- tracer
def test_disabled_span_is_shared_noop():
    assert not obs.enabled() and not obs.profiling()
    sp = obs.span("x", a=1)
    assert sp is obs.NULL_SPAN
    with sp as s:
        s.set(bytes_read=5)
        s.add("bytes_read", 5)
    assert obs.tracer().spans() == []


def test_span_nesting_and_profile(traced):
    with obs.span("root", k=5) as root:
        with obs.span("filter"):
            time.sleep(0.001)
        for i in range(3):
            with obs.span("iter", n=i) as it:
                it.set(bytes=10 * (i + 1))
    spans = traced.spans()
    # completion order: children land before their parent
    assert [s.name for s in spans] == ["filter", "iter", "iter",
                                       "iter", "root"]
    assert all(s.parent == root.id for s in spans[:-1])
    assert root.parent == -1
    prof = obs.last_profile("root")
    assert prof.attrs == {"k": 5}
    assert prof.count("iter") == 3
    assert prof.total("bytes") == 60
    assert set(prof.phase_ms) == {"filter", "iter"}
    assert prof.phase_ms["filter"] >= 1.0
    assert prof.duration_ms >= prof.phase_ms["filter"]


def test_subtree_isolates_concurrent_roots(traced):
    with obs.span("query") as q1:
        with obs.span("gather") as g1:
            pass
    with obs.span("query"):
        with obs.span("gather"):
            pass
    sub = traced.subtree(q1)
    assert {s.id for s in sub} == {q1.id, g1.id}


def test_threads_build_independent_subtrees(traced):
    barrier = threading.Barrier(2)
    roots = {}

    def work(tag):
        barrier.wait()
        with obs.span("troot", tag=tag) as r:
            with obs.span("tchild", tag=tag):
                pass
        roots[tag] = r

    ts = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for tag in ("a", "b"):
        assert roots[tag].parent == -1
        (child,) = [s for s in traced.find("tchild")
                    if s.attrs["tag"] == tag]
        assert child.parent == roots[tag].id
        assert child.tid == roots[tag].tid


def test_chrome_events_structure(tmp_path, traced):
    with obs.span("outer", codec="f32"):
        with obs.span("inner") as sp:
            sp.set(n=np.int64(7))  # numpy scalars must JSON-ify
    path = obs.dump_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert len(evs) == 2
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    inner = next(e for e in evs if e["name"] == "inner")
    outer = next(e for e in evs if e["name"] == "outer")
    assert inner["args"]["n"] == 7
    assert outer["args"]["codec"] == "f32"
    # child event nests inside its parent on the shared clock
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


# ----------------------------------------------------------- registry
def test_registry_label_keying_and_kind_conflict():
    reg = MetricsRegistry()
    a = reg.counter("reads", shard="0", codec="pq")
    b = reg.counter("reads", codec="pq", shard="0")  # order-insensitive
    c = reg.counter("reads", shard="1", codec="pq")
    assert a is b and a is not c
    a.inc(3)
    assert b.value == 3 and c.value == 0
    with pytest.raises(TypeError):
        reg.histogram("reads", shard="0", codec="pq")
    g = reg.gauge("depth")
    g.set(4)
    snap = reg.snapshot()
    assert snap["reads{codec=pq,shard=0}"] == 3
    assert snap["depth"] == 4
    assert len(reg.collect("reads")) == 2


def test_counter_window_marks_keep_lifetime_total():
    reg = MetricsRegistry()
    ctr = reg.counter("bytes")
    ctr.inc(100)
    ctr.mark()
    ctr.inc(7)
    assert ctr.since_mark == 7
    assert ctr.value == 107  # the registry never forgets


# ---------------------------------------------------------- histogram
def test_histogram_empty_and_singleton():
    h = Histogram("h", ())
    assert np.isnan(h.quantile(0.5))
    h.record(3.7)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.quantile(q) == 3.7  # clamped to [min, max] = point
    snap = h.snapshot()
    assert snap["count"] == 1 and snap["p50"] == 3.7


@given(st.lists(st.floats(1e-6, 1e6), min_size=1, max_size=100),
       st.floats(0.0, 1.0))
@settings(**SETTINGS)
def test_histogram_quantile_vs_numpy(xs, q):
    h = Histogram("h", ())
    for v in xs:
        h.record(v)
    got = h.quantile(q)
    x = np.asarray(xs, np.float64)
    # same rank convention as the histogram: value at floor(q*(n-1))
    ref = float(np.quantile(x, q, method="lower"))
    tol = GROWTH * (1 + 1e-9)
    assert ref / tol <= got <= ref * tol
    assert x.min() <= got <= x.max()


@given(st.lists(st.floats(1e-6, 1e6), min_size=2, max_size=60))
@settings(**SETTINGS)
def test_histogram_quantiles_monotone(xs):
    h = Histogram("h", ())
    for v in xs:
        h.record(v)
    qs = [h.quantile(q) for q in (0.0, 0.25, 0.5, 0.75, 0.95, 1.0)]
    assert all(a <= b for a, b in zip(qs, qs[1:]))
    assert h.count == len(xs)
    np.testing.assert_allclose(h.sum, sum(xs), rtol=1e-9)


# ------------------------------------------------------------ OocStats
def test_oocstats_mapping_surface():
    st_ = OocStats(codec="pq", bytes_read=42)
    assert st_["bytes_read"] == 42 and st_.get("codec") == "pq"
    assert st_.get("nope", 3) == 3
    with pytest.raises(KeyError):
        st_["nope"]
    assert "bytes_read" in st_ and "nope" not in st_
    assert dict(st_.items())["codec"] == "pq"
    assert st_.as_dict()["bytes_read"] == 42


def test_oocstats_aggregate_rates_and_weighted_slack():
    s1 = OocStats(hits=3, misses=1, hits_distinct=2, bytes_read=100,
                  stop_epsilon=2, eps_slack=1.0, stop_delta=1,
                  delta_slack=4.0, pruning_ratio=0.5, iterations=2,
                  loop_s=0.5, gather_s=0.25, sync_s=0.125, host_syncs=11)
    s2 = OocStats(hits=1, misses=3, hits_distinct=1, bytes_read=50,
                  stop_epsilon=0, eps_slack=99.0,  # zero-weight: ignored
                  pruning_ratio=0.7, iterations=3,
                  loop_s=1.0, gather_s=0.5, sync_s=0.25, host_syncs=17)
    agg = OocStats.aggregate([s1, s2])
    assert agg.bytes_read == 150 and agg.iterations == 5
    assert (agg.loop_s, agg.gather_s, agg.sync_s, agg.host_syncs) == (
        1.5, 0.75, 0.375, 28)
    assert agg.hits == 4 and agg.misses == 4
    np.testing.assert_allclose(agg.hit_rate, 4 / 8)
    np.testing.assert_allclose(agg.hit_rate_distinct, 3 / 7)
    np.testing.assert_allclose(agg.eps_slack, 1.0)
    np.testing.assert_allclose(agg.delta_slack, 4.0)
    np.testing.assert_allclose(agg.pruning_ratio, 0.6)
    assert agg.shards == [s1, s2]
    assert OocStats.aggregate([]).bytes_read == 0


# ------------------------------------- no-drift contract on a real query
def test_span_attrs_match_stats_on_real_query(walk_data, walk_queries,
                                              tmp_path, traced):
    ix = dstree.build(walk_data, leaf_cap=32)
    store = FrozenIndex.load(ix.save(str(tmp_path / "idx")),
                             resident="summaries")
    out = S.search_ooc(store, walk_queries, 5, G.epsilon(1.0),
                       cache_leaves=6)
    st_ = out.stats
    prof = obs.last_profile("ooc.query")
    assert prof is not None
    # the span attrs ARE the OocStats fields — one schema, two views
    for field in ("bytes_read", "bytes_h2d", "iterations",
                  "leaves_visited", "rows_scanned", "frontier_refills",
                  "stop_delta", "stop_epsilon", "stop_exhausted"):
        assert prof.attrs[field] == st_[field], field
    assert prof.count("ooc.iteration") == st_.iterations
    assert {"ooc.filter", "ooc.iteration",
            "ooc.finalize"} <= set(prof.phase_ms)
    # every lane accounted to exactly one stop condition
    assert (st_.stop_delta + st_.stop_epsilon
            + st_.stop_exhausted) == walk_queries.shape[0]
    assert 0.0 <= st_.pruning_ratio <= 1.0
    # per-iteration demand reads fold up to the sync-read total
    assert prof.total("bytes_read_sync") == st_.bytes_read_sync


def test_tracing_does_not_change_answers(walk_data, walk_queries,
                                         tmp_path):
    """Bit-identical answers with obs on or off and the profiler
    collecting or not: spans never sync or reorder device work."""
    import jax

    ix = dstree.build(walk_data, leaf_cap=32)
    store = FrozenIndex.load(ix.save(str(tmp_path / "idx")),
                             resident="summaries")

    def query():
        return S.search_ooc(store, walk_queries, 5, G.epsilon(1.0),
                            cache_leaves=6)

    plain = query()
    outs = []
    for record, profile in ((True, False), (False, True), (True, True)):
        if record:
            obs.enable()
        try:
            if profile:
                with jax.profiler.trace(str(tmp_path / f"tr{record}")):
                    outs.append(query())
            else:
                outs.append(query())
        finally:
            obs.disable()
            obs.clear()
    for traced in outs:
        np.testing.assert_array_equal(np.asarray(plain.result.ids),
                                      np.asarray(traced.result.ids))
        np.testing.assert_array_equal(np.asarray(plain.result.dists),
                                      np.asarray(traced.result.dists))
        assert plain.stats.leaves_visited == traced.stats.leaves_visited
        assert plain.stats.host_syncs == traced.stats.host_syncs


# ------------------------------------ the mirror into the profiler trace
@pytest.fixture(scope="module")
def resident_engine(walk_data):
    import jax

    from repro.core import IndexSpec
    from repro.core.engine import DistributedEngine

    eng = DistributedEngine(jax.make_mesh((1,), ("data",)),
                            method="dstree")
    return eng.build(walk_data, index=IndexSpec("dstree", leaf_cap=32))


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every host event of the one
    ``.xplane.pb`` under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    prof = ProfileData.from_file(path)
    return [(ev.name, ev.start_ns, ev.end_ns)
            for plane in prof.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_spans_reach_the_profiler_trace_with_obs_off(
        walk_data, walk_queries, resident_engine, tmp_path):
    """With obs off, a spilled search and a resident engine query write
    their spans into the profiler's trace, inside an enclosing
    ``bench.*``-style annotation on the same host clock, and the obs
    tracer records nothing."""
    import jax

    ix = dstree.build(walk_data, leaf_cap=32)
    store = FrozenIndex.load(ix.save(str(tmp_path / "idx")),
                             resident="summaries")
    resident_engine.query(walk_queries, 5, G.epsilon(1.0))  # warm
    assert not obs.enabled()
    obs.clear()
    with jax.profiler.trace(str(tmp_path / "trace")):
        with jax.profiler.TraceAnnotation("test.window"):
            S.search_ooc(store, walk_queries, 5, G.epsilon(1.0),
                         cache_leaves=6, prefetch=False)
            res = resident_engine.query(walk_queries, 5, G.epsilon(1.0))
            np.asarray(res.ids)
    assert obs.tracer().spans() == []
    assert not obs.profiling()
    events = _host_events(tmp_path / "trace")
    (w0, w1), = [(a, b) for n, a, b in events if n == "test.window"]
    names = {n for n, _, _ in events}
    assert {"ooc.query", "ooc.iteration", "ooc.tick", "ooc.gather",
            "ooc.prefetch", "ooc.score", "ooc.stop", "ooc.sync",
            "store.read", "store.h2d", "engine.query",
            "engine.dispatch"} <= names
    mine = [(a, b) for n, a, b in events
            if n.split(".")[0] in ("ooc", "store", "engine")]
    assert all(w0 <= a <= b <= w1 for a, b in mine)


def test_span_states(tmp_path):
    """Off: the shared no-op. Profiler only: an annotation that takes
    the span surface and records no Span. Obs on: a recording Span."""
    import jax

    assert obs.span("x") is obs.NULL_SPAN
    with jax.profiler.trace(str(tmp_path)):
        assert obs.profiling()
        sp = obs.span("x", a=1)
        assert sp is not obs.NULL_SPAN and not isinstance(sp, obs.Span)
        with sp as s:
            s.set(a=2)
            s.add("a", 1)
        obs.enable()
        try:
            assert isinstance(obs.span("x"), obs.Span)
        finally:
            obs.disable()
    assert obs.tracer().spans() == []
    assert obs.span("x") is obs.NULL_SPAN


def test_resident_dispatch_is_timed(walk_queries, resident_engine):
    res = resident_engine.query(walk_queries, 5, G.epsilon(1.0))
    assert res.stats is None
    assert res.dispatch_s > 0.0


def test_loop_counters_are_their_spans(walk_data, walk_queries,
                                       tmp_path, traced):
    """The loop's always-on counters: a pinned count of device->host
    reads per iteration, gathers and syncs inside the loop's time, and
    (with obs recording) the counters equal their spans' stamps."""
    ix = dstree.build(walk_data, leaf_cap=32)
    store = FrozenIndex.load(ix.save(str(tmp_path / "idx")),
                             resident="summaries")
    nprobe = 4
    st_ = S.search_ooc(store, walk_queries, 5, G.ng(nprobe),
                       cache_leaves=6, prefetch_depth=1).stats
    # every lane runs the whole rank budget: per iteration the reads of
    # pos, the window's leaves, the next window (none in the last
    # iteration), valid, next_lb and bsf
    assert st_.stop_exhausted == walk_queries.shape[0]
    assert st_.iterations == nprobe
    assert st_.host_syncs == 6 * nprobe - 1
    assert 0.0 < st_.gather_s + st_.sync_s <= st_.loop_s
    syncs = traced.find("ooc.sync")
    assert len(syncs) == st_.host_syncs
    assert sum(sp.duration_s for sp in syncs) == st_.sync_s
    assert sum(sp.duration_s
               for sp in traced.find("ooc.gather")) == st_.gather_s
    per_iter = {"ooc.tick", "ooc.gather", "ooc.prefetch", "ooc.score",
                "ooc.stop"}
    for name in per_iter:
        assert len(traced.find(name)) == nprobe, name
    its = traced.find("ooc.iteration")
    assert {sp.parent for sp in traced.spans()
            if sp.name in per_iter} == {sp.id for sp in its}


# ------------------------------------------------- serve-side plumbing
def test_request_submitted_at_on_the_shared_clock():
    from repro.serve.batching import Request

    t0 = obs.now()
    r = Request(uid=0, prompt=np.arange(4, dtype=np.int32))
    t1 = obs.now()
    assert t0 <= r.submitted_at <= t1


def test_run_retrieval_attributes_time_per_group(traced):
    """Satellite: a request is charged its OWN guarantee group's
    retrieval time, not the whole batch's."""
    import jax.numpy as jnp

    from repro.core.search import SearchResult
    from repro.serve.batching import Request, Scheduler

    class SleepyEngine:
        def query(self, q, k, g):
            if g.kind == "ng":
                time.sleep(0.05)  # only the degraded tier is slow
            b = q.shape[0]
            return SearchResult(
                dists=jnp.zeros((b, k), jnp.float32),
                ids=jnp.tile(jnp.arange(k, dtype=jnp.int32), (b, 1)),
                leaves_visited=jnp.zeros((b,), jnp.int32),
                rows_scanned=jnp.zeros((b,), jnp.int32),
                lb_computed=jnp.int32(0),
            )

    reqs = [Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                    series=np.zeros(8, np.float32)),
            Request(uid=1, prompt=np.arange(4, dtype=np.int32),
                    deadline_ms=2.0, series=np.zeros(8, np.float32))]
    eng = SleepyEngine()
    Scheduler().run_retrieval(eng, reqs, k=3)  # warm jnp dispatch
    out = Scheduler().run_retrieval(eng, reqs, k=3)
    assert out[1]["kind"] == "ng" and out[0]["kind"] == "exact"
    assert out[1]["retrieval_ms"] >= 50.0
    # the exact-group request is NOT charged for the ng group's sleep
    assert out[0]["retrieval_ms"] < out[1]["retrieval_ms"]
    kinds = {sp.attrs["kind"] for sp in
             traced.find("serve.retrieval_group")}
    assert kinds == {"exact", "ng"}
