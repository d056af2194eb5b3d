"""PR 9 continuous-batching front: admission, lanes, re-entrancy.

Four layers:

  admission  AdmissionController units — depth cap + reject reason,
             the serve.queue_depth gauge, accept/reject/shed
             counters, hysteresis latching, the degrade_tier ladder.
  scheduler  the starvation regression (oldest-head drain order under
             sustained small-bucket load) and the remaining-budget
             guarantee remap (a request that burned its budget in the
             queue drains at the tier its remaining time affords).
  front      ServeFront semantics over a stub engine — routing,
             rejection, shedding, stop(drain=...), error isolation.
  stress     N submitter threads against lane workers over a REAL
             spilled multi-shard engine: every answer bit-exact (ids
             AND dists) vs the serial oracle, no dropped or
             duplicated uids, and the dynamic lock graph
             (front cond + engine per-copy locks + cache/prefetcher
             locks) stays acyclic — the engine re-entrancy contract
             the tentpole introduced.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import search as S
from repro.core import IndexSpec, StoreSpec
from repro.core.engine import DistributedEngine, QueryResult
from repro.core.guarantees import Guarantee
from repro.serve.admission import AdmissionController, degrade_tier
from repro.serve.batching import (Request, Scheduler,
                                  guarantee_for_deadline,
                                  remaining_budget_ms, retrieval_groups)
from repro.serve.loop import LANES, Rejected, ServeFront, lane_of

pytestmark = pytest.mark.tier1

N, DIM, SHARDS, K = 512, 32, 4, 5


# ------------------------------------------------------------ admission
def test_admission_cap_rejects_with_reason():
    a = AdmissionController(max_depth=3)
    c_acc = obs.REGISTRY.counter("serve.admission.accepted",
                                 kind="epsilon")
    c_rej = obs.REGISTRY.counter("serve.admission.rejected",
                                 reason="queue_full")
    c_acc.mark()
    c_rej.mark()
    assert [a.try_admit("epsilon") for _ in range(3)] == [None] * 3
    assert a.depth == 3
    assert a.try_admit("epsilon") == "queue_full"
    assert a.depth == 3
    assert c_acc.since_mark == 3 and c_rej.since_mark == 1
    a.release(2)
    assert a.depth == 1 and a.try_admit("epsilon") is None


def test_admission_gauge_tracks_depth():
    a = AdmissionController(max_depth=8)
    g = obs.REGISTRY.gauge("serve.queue_depth")
    a.try_admit()
    a.try_admit()
    assert g.value == 2
    a.release()
    assert g.value == 1
    a.release(5)  # clamps at zero, never negative
    assert g.value == 0 and a.depth == 0


def test_admission_shedding_hysteresis():
    a = AdmissionController(max_depth=8, shed_high_frac=0.75,
                            shed_low_frac=0.25)
    for _ in range(5):
        a.try_admit()
    assert not a.shedding()          # 5 < shed_high=6
    a.try_admit()
    assert a.shedding()              # latched at 6
    a.release(3)
    assert a.shedding()              # 3 is inside the band: sticky
    a.release(1)
    assert not a.shedding()          # 2 <= shed_low=2: cleared
    a.try_admit()
    assert not a.shedding()          # re-latch needs shed_high again


def test_admission_validates_construction():
    with pytest.raises(ValueError):
        AdmissionController(max_depth=0)
    with pytest.raises(ValueError):
        AdmissionController(max_depth=8, shed_low_frac=0.8,
                            shed_high_frac=0.2)


def test_degrade_tier_ladder():
    eps = Guarantee(epsilon=0.5)
    de = degrade_tier(eps)
    assert de.kind == "delta-epsilon"
    assert de.delta == 0.99 and de.epsilon >= 1.0
    assert degrade_tier(Guarantee()).kind == "delta-epsilon"
    ng = degrade_tier(de)
    assert ng.kind == "ng" and ng.nprobe == 16
    assert degrade_tier(ng).nprobe == 8
    assert degrade_tier(Guarantee(nprobe=1)).nprobe == 1  # floor


def test_shed_counts_against_original_kind():
    a = AdmissionController(max_depth=8)
    c = obs.REGISTRY.counter("serve.admission.shed", kind="epsilon")
    c.mark()
    out = a.shed(Guarantee(epsilon=0.5))
    assert out.kind == "delta-epsilon" and c.since_mark == 1
    # bottomed-out tier: no-op, no counter
    c2 = obs.REGISTRY.counter("serve.admission.shed", kind="ng")
    c2.mark()
    assert a.shed(Guarantee(nprobe=1)) == Guarantee(nprobe=1)
    assert c2.since_mark == 0


# ------------------------------------------------------------ scheduler
def test_next_batch_no_starvation_under_small_request_load():
    """Regression: sorted(queues) drained the smallest bucket first,
    so one large request behind sustained small-prompt load NEVER
    drained. Oldest-head-first drains it as soon as its head is the
    longest-waiting."""
    s = Scheduler(max_batch=4, min_bucket=8)
    s.submit(Request(uid=100, prompt=np.arange(20, dtype=np.int32)))
    for uid in range(8):  # sustained small load AFTER the big request
        s.submit(Request(uid=uid, prompt=np.arange(4, dtype=np.int32)))
    bucket, batch = s.next_batch()
    assert bucket == 32 and [r.uid for r in batch] == [100]
    drained = []
    while True:
        nb = s.next_batch()
        if nb is None:
            break
        drained.extend(r.uid for r in nb[1])
    assert drained == list(range(8))


def test_remaining_budget_ms():
    t0 = obs.now()
    r = Request(uid=0, prompt=np.zeros(2, np.int32), deadline_ms=50.0)
    assert remaining_budget_ms(r, r.submitted_at) == pytest.approx(50.0)
    assert remaining_budget_ms(r, r.submitted_at + 0.040) \
        == pytest.approx(10.0, abs=1e-6)
    # spent budgets clamp to ~0, never negative
    assert remaining_budget_ms(r, r.submitted_at + 9.9) == 1e-3
    no_dl = Request(uid=1, prompt=np.zeros(2, np.int32))
    assert remaining_budget_ms(no_dl, t0) is None


def test_retrieval_groups_remap_from_remaining_budget():
    """A 50ms-deadline request that already waited 40ms must drain at
    the tier 10ms affords (ng), NOT the epsilon tier the submitted
    deadline bought; an un-waited twin keeps the full tier."""
    fresh = Request(uid=0, prompt=np.zeros(2, np.int32),
                    deadline_ms=50.0, series=np.zeros(8, np.float32))
    stale = Request(uid=1, prompt=np.zeros(2, np.int32),
                    deadline_ms=50.0, series=np.zeros(8, np.float32))
    now = max(fresh.submitted_at, stale.submitted_at)
    fresh.submitted_at = now               # zero wait: full 50ms left
    stale.submitted_at = now - 0.040        # 40ms already in queue
    by_kind = {g.kind: [r.uid for r in rs]
               for g, rs in retrieval_groups([fresh, stale], at=now)}
    assert by_kind["exact"] == [0]
    assert any(stale.uid in uids and kind == "ng"
               for kind, uids in by_kind.items()), by_kind
    # at=None keeps the pure submitted-deadline mapping: both full tier
    pure = retrieval_groups([fresh, stale], at=None)
    assert len(pure) == 1 and pure[0][0] == guarantee_for_deadline(50.0)


# ---------------------------------------------------------------- front
class _StubEngine:
    """Deterministic engine double: ids[i] = first k multiples of the
    lane's series value; stats=None (resident-style)."""

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.calls = []
        self._lock = threading.Lock()

    def query(self, qs, k, g):
        with self._lock:
            self.calls.append((int(qs.shape[0]), g))
        if self.delay_s:
            time.sleep(self.delay_s)
        q = np.asarray(qs)
        b = q.shape[0]
        ids = (q[:, :1].astype(np.int32) * 10
               + np.arange(k, dtype=np.int32))
        return QueryResult(
            dists=jnp.asarray(np.zeros((b, k), np.float32)),
            ids=jnp.asarray(ids),
            leaves_visited=jnp.zeros(b, jnp.int32),
            rows_scanned=jnp.zeros(b, jnp.int32),
            lb_computed=jnp.int32(0), stats=None)


def _req(uid, dl=None, val=None):
    return Request(uid=uid, prompt=np.zeros(2, np.int32),
                   deadline_ms=dl,
                   series=np.full(8, val if val is not None else uid,
                                  np.float32))


def test_lane_of_routing():
    assert lane_of("exact") == "epsilon"
    assert lane_of("epsilon") == "epsilon"
    assert lane_of("delta-epsilon") == "delta-epsilon"
    assert lane_of("ng") == "ng"
    assert set(LANES) == {"epsilon", "delta-epsilon", "ng"}


def test_front_forwards_ooc_opts():
    """ooc_opts reaches every engine.query call; without it the call
    carries no extra keyword (engines need not accept one)."""
    class _Recorder(_StubEngine):
        def query(self, qs, k, g, **kw):
            self.calls.append(kw)
            return super().query(qs, k, g)

    eng = _Recorder()
    with ServeFront(eng, k=3, ooc_opts={"share_gathers": True}) as front:
        front.submit(_req(0)).result(timeout=10.0)
    assert {"ooc_opts": {"share_gathers": True}} in eng.calls
    plain = _StubEngine()
    with ServeFront(plain, k=3) as front:
        assert "error" not in front.submit(_req(1)).result(timeout=10.0)


def test_front_answers_and_releases_admission():
    eng = _StubEngine()
    with ServeFront(eng, k=3, max_batch=4) as front:
        tickets = [front.submit(_req(u, dl)) for u, dl in
                   [(0, None), (1, 30.0), (2, 5.0), (3, None)]]
        outs = {t.uid: t.result(timeout=10.0) for t in tickets}
    assert sorted(outs) == [0, 1, 2, 3]
    for u, o in outs.items():
        assert np.array_equal(o["ids"], u * 10 + np.arange(3)), o
        assert o["latency_ms"] >= o["queue_wait_ms"] >= 0.0
    assert outs[0]["kind"] == "exact"
    assert outs[2]["kind"] == "ng"
    assert front.admission.depth == 0


def test_front_rejects_past_cap():
    # a stalled engine keeps the lane busy while submits pile up
    eng = _StubEngine(delay_s=0.2)
    adm = AdmissionController(max_depth=2)
    front = ServeFront(eng, k=3, max_batch=1, admission=adm).start()
    try:
        t0 = front.submit(_req(0))
        t1 = front.submit(_req(1))
        with pytest.raises(Rejected) as ei:
            front.submit(_req(2))
        assert ei.value.reason == "queue_full"
        assert t0.result(10.0)["ids"] is not None
        assert t1.result(10.0)["ids"] is not None
    finally:
        front.stop()
    # slots freed: a new submit is admitted again
    assert adm.try_admit() is None


def test_front_sheds_one_tier_under_pressure():
    """With shedding latched, a drained exact-tier request is degraded
    one tier (delta-epsilon), flagged on the entry, and counted
    against the ORIGINAL kind."""
    adm = AdmissionController(max_depth=8, shed_high_frac=0.25,
                              shed_low_frac=0.0)
    # latch shedding with phantom depth the front never releases
    adm.try_admit()
    adm.try_admit()
    assert adm.shedding()
    c = obs.REGISTRY.counter("serve.admission.shed", kind="exact")
    c.mark()
    eng = _StubEngine()
    with ServeFront(eng, k=3, admission=adm) as front:
        out = front.submit(_req(0, dl=None)).result(timeout=10.0)
    assert out["shed"] is True
    assert out["nominal_kind"] == "exact"
    assert out["kind"] == "delta-epsilon"
    assert c.since_mark >= 1
    assert all(g.kind == "delta-epsilon" for _b, g in eng.calls)


def test_front_stop_drain_false_fails_pending():
    eng = _StubEngine(delay_s=0.15)
    front = ServeFront(eng, k=3, max_batch=1).start()
    tickets = [front.submit(_req(u)) for u in range(4)]
    front.stop(drain=False)
    outs = [t.result(timeout=10.0) for t in tickets]
    # the in-flight batch completes; the rest fail fast with a reason
    assert any("error" in o for o in outs)
    assert all(o.get("error", "stopped") == "stopped" for o in outs)
    assert front.admission.depth == 0
    with pytest.raises(Rejected):
        front.submit(_req(9))


def test_front_worker_survives_engine_error():
    class Boom(_StubEngine):
        def query(self, qs, k, g):
            if int(np.asarray(qs)[0, 0]) == 7:
                raise RuntimeError("kaboom")
            return super().query(qs, k, g)

    eng = Boom()
    c = obs.REGISTRY.counter("serve.loop.errors", lane="epsilon")
    c.mark()
    with ServeFront(eng, k=3, max_batch=1) as front:
        bad = front.submit(_req(7)).result(timeout=10.0)
        good = front.submit(_req(1)).result(timeout=10.0)
    assert "kaboom" in bad["error"]
    assert np.array_equal(good["ids"], 10 + np.arange(3))
    assert c.since_mark == 1
    assert front.admission.depth == 0


def test_front_no_series_request_completes():
    with ServeFront(_StubEngine(), k=3) as front:
        out = front.submit(Request(
            uid=0, prompt=np.zeros(2, np.int32))).result(timeout=10.0)
    assert out["ids"] is None and out["kind"] == "exact"
    assert out["retrieval_ms"] == 0.0


# --------------------------------------------------------------- stress
@pytest.fixture(scope="module")
def corpus():
    rng = np.random.default_rng(0)
    data = np.cumsum(rng.normal(size=(N, DIM)), axis=1)
    data = ((data - data.mean(1, keepdims=True))
            / (data.std(1, keepdims=True) + 1e-9)).astype(np.float32)
    queries = (data[rng.choice(N, 16, replace=False)]
               + 0.05 * rng.normal(size=(16, DIM))).astype(np.float32)
    return data, queries


@pytest.fixture(scope="module")
def spilled_engine(tmp_path_factory, corpus):
    data, _ = corpus
    tmp = str(tmp_path_factory.mktemp("serve_loop_spill"))
    eng = DistributedEngine(mesh=None, method="dstree", shards=SHARDS)
    eng.build(data, index=IndexSpec("dstree", leaf_cap=16),
              store=StoreSpec(spill_dir=tmp, codec="f32",
                              keep_resident=False))
    yield eng
    eng.close()


def test_concurrent_queries_bit_exact_vs_serial(spilled_engine, corpus):
    """The tentpole's re-entrancy contract, engine-level: many
    concurrent query() calls (mixed guarantees, shared warm caches)
    return EXACTLY what serial execution returns — ids and dists."""
    _, queries = corpus
    eng = spilled_engine
    plans = [(jnp.asarray(queries[i:i + 4]), g)
             for i, g in [(0, Guarantee()),
                          (4, Guarantee(epsilon=1.0)),
                          (8, Guarantee(delta=0.99, epsilon=1.0)),
                          (12, Guarantee(nprobe=8)),
                          (2, Guarantee()),
                          (6, Guarantee(nprobe=4))]]
    serial = [eng.query(q, K, g) for q, g in plans]
    for rounds in range(3):  # repeat: interleavings differ per run
        results = [None] * len(plans)
        errs = []

        def worker(i, q, g):
            try:
                results[i] = eng.query(q, K, g)
            except Exception as e:  # noqa: BLE001 — surface thread failures to the main thread's assert instead of dying silently
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(i, q, g))
              for i, (q, g) in enumerate(plans)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs, errs
        for i, res in enumerate(results):
            assert np.array_equal(np.asarray(res.ids),
                                  np.asarray(serial[i].ids)), i
            assert np.array_equal(np.asarray(res.dists),
                                  np.asarray(serial[i].dists)), i
            # stats rode the result, one schema per shard
            assert res.stats is not None
            assert len(res.stats.shards) == SHARDS


def test_front_stress_bit_exact_no_drops_lockorder(corpus,
                                                   tmp_path_factory):
    """The full stack under fire: 4 submitter threads x 24 requests
    through the lanes over a spilled 4-shard store, with the front
    cond, the engine's per-copy locks, the OOC bookkeeping lock, and
    every shard cache/prefetcher lock wrapped in ONE lockorder
    recorder. Every answer must be bit-exact vs the serial oracle for
    its tier; no uid dropped or answered twice; the observed lock
    graph acyclic."""
    data, queries = corpus
    tmp = str(tmp_path_factory.mktemp("stress_spill"))
    eng = DistributedEngine(mesh=None, method="dstree", shards=SHARDS)
    eng.build(data, index=IndexSpec("dstree", leaf_cap=16),
              store=StoreSpec(spill_dir=tmp, codec="f32",
                              keep_resident=False))
    rec = obs.LockOrderRecorder()
    try:
        # no-deadline requests only: every answer is the exact tier,
        # so the serial oracle is ONE engine call per query row
        n_sub, per = 4, 6
        serial = eng.query(jnp.asarray(queries), K, Guarantee())
        s_ids, s_dists = np.asarray(serial.ids), np.asarray(serial.dists)

        # wrap the whole lock surface AFTER the serial warmup built
        # the caches/prefetchers
        eng._ooc_lock = rec.wrap(eng._ooc_lock, "engine._ooc_lock")
        for d in list(eng._copy_locks):
            eng._copy_locks[d] = rec.wrap(eng._copy_locks[d],
                                          f"engine.copy:{d[-8:]}")
        for d, cache in eng._shard_caches.items():
            cache._lock = rec.wrap(cache._lock, f"cache:{d[-8:]}")
            if cache.prefetcher is not None:
                cache.prefetcher._lock = rec.wrap(
                    cache.prefetcher._lock, f"prefetch:{d[-8:]}")

        front = ServeFront(
            eng, K, max_batch=4,
            admission=AdmissionController(max_depth=64),
            lock_recorder=rec).start()
        answers: dict = {}
        answers_lock = threading.Lock()
        errs: list = []

        def submitter(s):
            try:
                tickets = []
                for j in range(per):
                    uid = s * 100 + j
                    qi = (s * per + j) % len(queries)
                    tickets.append((uid, qi, front.submit(Request(
                        uid=uid, prompt=np.zeros(2, np.int32),
                        series=queries[qi]))))
                for uid, qi, t in tickets:
                    out = t.result(timeout=120.0)
                    with answers_lock:
                        assert uid not in answers, f"dup {uid}"
                        answers[uid] = (qi, out)
            except Exception as e:  # noqa: BLE001 — surface thread failures to the main thread's assert instead of dying silently
                errs.append(e)

        subs = [threading.Thread(target=submitter, args=(s,))
                for s in range(n_sub)]
        for t in subs:
            t.start()
        for t in subs:
            t.join()
        front.stop()
        assert not errs, errs
        assert len(answers) == n_sub * per, "dropped uids"
        for uid, (qi, out) in answers.items():
            assert "error" not in out, out
            assert out["kind"] == "exact"
            assert np.array_equal(out["ids"], s_ids[qi]), uid
            assert np.array_equal(out["dists"], s_dists[qi]), uid
        rec.assert_acyclic()
        assert rec.edges(), "recorder saw no lock activity"
    finally:
        eng.close()


# ------------------------------------------------- launch integration
def test_serve_requests_continuous_end_to_end():
    """launch/serve.serve_requests_continuous: decode batches overlap
    continuous retrieval, ticket results merge back per uid, a
    no-series request decodes without a retrieval entry, and an
    admission-rejected request still decodes and surfaces the
    reason."""
    import jax

    from repro.configs import get_smoke_config
    from repro.launch.serve import serve_requests_continuous
    from repro.models import model as M
    from repro.models.params import initialize

    cfg = get_smoke_config("gemma2-2b")
    params = initialize(M.model_specs(cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def mk(uid, dl, series):
        return Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, size=6
                                ).astype(np.int32),
            max_new_tokens=3, deadline_ms=dl, series=series)

    reqs = [mk(0, None, np.full(8, 0, np.float32)),
            mk(1, 30.0, np.full(8, 1, np.float32)),
            mk(2, None, None),                      # decode-only
            mk(3, 5.0, np.full(8, 3, np.float32))]
    out = serve_requests_continuous(params, cfg, reqs,
                                    engine=_StubEngine(),
                                    retrieval_k=3, max_batch=2)
    assert sorted(out) == [0, 1, 2, 3]
    for r in out.values():
        assert r["tokens"].shape == (3,)
        assert r["latency_ms"] >= r["queue_wait_ms"] >= 0.0
    assert np.array_equal(out[0]["retrieval"]["ids"], np.arange(3))
    assert out[0]["retrieval"]["nominal_kind"] == "exact"
    assert "retrieval" not in out[2] and out[2]["guarantee"] == "exact"
    assert out[3]["retrieval"]["kind"] == "ng"
    assert out[1]["guarantee"] == out[1]["retrieval"]["kind"]
    assert "deadline_hit" in out[1] and "deadline_hit" in out[3]

    # past the admission cap the request still DECODES; the entry
    # carries the reject reason instead of a retrieval block (the
    # stalled stub keeps the first request in-system so the second
    # submit deterministically hits the cap)
    reqs2 = [mk(10, None, np.full(8, 10, np.float32)),
             mk(11, None, np.full(8, 11, np.float32))]
    out2 = serve_requests_continuous(
        params, cfg, reqs2, engine=_StubEngine(delay_s=0.3),
        retrieval_k=3, max_batch=1,
        admission=AdmissionController(max_depth=1))
    assert out2[11]["retrieval_rejected"] == "queue_full"
    assert out2[11]["tokens"].shape == (3,)
    assert np.array_equal(out2[10]["retrieval"]["ids"],
                          100 + np.arange(3))
