"""repro.analysis: the static-analysis pass keeps its teeth.

Three layers of coverage, same philosophy as the PR 5 interpret
registry (a gate nobody exercises is a gate that silently rots):

  fixtures   every registered rule has a POSITIVE snippet its check
             must flag and a NEGATIVE snippet it must not — the
             near-miss shape that separates detection from pattern-
             matching on spelling.
  meta       the fixture table is asserted against the live rule
             registry, so registering a rule without fixtures fails
             here, not in review.
  self-run   ``src/`` is clean modulo the recorded allows, and the
             known while-in-shard_map engine site is DETECTED then
             suppressed (proving cross-module detection on real
             code, not just on fixtures).
"""

import os
import textwrap

import pytest

from repro.analysis import Project, all_rules, run

pytestmark = pytest.mark.tier1

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def _fix(src: str) -> str:
    return textwrap.dedent(src).lstrip()


# rule-id -> {positive: {path: src}, negative: {path: src}} — paths
# are virtual but repo-shaped so path-scoped rules behave as on disk
FIXTURES = {
    "guarded-by": {
        "positive": {"repro/fx/guard_pos.py": _fix("""
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0  # guarded_by: _lock

                def bump(self):
                    self._n += 1
            """)},
        "negative": {"repro/fx/guard_neg.py": _fix("""
            import threading

            class Box:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._n = 0  # guarded_by: _lock
                    self._free = 0

                def bump(self):
                    with self._lock:
                        self._n += 1
                    self._free += 1
            """)},
    },
    "clock-discipline": {
        "positive": {"repro/fx/clock_pos.py": _fix("""
            import time

            def elapsed(t0):
                return time.monotonic() - t0
            """)},
        "negative": {"repro/fx/clock_neg.py": _fix("""
            import time

            from repro import obs

            def elapsed(t0):
                time.sleep(0.0)  # sleep is not a clock READ
                return obs.now() - t0
            """)},
    },
    "jax-while-shard-map": {
        # the hard shape: the while_loop is NOT lexical in the closure
        # — it hides one call away, exactly like engine.py ->
        # core/search.search_impl
        "positive": {
            "repro/fx/wsm_search.py": _fix("""
                import jax

                def refine(state):
                    return jax.lax.while_loop(
                        lambda c: c < 3, lambda c: c + 1, state)
                """),
            "repro/fx/wsm_engine.py": _fix("""
                import jax
                from repro.fx.wsm_search import refine

                def local(q):
                    return refine(q)

                fn = jax.shard_map(local, mesh=None, in_specs=(),
                                   out_specs=())
                """),
        },
        "negative": {
            "repro/fx/wsm_neg.py": _fix("""
                import jax

                def refine(state):
                    # while_loop OUTSIDE any shard_map closure: legal
                    return jax.lax.while_loop(
                        lambda c: c < 3, lambda c: c + 1, state)

                def local(q):
                    return q * 2

                fn = jax.shard_map(local, mesh=None, in_specs=(),
                                   out_specs=())
                """),
        },
    },
    "jax-topk-on-topk": {
        "positive": {"repro/fx/tot_pos.py": _fix("""
            import jax

            def select(dists, kk):
                neg, _ = jax.lax.top_k(-dists, kk)
                thr = -neg[:, -1:]
                _, pos = jax.lax.top_k(dists * thr, kk)
                return pos
            """)},
        "negative": {"repro/fx/tot_neg.py": _fix("""
            import jax
            import jax.numpy as jnp

            def select(dists, ids, kk):
                # argsort-permute + ONE top_k: the shared-pool idiom
                order = jnp.argsort(ids)
                neg, pos = jax.lax.top_k(-dists[:, order], kk)
                return -neg, pos
            """)},
    },
    "jax-int32-topk": {
        "positive": {"repro/fx/i32_pos.py": _fix("""
            import jax
            import jax.numpy as jnp

            def pick(ids, kk):
                keys = ids.astype(jnp.int32)
                return jax.lax.top_k(keys, kk)
            """)},
        "negative": {"repro/fx/i32_neg.py": _fix("""
            import jax
            import jax.numpy as jnp

            def pick(ids, kk):
                keys = ids.astype(jnp.float32)
                return jax.lax.top_k(keys, kk)
            """)},
    },
    "jax-host-sync-in-jit": {
        "positive": {"repro/fx/sync_pos.py": _fix("""
            import jax
            import numpy as np

            @jax.jit
            def step(x):
                y = x + 1
                host = np.asarray(y)
                return host.sum(), y[0].item()
            """)},
        "negative": {"repro/fx/sync_neg.py": _fix("""
            import jax
            import numpy as np

            TABLE = [1, 2, 3]

            @jax.jit
            def step(x):
                # np on STATIC module data at trace time is fine
                lut = np.asarray(TABLE)
                return x + lut.sum()

            def host_side(x):
                return np.asarray(x)  # not a jitted body
            """)},
    },
    "broad-except": {
        # reasonless broad handlers in all three spellings — including
        # a BARE noqa, which silences a linter without explaining the
        # boundary
        "positive": {"repro/fx/be_pos.py": _fix("""
            def risky():
                try:
                    return 1
                except Exception:
                    return None

            def risky2():
                try:
                    return 1
                except (ValueError, BaseException):  # noqa: BLE001
                    return None

            def risky3():
                try:
                    return 1
                except:
                    return None
            """)},
        "negative": {"repro/fx/be_neg.py": _fix("""
            def narrow():
                try:
                    return 1
                except ValueError:  # narrow handlers need no reason
                    return None

            def boundary():
                try:
                    return 1
                except Exception:  # supervisor restart boundary: any step fault must restart, not crash
                    return None

            def linted():
                try:
                    return 1
                except Exception:  # noqa: BLE001 — reason after the directive counts
                    return None
            """)},
    },
    "guarantee-kwargs": {
        # entry-point call with a loose guarantee kwarg fires; the
        # near-miss is the internal unpacked layer (search_impl) and
        # the typed spelling on a real entry point — both clean
        "positive": {"repro/fx/gkw_pos.py": _fix("""
            from repro.core import search as S

            def lookup(idx, q, store):
                a = S.search(idx, q, 5, epsilon=1.0)
                b = S.search_ooc(store, q, 5, delta=0.99,
                                 epsilon=0.5, cache_leaves=6)
                return a, b

            def served(engine, q):
                return engine.query(q, 5, nprobe=16)
            """)},
        "negative": {"repro/fx/gkw_neg.py": _fix("""
            from repro.core import guarantees as G
            from repro.core import search as S
            from repro.core.search import search_impl

            def lookup(idx, q, store):
                a = S.search(idx, q, 5, G.epsilon(1.0))
                b = S.search_ooc(store, q, 5,
                                 G.delta_epsilon(0.99, 0.5),
                                 cache_leaves=6)
                return a, b

            def internal(idx, q):
                # the unpacked layer legitimately takes the scalars
                return search_impl(idx, q, 5, delta=0.99,
                                   epsilon=1.0, nprobe=0)

            def served(engine, q):
                return engine.query(q, 5, G.ng(16))
            """)},
    },
    "engine-stats": {
        "positive": {"repro/fx/engstat_pos.py": _fix("""
            def degraded(engine, res):
                a = engine.last_ooc_stats
                b = getattr(engine, "last_ooc_stats", None)
                return a, b
            """)},
        "negative": {"repro/fx/engstat_neg.py": _fix("""
            def degraded(res):
                stats = getattr(res, "stats", None)
                return stats is not None and stats.degraded
            """)},
    },
    "stats-schema": {
        "positive": {"repro/fx/stats_pos.py": _fix("""
            def report(a, b, c):
                return {"bytes_read": a, "hits": b, "misses": c}
            """)},
        "negative": {"repro/fx/stats_neg.py": _fix("""
            def report(a, b):
                # < 3 schema fields: incidental overlap, not a stats
                # surface
                return {"bytes_read": a, "hits": b, "latency": 0.0}
            """)},
    },
}


# ------------------------------------------------------------- meta test
def test_every_rule_has_positive_and_negative_fixtures():
    """Registering a rule without fixture coverage fails HERE (the
    interpret-registry idiom: the meta test is what gives the fixture
    table teeth)."""
    assert set(FIXTURES) == set(all_rules())
    for rid, fx in FIXTURES.items():
        assert fx["positive"] and fx["negative"], rid


@pytest.mark.parametrize("rid", sorted(FIXTURES))
def test_positive_fixture_fires(rid):
    report = run(Project.from_sources(FIXTURES[rid]["positive"]), [rid])
    assert report.findings, f"{rid}: positive fixture produced nothing"
    assert all(f.rule == rid for f in report.findings)


@pytest.mark.parametrize("rid", sorted(FIXTURES))
def test_negative_fixture_is_clean(rid):
    report = run(Project.from_sources(FIXTURES[rid]["negative"]), [rid])
    assert report.ok, [f.format() for f in report.findings]


# --------------------------------------------------------- suppressions
def _guard_pos_with_allow(reason: str) -> dict:
    src = FIXTURES["guarded-by"]["positive"]["repro/fx/guard_pos.py"]
    return {"repro/fx/guard_pos.py": src.replace(
        "self._n += 1\n",
        f"self._n += 1  # repro: allow[guarded-by] {reason}\n")}


def test_allow_with_reason_suppresses():
    report = run(Project.from_sources(_guard_pos_with_allow(
        "fixture: lock-free by design")), ["guarded-by"])
    assert report.ok
    assert len(report.suppressed) == 1
    finding, allow = report.suppressed[0]
    assert finding.rule == "guarded-by"
    assert allow.reason == "fixture: lock-free by design"


def test_allow_without_reason_is_an_error():
    report = run(Project.from_sources(_guard_pos_with_allow("")),
                 ["guarded-by"])
    assert [f.rule for f in report.findings] == ["allow-hygiene"]
    assert "without a reason" in report.findings[0].message


def test_unused_allow_is_an_error():
    report = run(Project.from_sources({"repro/fx/clean.py": _fix("""
        # repro: allow[guarded-by] nothing here needs this
        X = 1
        """)}), ["guarded-by"])
    assert [f.rule for f in report.findings] == ["allow-hygiene"]
    assert "unused" in report.findings[0].message


def test_allow_naming_unknown_rule_is_an_error():
    report = run(Project.from_sources({"repro/fx/typo.py": _fix("""
        X = 1  # repro: allow[guarded-bye] typo'd rule id
        """)}), ["guarded-by"])
    assert [f.rule for f in report.findings] == ["allow-hygiene"]
    assert "unknown rule" in report.findings[0].message


def test_allow_above_statement_covers_next_code_line():
    src = FIXTURES["guarded-by"]["positive"]["repro/fx/guard_pos.py"]
    src = src.replace(
        "        self._n += 1\n",
        "        # repro: allow[guarded-by] fixture: comment-above "
        "placement\n        self._n += 1\n")
    report = run(Project.from_sources({"repro/fx/g.py": src}),
                 ["guarded-by"])
    assert report.ok and len(report.suppressed) == 1


# ------------------------------------------------------------- self-run
@pytest.fixture(scope="module")
def src_report():
    return run(Project.from_paths([SRC]))


def test_src_is_clean_modulo_recorded_allows(src_report):
    assert src_report.ok, "\n".join(
        f.format() for f in src_report.findings)


def test_at_least_six_active_rules(src_report):
    assert len(src_report.rules_run) >= 6


def test_engine_shard_map_site_detected_then_suppressed(src_report):
    """The 0.4.37 while-in-shard_map engine site must be FOUND (the
    rule sees through engine.local -> search_impl) and then allowed
    with a reason — detection proven on real code."""
    hits = [(f, al) for f, al in src_report.suppressed
            if f.rule == "jax-while-shard-map"
            and f.path.endswith("core/engine.py")]
    assert hits, "engine.py shard_map site no longer detected"
    assert all(al.reason for _, al in hits)


def test_clock_rule_scoping_on_real_tree(src_report):
    """repro/obs/trace.py defines obs.now via time.perf_counter —
    exempt; no clock finding may point into repro/obs/."""
    for f, _ in src_report.suppressed:
        if f.rule == "clock-discipline":
            assert "/obs/" not in f.path
