"""Bring-up smoke test: the retrieval engine's main path on a TPU.

    python chip_smoke.py              # one chip: phases A, B, C
    python chip_smoke.py --chips 4    # four chips: the sharded resident
                                      # engine only

One chip. The paper's synthetic collection (z-normalized random walks
of length 256, ``data/randomwalk.generate``) at 4,194,304 series (4 GiB
of f32) with 32 noisy queries of graded hardness
(``data/queries.noisy_queries``), all made from a seed. Every answer is
checked against a float64 NumPy brute force on the host that shares no
code with ``repro``:

  A  resident    ``DistributedEngine.build`` on a one-chip mesh with
                 ``IndexSpec("dstree")``; the exact, epsilon (1),
                 delta-epsilon (0.99, 1) and ng lanes at k=10, and
                 exact again at k=100.
  B  spilled     ``StoreSpec(spill_dir, keep_resident=False)`` with the
                 f32 and then the pq codec, a leaf cache of 1/8 of the
                 leaves, every guarantee lane served through
                 ``ServeFront`` with ``share_gathers`` off and on.
  C  writes      ``enable_writes()`` on the f32 spilled engine, 1,024
                 inserts through the front's write lane; exact copies
                 of inserted rows, asked on the ng lane, come back at
                 distance 0 under their ids; deleted ids are gone.

Four chips (``--chips 4``). The resident shard_map engine on a
``("data",)`` mesh over 16,777,216 series (16 GiB, 4 GiB per chip):
exact and epsilon lanes against the host reference, one shard per
chip, plus a report of where the leaf caches of a mesh-free
(``mesh=None, shards=4``) spilled engine are placed.

The script exits non-zero, printing no result, when JAX sees no TPU.
Any failed check, serving-lane error, failed shard attempt, degraded
answer or dead prefetcher also makes it exit non-zero. On success the
last line of standard output is one JSON object naming the device.
Times, compile seconds and peak device bytes printed along the way are
set-up information, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
import traceback
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0
SERIES_LEN = 256
N_ONE_CHIP = 4_194_304
N_FOUR_CHIPS = 16_777_216
N_QUERIES = 32
K = 10
K_BIG = 100
EPS = 1.0
DELTA = 0.99
NPROBE = 16
N_INSERT = 1024
SPILL_DIR = os.path.join(REPO, ".chip_smoke_spill")
# squared-distance tolerance of the reference comparisons: the engine
# accumulates in f32 over rows with |x|^2 = 256, so its squared
# distances carry ~1e-4 of rounding; 1e-2 stays ~100x below the error
# a bf16 matmul pass would leave
TOL_SQ = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


# ------------------------------------------------------------ checks
class Checks:
    """Named pass/fail records; the run fails if any record failed."""

    def __init__(self):
        self.failed = []
        self.passed = 0

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        if ok:
            self.passed += 1
        else:
            self.failed.append(name)
        log(f"  {'ok  ' if ok else 'FAIL'} {name}"
            + (f"  ({detail})" if detail else ""))
        return ok

    def phase(self, name: str, fn, *a, **kw):
        """Run one phase; an exception fails it and the run goes on
        to the next phase so one run reports every fault."""
        log(f"== {name}")
        t0 = time.perf_counter()
        try:
            fn(*a, **kw)
        except Exception:  # noqa: BLE001 reported and counted as a failed phase
            traceback.print_exc()
            self.check(f"{name}: ran to its end", False, "exception")
        log(f"   {name}: {time.perf_counter() - t0:.1f} s wall, "
            f"{COMPILE.take():.1f} s compiling, peak device bytes "
            f"{peak_bytes()}")


class _CompileClock:
    """Sums XLA backend-compile seconds reported by jax.monitoring."""

    def __init__(self):
        self.total = 0.0
        self._last = 0.0

    def listen(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += duration

    def take(self) -> float:
        out, self._last = self.total - self._last, self.total
        return out


COMPILE = _CompileClock()


def peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


# --------------------------------------------------------------- data
def make_collection(n: int) -> np.ndarray:
    """Rows [0, n) of the seeded random-walk collection, generated in
    BLOCK-aligned slices on host threads (``generate`` is slice-
    invariant, so the rows equal one ``generate(SEED, n)`` call)."""
    from repro.data import randomwalk

    out = np.empty((n, SERIES_LEN), np.float32)
    step = 64 * randomwalk.BLOCK

    def fill(lo):
        hi = min(lo + step, n)
        out[lo:hi] = randomwalk.generate(SEED, hi - lo, SERIES_LEN,
                                         start=lo)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as ex:
        list(ex.map(fill, range(0, n, step)))
    return out


class Reference:
    """Float64 brute force on the host: exact squared distances of the
    queries to every row, top ``kmax`` per query (ties by id).
    Independent of repro's kernels and search code."""

    CHUNK = 1 << 17

    def __init__(self, data: np.ndarray, queries: np.ndarray, kmax: int):
        self.data = data
        self.q = queries.astype(np.float64)
        qn = np.einsum("bn,bn->b", self.q, self.q)

        def part(lo):
            x = data[lo:lo + self.CHUNK].astype(np.float64)
            d2 = qn[:, None] - 2.0 * (self.q @ x.T) \
                + np.einsum("mn,mn->m", x, x)[None, :]
            kk = min(kmax, d2.shape[1])
            sel = np.argpartition(d2, kk - 1, axis=1)[:, :kk]
            return np.take_along_axis(d2, sel, 1), sel + lo

        with ThreadPoolExecutor(max_workers=os.cpu_count() or 4) as ex:
            parts = list(ex.map(part, range(0, data.shape[0],
                                            self.CHUNK)))
        d2 = np.concatenate([p[0] for p in parts], axis=1)
        ii = np.concatenate([p[1] for p in parts], axis=1)
        order = np.lexsort((ii, d2), axis=1)[:, :kmax]
        self.d2 = np.maximum(np.take_along_axis(d2, order, 1), 0.0)
        self.ids = np.take_along_axis(ii, order, 1)

    def true_sq(self, ids: np.ndarray) -> np.ndarray:
        """Exact squared distances of each query to the rows of its
        line of ``ids`` (direct differences, float64)."""
        diff = self.data[ids].astype(np.float64) - self.q[:, None, :]
        return np.einsum("...n,...n->...", diff, diff)


def answer_issues(ids: np.ndarray, dists: np.ndarray, k: int,
                  n_rows: int) -> list:
    """Shape, range, distinctness and finiteness problems."""
    out = []
    if ids.shape != (ids.shape[0], k) or dists.shape != ids.shape:
        out.append(f"shape {ids.shape}/{dists.shape}")
        return out
    if not np.isfinite(dists).all():
        out.append("non-finite distances")
    if ((ids < 0) | (ids >= n_rows)).any():
        out.append("ids out of range")
    if any(len(set(r.tolist())) != k for r in ids):
        out.append("repeated ids")
    if (np.diff(dists, axis=1) < 0).any():
        out.append("distances not ascending")
    return out


def grade(ref: Reference, ids, dists, k: int, kind: str) -> dict:
    """Compare one answer block (a row per query) with the reference.
    Returns the measurements and the list of violations of ``kind``'s
    guarantee: exact, the k-NN set up to ties within TOL_SQ; epsilon,
    every returned distance <= (1+EPS) x the true k-th; delta-epsilon
    and ng report attainment and recall and need a valid answer."""
    ids = np.asarray(ids)
    dists = np.asarray(dists, np.float64)
    bad = answer_issues(ids, dists, k, ref.data.shape[0])
    out = {"bad": bad}
    if bad:
        return out
    d_ref, i_ref = ref.d2[:, :k], ref.ids[:, :k]
    tsq = ref.true_sq(ids)
    out["recall"] = float(np.mean([
        len(set(a.tolist()) & set(b.tolist())) / k
        for a, b in zip(ids, i_ref)]))
    out["max_report_err_sq"] = float(np.abs(dists ** 2 - tsq).max())
    if out["max_report_err_sq"] > TOL_SQ:
        bad.append("reported distances differ from the true ones by "
                   f"{out['max_report_err_sq']:.3g} (squared)")
    eps_ok = (tsq <= (1 + EPS) ** 2 * d_ref[:, k - 1:]
              + TOL_SQ).all(axis=1)
    out["eps_attainment"] = float(eps_ok.mean())
    if kind == "exact":
        out["max_gap_sq"] = float(np.abs(np.sort(tsq, axis=1)
                                         - d_ref).max())
        out["id_mismatches"] = int(sum(
            len(set(a.tolist()) - set(b.tolist()))
            for a, b in zip(ids, i_ref)))
        if out["max_gap_sq"] > TOL_SQ:
            bad.append(f"not the exact k-NN: squared gap "
                       f"{out['max_gap_sq']:.3g}, "
                       f"{out['id_mismatches']} ids differ")
    elif kind == "epsilon" and not eps_ok.all():
        bad.append(f"epsilon bound broken on {int((~eps_ok).sum())} "
                   "queries")
    return out


def report(checks: Checks, name: str, g: dict) -> None:
    keys = ("recall", "eps_attainment", "id_mismatches", "max_gap_sq",
            "max_report_err_sq")
    detail = ", ".join(f"{k}={g[k]:.6g}" if isinstance(g[k], float)
                       else f"{k}={g[k]}" for k in keys if k in g)
    checks.check(name, not g["bad"],
                 "; ".join(g["bad"]) if g["bad"] else detail)


# ------------------------------------------------------- one chip: A
def phase_resident(checks, data, queries, ref, mesh):
    import jax

    from repro.core import IndexSpec
    from repro.core.engine import DistributedEngine
    from repro.core.guarantees import Guarantee

    n = data.shape[0]
    t0 = time.perf_counter()
    eng = DistributedEngine(mesh, axes=("data",))
    eng.build(data, index=IndexSpec("dstree"))
    jax.block_until_ready(eng.stacked.data)
    log(f"   build: {time.perf_counter() - t0:.1f} s, "
        f"{eng.stacked.num_leaves} leaves")
    lanes = [("exact", Guarantee(), K),
             ("epsilon", Guarantee(epsilon=EPS), K),
             ("delta-epsilon", Guarantee(delta=DELTA, epsilon=EPS), K),
             ("ng", Guarantee(nprobe=NPROBE), K),
             ("exact", Guarantee(), K_BIG)]
    for kind, g, k in lanes:
        t0, c0 = time.perf_counter(), COMPILE.total
        res = eng.query(queries, k, g)
        jax.block_until_ready(res.dists)
        leaves = np.asarray(res.leaves_visited)
        log(f"   {kind} k={k}: {time.perf_counter() - t0:.2f} s "
            f"({COMPILE.total - c0:.2f} s compiling), leaves visited "
            f"mean {leaves.mean():.1f} max {leaves.max()}")
        checks.check(f"A {kind} k={k}: no degradation block",
                     res.stats is None or not res.stats.degraded)
        report(checks, f"A {kind} k={k} vs float64 reference",
               grade(ref, res.ids, res.dists, k, kind))
    eng.close()


# ---------------------------------------------------- one chip: B, C
def _front(eng, share: bool, epsilon: float, n_req: int):
    from repro.serve.admission import AdmissionController
    from repro.serve.loop import ServeFront

    # deadlines in the default 50 ms budget would remap with queue
    # wait; a budget far above any wait keeps each request's lane
    # fixed, and an admission cap above the request count never sheds
    return ServeFront(
        eng, k=K, max_batch=N_QUERIES,
        admission=AdmissionController(max_depth=2 * n_req,
                                      shed_high_frac=1.0,
                                      shed_low_frac=1.0),
        guarantee_kw={"full_budget_ms": 1e9, "epsilon": epsilon},
        ooc_opts={"share_gathers": share})


# deadline (ms, under full_budget_ms=1e9) of each served lane
LANE_DEADLINE = {"exact": None, "epsilon": None,
                 "delta-epsilon": 0.75e9, "ng": 0.125e9}


def serve_lanes(eng, queries, share: bool, kinds) -> dict:
    """Submit every query on each lane in ``kinds`` through one
    ServeFront and return {kind: [ticket entries in query order]}."""
    from repro.serve.batching import Request

    eps = EPS if "epsilon" in kinds else 0.0
    n_req = len(kinds) * queries.shape[0]
    out = {}
    with _front(eng, share, eps, n_req) as front:
        tickets = {}
        uid = 0
        for kind in kinds:
            tickets[kind] = []
            for q in queries:
                tickets[kind].append(front.submit(Request(
                    uid=uid, prompt=np.zeros(1, np.int32),
                    deadline_ms=LANE_DEADLINE[kind], series=q)))
                uid += 1
        for kind in kinds:
            out[kind] = [t.result(timeout=1500.0) for t in tickets[kind]]
    return out


def check_entries(checks, label, kind, entries, ref, graded_as):
    """Every ticket answered without error, on the lane asked for, not
    shed, not degraded; then grade the answers as ``graded_as``."""
    errors = [e["error"] for e in entries if "error" in e]
    if not checks.check(f"{label}: every ticket answered",
                        not errors, errors[0] if errors else ""):
        return
    kinds = {e["kind"] for e in entries}
    checks.check(f"{label}: served as {kind}, none shed or degraded",
                 kinds == {kind} and not any(
                     e.get("shed") or e.get("degraded") or
                     (e["stats"] is not None and e["stats"].degraded)
                     for e in entries), f"kinds {sorted(kinds)}")
    ids = np.stack([e["ids"] for e in entries])
    dists = np.stack([e["dists"] for e in entries])
    st = entries[0]["stats"]
    if st is not None:
        log(f"   {label}: iterations {st.iterations}, leaves visited "
            f"{st.leaves_visited}, bytes read {st.bytes_read} of "
            f"{st.dataset_bytes}, retrieval "
            f"{entries[0]['retrieval_ms']:.0f} ms")
    report(checks, f"{label} vs float64 reference",
           grade(ref, ids, dists, K, graded_as))


def phase_spilled(checks, data, queries, ref, codec, writes=False):
    from repro.core import IndexSpec, StoreSpec
    from repro.core.engine import DistributedEngine

    spill = os.path.join(SPILL_DIR, codec)
    shutil.rmtree(spill, ignore_errors=True)
    t0 = time.perf_counter()
    eng = DistributedEngine(mesh=None, shards=1)
    eng.build(data, index=IndexSpec("dstree"),
              store=StoreSpec(spill_dir=spill, codec=codec,
                              keep_resident=False))
    log(f"   build + spill ({codec}): {time.perf_counter() - t0:.1f} s")
    try:
        for share in (False, True):
            label = f"B {codec} share_gathers={share}"
            t0 = time.perf_counter()
            with warnings.catch_warnings():
                # pq cannot honor exact; the lane still runs and is
                # graded on recall only (search_ooc's warning)
                warnings.simplefilter("ignore", UserWarning)
                got = serve_lanes(eng, queries, share,
                                  ("exact", "delta-epsilon", "ng"))
                got.update(serve_lanes(eng, queries, share,
                                       ("epsilon",)))
            log(f"   {label}: {time.perf_counter() - t0:.1f} s")
            for kind in ("exact", "epsilon", "delta-epsilon", "ng"):
                # the pq re-rank makes distances exact but not the
                # visit set: its exact lane is graded like ng
                gk = "ng" if (codec == "pq" and kind == "exact") else kind
                check_entries(checks, f"{label} {kind}", kind, got[kind],
                              ref, gk)
        if writes:
            phase_writes(checks, eng, data.shape[0])
    finally:
        eng.close()
        shutil.rmtree(spill, ignore_errors=True)


def phase_writes(checks, eng, n_rows):
    from repro.data import randomwalk
    from repro.serve.batching import Request

    rows = randomwalk.generate(SEED, N_INSERT, SERIES_LEN, start=n_rows)
    eng.enable_writes()
    probe = np.arange(0, N_INSERT, N_INSERT // N_QUERIES)[:N_QUERIES]
    with _front(eng, False, 0.0, 2 * N_QUERIES) as front:
        ins = front.submit_write("insert", rows=rows).result(timeout=600)
        if not checks.check("C insert through the write lane",
                            "error" not in ins, ins.get("error", "")):
            return
        new_ids = np.asarray(ins["ids"])
        checks.check("C inserted ids follow the collection",
                     np.array_equal(new_ids,
                                    np.arange(n_rows, n_rows + N_INSERT)))

        # probes ride the ng lane: the memtable is brute-scored on
        # every lane, so the lane only sets how much of the frozen
        # store is searched alongside it (the exact lane costs two
        # more full exact batches over the spilled collection)
        def ask(uid0):
            ts = [front.submit(Request(uid=uid0 + i,
                                       prompt=np.zeros(1, np.int32),
                                       deadline_ms=LANE_DEADLINE["ng"],
                                       series=rows[j]))
                  for i, j in enumerate(probe)]
            return [t.result(timeout=1500.0) for t in ts]

        got = ask(0)
        errs = [e["error"] for e in got if "error" in e]
        if checks.check("C queries after insert answered", not errs,
                        errs[0] if errs else ""):
            top_id = np.array([e["ids"][0] for e in got])
            top_d = np.array([e["dists"][0] for e in got])
            checks.check(
                "C exact copy of an inserted row: its id at distance 0",
                np.array_equal(top_id, new_ids[probe])
                and (top_d ** 2 <= TOL_SQ).all(),
                f"max distance {top_d.max():.3g}")
        gone = new_ids[probe]
        dele = front.submit_write("delete", ids=gone).result(timeout=600)
        checks.check("C delete through the write lane",
                     "error" not in dele, dele.get("error", ""))
        got = ask(1000)
        errs = [e["error"] for e in got if "error" in e]
        if checks.check("C queries after delete answered", not errs,
                        errs[0] if errs else ""):
            seen = np.concatenate([e["ids"] for e in got])
            checks.check("C deleted ids are gone",
                         not np.isin(seen, gone).any())


# --------------------------------------------------- four chips
def phase_four_chips(checks, data, queries, ref, mesh):
    import jax

    from repro.core import IndexSpec, StoreSpec
    from repro.core.engine import DistributedEngine
    from repro.core.guarantees import Guarantee

    n = data.shape[0]
    t0 = time.perf_counter()
    eng = DistributedEngine(mesh, axes=("data",))
    eng.build(data, index=IndexSpec("dstree"))
    jax.block_until_ready(eng.stacked.data)
    log(f"   build: {time.perf_counter() - t0:.1f} s")
    for name in ("data", "ids", "row_norms", "box_lo", "offsets"):
        arr = getattr(eng.stacked, name)
        blocks = arr.addressable_shards
        devs = {s.device for s in blocks}
        checks.check(f"4 stacked.{name}: one shard per chip",
                     len(blocks) == 4 and len(devs) == 4
                     and all(4 * s.data.shape[0] == arr.shape[0]
                             for s in blocks),
                     f"blocks {[tuple(s.data.shape) for s in blocks]} on "
                     f"devices {sorted(d.id for d in devs)}")
    for kind, g in (("exact", Guarantee()),
                    ("epsilon", Guarantee(epsilon=EPS))):
        t0 = time.perf_counter()
        res = eng.query(queries, K, g)
        jax.block_until_ready(res.dists)
        log(f"   {kind}: {time.perf_counter() - t0:.2f} s, leaves "
            f"visited mean {np.asarray(res.leaves_visited).mean():.1f}")
        report(checks, f"4 {kind} k={K} vs float64 reference",
               grade(ref, res.ids, res.dists, K, kind))
    eng.close()
    del eng
    gc.collect()

    # finding, not a check: where a mesh-free spilled engine's
    # per-shard leaf caches are placed (one ng query opens them)
    small = 1 << 16
    spill = os.path.join(SPILL_DIR, "four")
    shutil.rmtree(spill, ignore_errors=True)
    eng = DistributedEngine(mesh=None, shards=4)
    try:
        eng.build(data[:small], index=IndexSpec("dstree"),
                  store=StoreSpec(spill_dir=spill, keep_resident=False))
        eng.query(queries, K, Guarantee(nprobe=2))
        placed = {os.path.basename(d): sorted(
                      str(dv) for dv in c.slots.devices())
                  for d, c in sorted(eng._shard_caches.items())}
        log(f"   finding: leaf caches of a mesh=None, shards=4 spilled "
            f"engine ({small} rows) live on {placed}")
    finally:
        eng.close()
        shutil.rmtree(spill, ignore_errors=True)


# ----------------------------------------------------------- driver
def serving_faults() -> dict:
    """Process-wide counts of the failures the serving stack absorbs
    on purpose: lane exceptions, failed shard attempts, degraded
    queries, dead prefetchers."""
    from repro import obs

    def total(name):
        return sum(c.value for c in obs.REGISTRY.collect(name))

    return {name: total(name) for name in (
        "serve.loop.errors", "fault.attempt_failed",
        "engine.degraded_queries", "store.prefetch.died")}


def run(chips: int, n: int) -> Checks:
    """Generate the collection and its reference, then run the phases
    of a ``chips``-chip run over ``n`` rows."""
    import jax
    from jax.sharding import Mesh

    from repro.data.queries import noisy_queries

    checks = Checks()
    t0 = time.perf_counter()
    data = make_collection(n)
    queries = noisy_queries(data, N_QUERIES)
    log(f"data: {n} x {SERIES_LEN} f32 random walks (seed {SEED}), "
        f"{N_QUERIES} noisy queries: {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    kmax = K if chips == 4 else K_BIG
    ref = Reference(data, queries, kmax)
    log(f"float64 reference (k={kmax}): {time.perf_counter() - t0:.1f} s")
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    try:
        if chips == 4:
            checks.phase("four chips: sharded resident engine",
                         phase_four_chips, checks, data, queries, ref,
                         mesh)
        else:
            checks.phase("A resident", phase_resident, checks, data,
                         queries, ref, mesh)
            gc.collect()
            checks.phase("B spilled f32, then C writes", phase_spilled,
                         checks, data, queries, ref, "f32", writes=True)
            checks.phase("B spilled pq", phase_spilled, checks, data,
                         queries, ref, "pq")
    finally:
        shutil.rmtree(SPILL_DIR, ignore_errors=True)
    faults = serving_faults()
    log(f"serving-stack fault counters: {faults}")
    for name, val in faults.items():
        checks.check(f"{name} == 0", val == 0, f"{val}")
    log(f"{checks.passed} checks passed, {len(checks.failed)} failed")
    return checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded resident engine over a "
                         "collection no single chip holds")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.runtime import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}; "
        f"{shutil.disk_usage(REPO).free / 2**30:.1f} GiB free on disk, "
        f"{os.cpu_count()} host cores")
    jax.monitoring.register_event_duration_secs_listener(COMPILE.listen)
    checks = run(args.chips,
                 N_FOUR_CHIPS if args.chips == 4 else N_ONE_CHIP)
    if checks.failed:
        log("failed: " + "; ".join(checks.failed))
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
