"""Perf-trajectory snapshot: one compact JSON at the repo root per PR.

``python -m benchmarks.run --snapshot`` writes ``SNAPSHOT_NAME``
(override with ``--out``) with the currencies of the serving hot path
at the default bench scale — kernel µs (selection merges vs their
full-sort baselines, and since PR 5 the fused pq_adc_select vs its
materializing oracle plus the [B, R]-never-materialized memory
check), on-disk bytes-read, in-memory queries/s, and since PR 4 the
out-of-core serving rows: engine queries/s over spill-built shards
and the Scheduler-driven deadline-mixed retrieval front, now with
per-request serve-latency DISTRIBUTIONS (p50/p95/p99 via the
repro.obs log-bucketed histograms), and the streaming-ingest
freshness row (insert ->
first-retrievable lag through the ServeFront write lane,
docs/INGEST.md) — so later PRs can diff the perf trajectory without
rerunning whole suites.
``--smoke`` compiles and runs every path once at the small scale
without writing the file (the scripts/verify.sh regression gate: a
snapshot that stops compiling fails verify before it rots).
``benchmarks/compare.py`` diffs a fresh snapshot against the
committed baseline with per-metric tolerances (the CI bench-compare
job).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import IndexSpec, StoreSpec
from repro.core import search as S
from repro.core.engine import DistributedEngine
from repro.core.guarantees import Guarantee
from repro.core.index import FrozenIndex
from repro.core.indexes import dstree
from repro.serve.batching import Request, Scheduler
from repro.store import DeviceLeafCache

from . import bench_kernels
from .common import dataset, timeit

SNAPSHOT_NAME = "BENCH_pr10.json"


def _repo_root_path(name: str = None) -> str:
    return os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..",
                     name or SNAPSHOT_NAME))


def collect(scale: str = "default", smoke: bool = False) -> dict:
    repeats = 1 if smoke else 3
    data, q, _bf, p = dataset(scale)
    qj = jnp.asarray(q)
    k = p["k"]

    # --- kernel µs + the selection-vs-full-sort speedups ---
    krows = bench_kernels.run(scale, out_dir=None)
    kernels_us = {r["kernel"]: round(r["us_per_call"], 1)
                  for r in krows if "us_per_call" in r}
    speedups = {r["kernel"]: round(r["speedup_vs_full_sort"], 2)
                for r in krows if "speedup_vs_full_sort" in r}
    pq_mem = next(
        ({k: v for k, v in r.items()
          if k not in ("bench", "kernel")}
         for r in krows if r.get("kernel") == "pq_adc_select_memory"),
        None)

    # --- in-memory queries/s (the paper's best tree, eps=1) ---
    idx = dstree.build(data, leaf_cap=256)

    def qfn():
        return S.search(idx, qj, k, Guarantee(delta=0.99, epsilon=1.0))

    sec = timeit(qfn, repeats=repeats)
    qps = len(q) / sec

    # --- on-disk bytes-read (f32 store, solo vs cooperative) ---
    disk = {}
    with tempfile.TemporaryDirectory() as tmp:
        store = FrozenIndex.load(idx.save(os.path.join(tmp, "f32")),
                                 resident="summaries")
        cap = max(store.num_leaves // 8, qj.shape[0])
        for share in (False, True):
            cache = DeviceLeafCache(store, cap)
            t0 = time.perf_counter()
            out = S.search_ooc(store, qj, k,
                               Guarantee(delta=0.99, epsilon=1.0),
                               cache=cache, share_gathers=share)
            jax.block_until_ready(out.result.dists)
            tag = "coop" if share else "solo"
            disk[f"bytes_read_cold_{tag}"] = out.stats["bytes_read"]
            disk[f"t_cold_s_{tag}"] = round(time.perf_counter() - t0, 4)
        disk["dataset_bytes"] = out.stats["dataset_bytes"]

    # --- out-of-core serving: engine over spilled shards + the
    #     Scheduler-driven deadline-mixed retrieval front ---
    engine_ooc = {}
    serve = {}
    with tempfile.TemporaryDirectory() as tmp:
        mesh = jax.make_mesh((1,), ("data",))
        eng = DistributedEngine(mesh, method="dstree")
        eng.build(data, index=IndexSpec("dstree", leaf_cap=256),
                  store=StoreSpec(spill_dir=os.path.join(tmp, "sp"),
                                  codec="bf16", keep_resident=False))
        g = Guarantee(epsilon=1.0)
        eng.query(qj, k, g)  # warm caches + compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            res = eng.query(qj, k, g)
            jax.block_until_ready(res.dists)
        dt = (time.perf_counter() - t0) / repeats
        engine_ooc = {
            "codec": "bf16", "epsilon": 1.0,
            "queries_per_s": round(len(q) / dt, 1),
            "bytes_read_warm": res.stats["bytes_read"],
            "shards": len(eng.shard_dirs),
        }

        deadlines = [None, 40.0, 20.0, 5.0] * (len(q) // 4 + 1)
        reqs = [Request(uid=i, prompt=np.zeros(4, np.int32),
                        deadline_ms=deadlines[i], series=q[i])
                for i in range(len(q))]
        sched = Scheduler()
        sched.run_retrieval(eng, reqs, k)  # warm per-group shapes
        # per-request retrieval-latency distribution: every repeat's
        # per-uid retrieval_ms lands in a private log-bucketed
        # histogram (repro.obs quantile extraction — the serving
        # stack's own p50/p95/p99 machinery, not numpy over a list)
        lat_hist = obs.Histogram("serve.retrieval_ms", ())
        t0 = time.perf_counter()
        for _ in range(repeats):
            out_r = sched.run_retrieval(eng, reqs, k)
            for v in out_r.values():
                lat_hist.record(v["retrieval_ms"])
        dt = (time.perf_counter() - t0) / repeats
        kinds = sorted({v["kind"] for v in out_r.values()})
        qn = lat_hist.quantiles()
        serve = {
            "requests_per_s": round(len(reqs) / dt, 1),
            "deadline_mix_kinds": kinds,
            "latency_ms": {key: round(val, 3)
                           for key, val in qn.items()},
        }

        # --- the latency-vs-load curve: static barrier front vs the
        #     continuous-batching front over the SAME warm engine ---
        from . import bench_serve_load
        serve_load = bench_serve_load.run(scale, smoke=smoke,
                                          engine=eng)
        # freshness is its own top-level section (the streaming-ingest
        # headline: insert -> first-retrievable lag through the write
        # lane, docs/INGEST.md) so compare.py can gate it
        # independently of the latency-vs-load curve
        freshness = serve_load.pop("freshness", None)

    return {
        "snapshot": SNAPSHOT_NAME,
        "scale": scale,
        "backend": jax.default_backend(),
        "kernels_us": kernels_us,
        "merge_speedup_vs_full_sort": speedups,
        "pq_fused_memory": pq_mem,
        "query_memory": {
            "method": "dstree", "epsilon": 1.0, "delta": 0.99,
            "queries_per_s": round(qps, 1),
            "us_per_query": round(sec / len(q) * 1e6, 1),
        },
        "query_disk": disk,
        "engine_ooc": engine_ooc,
        "serve": serve,
        "serve_load": serve_load,
        "freshness": freshness,
    }


def run_snapshot(scale: str = "default", smoke: bool = False,
                 out_path: Optional[str] = None) -> dict:
    snap = collect(scale=scale, smoke=smoke)
    if smoke:
        print("# snapshot smoke OK (nothing written)")
        return snap
    path = out_path or _repo_root_path()
    snap["snapshot"] = os.path.basename(path)
    with open(path, "w") as f:
        json.dump(snap, f, indent=1)
    print(f"# snapshot written to {path}")
    return snap
