"""One run of one cell: set-up, the measured window, the check, the line.

Set-up (counted in ``setup_s``, from process start to the first request
due): the collection made on the device from the seed in one call
(sharded by rows over the cell's chips where it has more than one) and
brought to the host for the build, the query pool, the engine build (and
spill), and a warm-up of every padded batch shape the lane can form.
The window then drives ``repro.serve.loop.ServeFront.submit`` with the
cell's traffic for ``--seconds``. After it closes: the peak memory of
the fullest chip is read, the engine is freed, the collection is made
again on the device and the reference grades every answer of the
window.

With ``--trace 1`` the same run records a profiler trace of the window
and prints the cell's per-layer metrics instead of its end-to-end ones.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import os
import resource
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import data as bdata
from . import devtrace, grade, load, reference, spec
from .peaks import peaks

clock = time.perf_counter


class NoAccelerator(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def require_accelerator(chips: int):
    """The devices of the run; raises :class:`NoAccelerator` rather than
    fall back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX sees no TPU ({devs[0].platform})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chip(s), JAX sees "
                            f"{len(devs)}")
    return devs


def enable_compile_cache(config: dict) -> str:
    """The program's persistent compilation cache (``$JAX_COMPILATION_
    CACHE_DIR``, else ``<checkout>/.jax_cache``), keeping what the
    configuration's ``compile_cache_min_compile_time_secs`` says: a
    deployment setting, since it decides whether the resident engine's
    eager calls load their programs or compile them again."""
    import jax

    from repro.runtime import enable_compile_cache as enable

    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(config["compile_cache_min_compile_time_secs"]))
    return path


class CompileClock:
    """Seconds and count of XLA backend compiles, from ``jax.monitoring``
    (JAX times a load from the persistent cache as a compile too; those
    are also counted as ``cache_hits``)."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.cache_hits = 0

    def listen(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += duration
            self.count += 1

    def hit(self, event: str, **_kw) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def register(self) -> "CompileClock":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.listen)
        jax.monitoring.register_event_listener(self.hit)
        return self


@dataclasses.dataclass
class Call:
    """One ``engine.query`` the front made, as the benchmark saw it."""
    t0: float
    t1: float
    result: object


class EngineProbe:
    """Stands between the front and the engine: passes every call on,
    wraps ``query`` in a ``bench.engine_query`` trace annotation, and
    keeps what each call returned (counters ride on the result)."""

    def __init__(self, engine, annotate):
        self._engine = engine
        self._annotate = annotate
        self.calls: List[Call] = []

    def query(self, queries, k, g, **kw):
        t0 = clock()
        with self._annotate("bench.engine_query"):
            res = self._engine.query(queries, k, g, **kw)
        self.calls.append(Call(t0, clock(), res))
        return res

    def __getattr__(self, name):
        return getattr(self._engine, name)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric's reader gets."""
    window: load.Window
    calls: List[Call]
    max_batch: int
    series_len: int
    compile_s: float
    batches: int                 # lane batches drained in the window
    batched: int                 # requests in them
    trace: Optional[devtrace.Reduction]   # averaged over the chips
    peaks: dict                  # one chip's
    chips: int

    @property
    def answered(self) -> List[load.Record]:
        return [r for r in self.window.records if r.answered]

    def stats(self) -> list:
        return [c.result.stats for c in self.calls
                if getattr(c.result, "stats", None) is not None]


def lane_sizes(max_batch: int) -> List[int]:
    """Every padded batch the front forms at ``max_batch``: 1, 2, 4, ...
    (``repro.serve.batching.bucket_of(n, 1)``)."""
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [b]


def p95(window: load.Window) -> float:
    """95th percentile (nearest rank) of the latency of every request of
    the window, in ms. A request rejected, failed or never answered
    counts as missing: its latency is taken as the longest any request
    of the run could have waited, past every answered one."""
    lat = []
    for r in window.records:
        if r.answered:
            lat.append(r.done - r.due)
        else:
            lat.append(window.close - r.due + load.ANSWER_WAIT_S)
    lat.sort()
    return 1e3 * lat[max(math.ceil(0.95 * len(lat)) - 1, 0)]


def make_engine(cell: spec.Cell, rows: np.ndarray, spill_dir: str):
    """The system under test, built from the configuration."""
    import jax
    from jax.sharding import Mesh

    from repro.core import IndexSpec, StoreSpec
    from repro.core.engine import DistributedEngine

    cfg = cell.config
    index = IndexSpec(cfg["index"])
    if cfg["residency"] == "hbm":
        mesh = Mesh(np.array(jax.devices()[:cell.chips]), ("data",))
        eng = DistributedEngine(mesh, axes=("data",))
        eng.build(rows, index=index)
        jax.block_until_ready(eng.stacked.data)
        return eng
    eng = DistributedEngine(mesh=None, shards=cell.chips)
    eng.build(rows, index=index,
              store=StoreSpec(spill_dir=spill_dir, codec=cfg["codec"],
                              keep_resident=False))
    return eng


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class System:
    """A cell's system under test after set-up, with what it was made
    from."""
    cell: spec.Cell
    words: object                # the seed as the generator takes it
    rows: np.ndarray             # the collection, on the host
    pool: np.ndarray             # one query per request of the window
    order: np.ndarray            # the order the pool is asked in
    offsets: np.ndarray          # the window's arrival times (s)
    engine: object
    guarantee: object            # the lane's Guarantee
    split: Dict[str, float]      # set-up seconds by stage
    warm_compiles: int
    spill_dir: str

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def front(self, annotate):
        """A started ``ServeFront`` over the engine, with the probe
        between them."""
        from repro.serve.admission import AdmissionController
        from repro.serve.loop import ServeFront

        tr = self.traffic
        probe = EngineProbe(self.engine, annotate)
        front = ServeFront(
            probe, k=int(self.cell.config["k"]),
            max_batch=int(tr["max_batch"]),
            admission=AdmissionController(**tr["admission"]),
            guarantee_kw=dict(tr["guarantee_kw"]))
        return front.start(), probe

    def request(self, rec: load.Record):
        from repro.serve.batching import Request

        return Request(uid=rec.uid, prompt=np.zeros(1, np.int32),
                       deadline_ms=self.traffic["deadline_ms"],
                       series=self.pool[rec.query])

    def close(self) -> None:
        if hasattr(self.engine, "close"):
            self.engine.close()
        self.engine = None
        shutil.rmtree(self.spill_dir, ignore_errors=True)


def collection(cfg: dict, words, chips: int):
    """The configuration's collection in the order of the run's seed
    (:func:`bench.data.collection`), on the device; over more than one
    chip, sharded by rows over the first ``chips`` devices
    (:func:`bench.data.sharded_collection`)."""
    import jax
    import jax.numpy as jnp

    base = jnp.asarray(bdata.seed_words(int(cfg["collection_seed"])))
    if chips > 1:
        return bdata.sharded_collection(base, words, int(cfg["rows"]),
                                        int(cfg["series_len"]),
                                        jax.devices()[:chips])
    return bdata.collection(base, words, int(cfg["rows"]),
                            int(cfg["series_len"]))


def chip_peaks(devices) -> List[Optional[int]]:
    """Each device's ``peak_bytes_in_use`` (None where it reports
    none)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]


def memory_peak(peaks: List[Optional[int]]) -> Optional[int]:
    """The fullest chip's peak: the largest of :func:`chip_peaks`."""
    return max((p for p in peaks if p is not None), default=None)


def prepare(cell: spec.Cell, seed: int, seconds: float,
            compiles: CompileClock,
            engine_factory: Optional[Callable] = None) -> System:
    """Set-up: the collection in the seed's order, the arrivals of a
    window of ``seconds`` and one query for each, the engine, and a
    warm-up of every padded batch the lane can form. Every seed asks the
    same queries, the configuration's, in an order of its own, so that
    the seed does not change the work. ``engine_factory(cell, rows,
    data_dev, spill_dir)`` replaces the engine."""
    import jax.numpy as jnp

    from repro.serve.batching import guarantee_for_deadline

    cfg, tr = cell.config, cell.traffic
    k = int(cfg["k"])
    split: Dict[str, float] = {}
    t = clock()
    words = jnp.asarray(bdata.seed_words(seed))
    dev, scale, where = collection(cfg, words, cell.chips)
    rows = np.asarray(dev)
    scale, where = float(scale), np.asarray(where)
    split["data"] = clock() - t
    t = clock()
    offsets = load.poisson_schedule(float(tr["rate_qps"]), seconds, seed)
    pool = bdata.query_pool(rows, where, scale, len(offsets),
                            cfg["noise_levels"], int(cfg["collection_seed"]))
    order = load.query_order(len(pool), seed)
    split["pool"] = clock() - t

    spill_dir = tempfile.mkdtemp(prefix="bench-spill-")
    try:
        t = clock()
        if engine_factory is None:
            del dev
            engine = make_engine(cell, rows, os.path.join(spill_dir, "s"))
        else:
            engine = engine_factory(cell, rows, dev, spill_dir)
            del dev
        split["build"] = clock() - t

        g = guarantee_for_deadline(tr["deadline_ms"], **tr["guarantee_kw"])
        t, c0 = clock(), compiles.count
        # the easiest queries of the pool first (noise level 0)
        warm = np.argsort(np.arange(len(pool)) % len(cfg["noise_levels"]),
                          kind="stable")
        # largest first: a spilled engine sizes its leaf cache on the first
        # call, and each later call then finds it the size it keeps
        for b in reversed(lane_sizes(int(tr["max_batch"]))):
            np.asarray(engine.query(jnp.asarray(pool[warm[:b]]), k, g).ids)
        split["warm_up"] = clock() - t
    except BaseException:
        shutil.rmtree(spill_dir, ignore_errors=True)
        raise
    return System(cell=cell, words=words, rows=rows, pool=pool,
                  order=order, offsets=offsets, engine=engine, guarantee=g, split=split,
                  warm_compiles=compiles.count - c0, spill_dir=spill_dir)


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, engine_factory: Optional[Callable] = None) -> dict:
    """One run; returns the result line as a dict. ``engine_factory``
    (see :func:`prepare`) is for the control."""
    import jax

    from repro import obs
    from repro.serve.loop import Rejected, lane_of

    cell = spec.load(root, workload)
    cfg, tr = cell.config, cell.traffic
    devs = jax.devices()
    kind_peaks = peaks(devs[0].device_kind) if devs[0].platform == "tpu" \
        else {}
    compiles = CompileClock().register()
    annotate = jax.profiler.TraceAnnotation
    k = int(cfg["k"])

    system = prepare(cell, seed, seconds, compiles, engine_factory)
    front = None
    try:
        g = system.guarantee
        front, probe = system.front(annotate)
        hist = obs.REGISTRY.histogram("serve.lane.batch_size",
                                      lane=lane_of(g.kind))
        h0 = hist.snapshot()
        log_dir = os.path.join(system.spill_dir, "trace")
        if trace:
            # user annotations only on the host, and the HLO modules, so
            # that device ops can be mapped to their kernel scopes
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = True
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        c_win = (compiles.seconds, compiles.count, compiles.cache_hits)
        setup_s = clock() - t_start
        win = load.open_loop(front, system.request, system.order,
                             system.offsets, Rejected, annotate)
        compile_s = compiles.seconds - c_win[0]
        n_compiles = compiles.count - c_win[1]
        n_hits = compiles.cache_hits - c_win[2]
        if trace:
            jax.profiler.stop_trace()
        h1 = hist.snapshot()
        front.stop(drain=True)
        front = None
        by_chip = chip_peaks(devs[:cell.chips])
        peak = memory_peak(by_chip)
        red = devtrace.reduce_dir(log_dir) if trace else None
        calls = probe.calls
        del probe
        system.close()
        gc.collect()

        _log(f"set-up {setup_s:.3f} s: " + ", ".join(
            f"{name} {s:.3f} s" for name, s in system.split.items())
            + f"; warm-up compiled {system.warm_compiles} programs")
        _log(f"window: {win.seconds:.3f} s, {len(win.records)} requests, "
             f"{sum(r.answered for r in win.records)} answered, "
             f"{len(calls)} engine calls, {n_compiles} compiles "
             f"({compile_s:.3f} s, {n_hits} of them loads from the "
             f"persistent cache) inside it")
        if win.lateness_s:
            _log(f"open loop ran late by median "
                 f"{1e3 * statistics.median(win.lateness_s):.3f} ms, "
                 f"max {1e3 * max(win.lateness_s):.3f} ms")

        # the reference, once the window is closed and the engine freed
        t = clock()
        dev, _, _ = collection(cfg, system.words, cell.chips)
        asked = sorted({r.query for r in win.records if r.answered})
        top = reference.reference_topk(dev, system.rows, system.pool[asked],
                                       k)
        del dev
        graded = grade.grade(win.records, system.pool, system.rows,
                             {q: i for i, q in enumerate(asked)}, top, k,
                             tr["guarantee"], cell.limits)
        _log(f"reference over {len(asked)} queries: {clock() - t:.3f} s")
        _log(f"peak bytes by chip {by_chip}; host peak RSS "
             f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss} KiB")
    finally:
        if front is not None:
            front.stop(drain=False)
        system.close()

    attempted = len(win.records)
    answered = sum(r.answered for r in win.records)
    if trace:
        view = RunView(window=win, calls=calls,
                       max_batch=int(tr["max_batch"]),
                       series_len=int(cfg["series_len"]),
                       compile_s=compile_s,
                       batches=h1["count"] - h0["count"],
                       batched=int(round(h1["sum"] - h0["sum"])),
                       trace=red, peaks=kind_peaks, chips=cell.chips)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m.name)(view)
            if v is not None:
                metrics[m.name] = {"value": float(v), "unit": m.unit}
    else:
        values = {"qps": answered / win.seconds, "p95_ms": p95(win),
                  "recall": graded["recall"], "setup_s": setup_s}
        # ``<name>.<part>`` is <name> under a bound of its own, in the
        # cells it lists
        metrics = {m.name: {"value": values[m.name.split(".")[0]],
                            "unit": m.unit}
                   for m in cell.end_to_end}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if red is not None:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    checks = {name: {"value": v, "limit": cell.limits[name]}
              for name, v in graded["checks"].items()}
    line = {"correct": bool(graded["correct"] and graded["graded"] > 0),
            "attempted": attempted, "failed": attempted - answered,
            "metrics": metrics, "device": device}
    if red is not None:
        line["breakdown"] = red.breakdown()
    line["checks"] = checks
    return line


def main(argv: List[str], root: str, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load(root, args.workload)
    try:
        require_accelerator(cell.chips)
    except NoAccelerator as e:
        _log(f"bench: {e}; the benchmark runs on the chip only")
        return 3
    _log(f"compile cache: {enable_compile_cache(cell.config)}")
    line = run(root, args.workload, args.seed, args.seconds,
               bool(args.trace), t_start)
    for name, c in line["checks"].items():
        _log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(line), flush=True)
    return 0
