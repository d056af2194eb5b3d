"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found by name:

    bench/configs/<config>.json     the deployment (collection, index,
                                    residency), as ``BENCHMARK.json``'s
                                    ``configs[].file`` says
    bench/traffic/<traffic>.json    the mix: arrival rate, lane and the
                                    guarantee it answers under, batch,
                                    admission
    bench/limits/<workload>.json    the limit of each number that
                                    decides ``correct``
    bench/metrics/<metric>.py       one reader per per-layer metric

A later cell or metric is added by adding such files and entries in
``BENCHMARK.json``; nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    root: str
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[Metric]
    per_layer: List[Metric]

    def reader(self, metric: str) -> Callable:
        """The ``read(run)`` function of ``bench/metrics/<metric>.py``
        (loaded by path: metric names may hold dots)."""
        path = os.path.join(self.root, "bench", "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{metric.replace('.', '_').replace('-', '_')}",
            path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _metric(entry: dict) -> Metric:
    return Metric(name=entry["name"], unit=entry["unit"])


def load(root: str, workload: str) -> Cell:
    """Resolve ``workload`` against ``<root>/BENCHMARK.json``: its
    configuration and traffic files, its limits, and the metrics it
    reports. A name that resolves to nothing raises."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a cell list is reported wherever the
    # end-to-end metric it moves is
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in reported)]
    return Cell(
        root=root, name=workload, chips=int(w["chips"]),
        config=_load_json(os.path.join(root, cfg["file"])),
        traffic=_load_json(os.path.join(root, "bench", "traffic",
                                        f"{w['traffic']}.json")),
        limits=_load_json(os.path.join(root, "bench", "limits",
                                       f"{workload}.json")),
        end_to_end=[_metric(m) for m in e2e],
        per_layer=[_metric(m) for m in layer],
    )
