"""A copy of the benchmark at a tiny size, for the CPU tests.

``make_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
directory, writes tiny configuration files beside the real ones and
points the copy's configurations at them. ``run`` drives
``bench/run.py``'s ``main`` there with the look for a chip and the
compile cache steered past, and returns the result line.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 4096


def make_root(tmp, rows: int = ROWS) -> str:
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg["rows"] = rows
        c["file"] = f"bench/configs/{c['name']}-tiny.json"
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(cfg, f)
    write_json(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def add_cell(root: str, name: str, like: str, chips: int) -> None:
    """A cell ``name`` in the copy at ``root``: ``like``'s entry, traffic
    and limits on ``chips`` chips."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = read_json(path)
    entry = dict(next(w for w in bench["workloads"] if w["name"] == like),
                 name=name, chips=chips)
    bench["workloads"].append(entry)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(name)
    write_json(path, bench)
    limits = os.path.join(root, "bench", "limits", "{}.json")
    write_json(limits.format(name), read_json(limits.format(like)))


def write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def run(root: str, monkeypatch, capsys, *argv: str):
    """``bench/run.py`` ``main`` over ``root`` on the CPU; the last line
    of standard output, parsed, and the lines of standard error."""
    from bench import harness
    from bench import run as run_py

    monkeypatch.setattr(harness, "require_accelerator", lambda chips: None)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda config: "off")
    capsys.readouterr()
    assert run_py.main(list(argv), root=root) == 0
    cap = capsys.readouterr()
    return (json.loads(cap.out.strip().splitlines()[-1]),
            cap.err.strip().splitlines())
