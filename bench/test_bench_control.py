"""``correct`` comes out false for the lower-precision control and for
each fault a cell can have, at a tiny size on the CPU.

The control is the reference in the engine's place at ``high`` (three
bfloat16 passes), one precision below the configurations' float32 at
``highest``. The faults are planted under the timed path: an answer
altered where the engine produces it, half of each batch left out (its
lanes given the other half's answers), and a refinement step that
returns its state unchanged. One chip, so there is no exchange between
chips to leave out.
"""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference, tiny

with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("control"))


def _run(root, cell, factory, seed=21):
    return harness.run(root, cell, seed, 1.5, False, time.perf_counter(),
                       engine_factory=factory)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(root, cell):
    line = _run(root, cell, lambda c, rows, dev, spill:
                reference.ReferenceEngine(dev, "high"))
    assert line["correct"] is False
    assert line["checks"]["dist_err_sq"]["value"] > \
        line["checks"]["dist_err_sq"]["limit"]


def test_reference_at_the_stated_precision_is_correct(root):
    line = _run(root, CELLS[0], lambda c, rows, dev, spill:
                reference.ReferenceEngine(dev, "highest"))
    assert line["correct"] is True


@pytest.mark.parametrize("cell", CELLS)
def test_answer_on_another_lane_is_not_correct(root, cell, tmp_path):
    """The traffic file names the lane every answer has to come back on;
    a program whose deadline mapping moves the lane fails ``off_lane``.
    Here the file names a number one off from what the deadline maps
    to."""
    import shutil

    other = os.path.join(str(tmp_path), "checkout")
    shutil.copytree(root, other)
    bench = tiny.read_json(os.path.join(other, "BENCHMARK.json"))
    traffic = next(w["traffic"] for w in bench["workloads"]
                   if w["name"] == cell)
    path = os.path.join(other, "bench", "traffic", f"{traffic}.json")
    tr = tiny.read_json(path)
    g = tr["guarantee"]
    if g["kind"] == "ng":
        g["nprobe"] += 1
    else:
        g["epsilon"] /= 2
    tiny.write_json(path, tr)
    line = _run(other, cell, None)
    assert line["correct"] is False
    assert line["checks"]["off_lane"]["value"] == line["attempted"]


class _Faulty:
    """The real engine with a fault planted where answers are made."""

    def __init__(self, engine, fault):
        self.engine, self.fault = engine, fault

    def query(self, queries, k, g, **kw):
        b = queries.shape[0]
        if self.fault == "half_batch" and b > 1:
            res = self.engine.query(queries[:b // 2], k, g, **kw)
            rep = np.resize(np.arange(b // 2), b)
            return res._replace(dists=jnp.asarray(res.dists)[rep],
                                ids=jnp.asarray(res.ids)[rep])
        res = self.engine.query(queries, k, g, **kw)
        if self.fault == "answer_altered":
            ids = np.asarray(res.ids).copy()
            ids[:, 0] = (ids[:, 0] + 1) % tiny.ROWS
            return res._replace(ids=jnp.asarray(ids))
        return res

    def close(self):
        self.engine.close()


def _unchanged(ctx, pool, gather_idx, row_idx, valid, top_d, top_i, **kw):
    return top_d, top_i


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    if fault == "state_unchanged":
        from repro.core import refine
        from repro.store import ooc

        monkeypatch.setattr(refine, "refine_step", _unchanged)
        monkeypatch.setattr(ooc, "_refine_step", _unchanged)

    def factory(c, rows, dev, spill):
        return _Faulty(harness.make_engine(c, rows, os.path.join(spill, "s")),
                       fault)

    line = _run(root, cell, factory)
    assert line["correct"] is False, line["checks"]
