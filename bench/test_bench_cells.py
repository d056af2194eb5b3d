"""Each cell of BENCHMARK.json, end to end at a tiny size on the CPU."""

import json
import os

import pytest

from bench import tiny

REPO = tiny.REPO
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("cells"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_end_to_end(root, cell, monkeypatch, capsys):
    line, err = tiny.run(root, monkeypatch, capsys, "--workload", cell,
                         "--seed", str(2**33 + 5), "--seconds", "1.5",
                         "--trace", "0")
    assert list(line) == KEYS + ["checks"]
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = tiny.read_json(os.path.join(root, "BENCHMARK.json"))
    assert sorted(line["metrics"]) == sorted(
        m["name"] for m in bench["end_to_end"]
        if cell in m.get("workloads", [cell]))
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    for name, c in line["checks"].items():
        assert c["value"] <= c["limit"], name
    assert err[-len(line["checks"]):] == [
        f"check {n}: {c['value']} (limit {c['limit']})"
        for n, c in line["checks"].items()]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_per_layer_metrics(root, cell, monkeypatch,
                                              capsys):
    line, _ = tiny.run(root, monkeypatch, capsys, "--workload", cell,
                       "--seed", "77", "--seconds", "1.5", "--trace", "1")
    assert set(KEYS) <= set(line) <= set(KEYS + ["breakdown", "checks"])
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    bench = tiny.read_json(os.path.join(root, "BENCHMARK.json"))
    mine = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"]}
    # the counters' readers find something on any backend; the device
    # trace's only on the chip
    from_counters = {m["name"] for m in bench["per_layer"]
                     if m["source"] != "device_trace"} & mine
    assert from_counters <= set(line["metrics"]) <= mine


def test_same_seed_same_inputs(root):
    """The seed alone makes the collection, the pool and the order."""
    import jax.numpy as jnp
    import numpy as np

    from bench import data, load

    seed = 2**40 + 3
    a, sa = data.random_walks(jnp.asarray(data.seed_words(seed)), 1024, 256)
    b, sb = data.random_walks(jnp.asarray(data.seed_words(seed)), 1024, 256)
    c, _ = data.random_walks(jnp.asarray(data.seed_words(seed + 1)), 1024,
                             256)
    assert np.array_equal(a, b) and float(sa) == float(sb)
    assert not np.array_equal(a, c)
    rows, where = np.asarray(a), np.arange(1024)
    assert np.array_equal(
        data.query_pool(rows, where, 1.0, 64, (0.0, 0.1), seed),
        data.query_pool(rows, where, 1.0, 64, (0.0, 0.1), seed))
    assert np.array_equal(load.query_order(64, seed),
                          load.query_order(64, seed))


def test_every_seed_serves_the_same_set():
    """Two seeds serve the configuration's one set of series in two
    orders, so both build the same leaves and compile the same shapes,
    and ask the same queries."""
    import jax.numpy as jnp
    import numpy as np

    from bench import data

    base = jnp.asarray(data.seed_words(2006))
    a, sa, wa = data.collection(base, jnp.asarray(data.seed_words(5)), 1024,
                                256)
    b, sb, wb = data.collection(base, jnp.asarray(data.seed_words(2**33 + 5)),
                                1024, 256)
    a, b, wa, wb = (np.asarray(x) for x in (a, b, wa, wb))
    assert float(sa) == float(sb) and not np.array_equal(a, b)
    assert np.array_equal(a[wa], b[wb])
    # and the same queries
    assert np.array_equal(data.query_pool(a, wa, 1.0, 64, (0.0, 0.1), 2006),
                          data.query_pool(b, wb, 1.0, 64, (0.0, 0.1), 2006))
