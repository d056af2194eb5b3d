"""The harness over a collection spread across several chips, on four
virtual CPU devices, and the one-chip path left as it was.

Each multi-device check runs in a subprocess of its own with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (this process
sees one CPU device). ``ROW_BLOCK`` is cut there so that 4,096 rows make
two blocks a chip.
"""

import hashlib
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from bench import data, harness, tiny

PRELUDE = f"""
import json, os, sys
sys.path[:0] = [{tiny.REPO!r}, {os.path.join(tiny.REPO, "src")!r}]
import jax, jax.numpy as jnp, numpy as np
from bench import data
data.ROW_BLOCK = 512
assert len(jax.devices()) == 4
base = jnp.asarray(data.seed_words(2006))
words = jnp.asarray(data.seed_words(2**33 + 5))
"""


def _sub(tmp_path, *code: str) -> dict:
    """Run ``PRELUDE`` and the pieces of ``code`` on four virtual
    devices; the JSON object it prints last."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "-c",
         PRELUDE + "".join(textwrap.dedent(c) for c in code)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_chip_collection_is_the_parents():
    """The one-chip path makes the same bytes as before the sharded path
    was added (digest of rows, where and scale from that code)."""
    cfg = {"collection_seed": 2006, "rows": 4096, "series_len": 256}
    rows, scale, where = harness.collection(
        cfg, jnp.asarray(data.seed_words(2**33 + 5)), 1)
    h = hashlib.sha256()
    for a in (rows, where, scale):
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == ("442ddb210814ef56eabb6d8f49b119e4"
                             "b58e283f53c2a70bea6281e4eebb553b")


def test_sharded_collection_is_the_one_device_collection(tmp_path):
    got = _sub(tmp_path, """
        a, sa, wa = data.collection(base, words, 4096, 256)
        b, sb, wb = data.sharded_collection(base, words, 4096, 256,
                                            jax.devices())
        parts = sorted((s.index[0].start, s.data.shape[0], s.device.id)
                       for s in b.addressable_shards)
        try:
            data.sharded_collection(base, words, 4096 + 512, 256,
                                    jax.devices())
            err = ""
        except ValueError as e:
            err = str(e)
        print(json.dumps({
            "rows": bool(np.array_equal(np.asarray(a), np.asarray(b))),
            "where": bool(np.array_equal(np.asarray(wa), np.asarray(wb))),
            "scale": [float(sa), float(sb)], "parts": parts, "err": err}))
    """)
    assert got["rows"] and got["where"]
    sa, sb = got["scale"]
    assert abs(sb - sa) <= 1e-6 * sa
    assert got["parts"] == [[i * 1024, 1024, i] for i in range(4)]
    assert "multiple of ROW_BLOCK x chips = 2048" in got["err"]


def test_sharded_reference_is_a_float64_brute_force(tmp_path):
    """Over 130 queries (two query blocks, the last one padded)."""
    got = _sub(tmp_path, """
        from bench import reference
        dev, scale, where = data.sharded_collection(base, words, 4096, 256,
                                                    jax.devices())
        rows = np.asarray(dev)
        q = data.query_pool(rows, np.asarray(where), float(scale), 130,
                            (0.0, 0.01, 0.1, 0.25), 2006)
        top = reference.reference_topk(dev, rows, q, 10)
        d2 = np.stack([reference.true_sq(rows, x, np.arange(4096))
                       for x in q])
        ids = np.lexsort((np.broadcast_to(np.arange(4096), d2.shape), d2),
                         axis=1)[:, :10]
        print(json.dumps({
            "ids": bool(np.array_equal(top.ids, ids)),
            "d2": bool(np.array_equal(top.d2,
                                      np.take_along_axis(d2, ids, 1)))}))
    """)
    assert got == {"ids": True, "d2": True}


RUN4 = """
    import contextlib, io, tempfile, time
    from bench import harness, reference, tiny
    from bench import run as run_py

    root = tiny.make_root(tempfile.mkdtemp(dir="."))
    tiny.add_cell(root, "four-chip", "hbm-eps-over", 4)
    harness.require_accelerator = lambda chips: None
"""


def test_four_chip_cell_runs_end_to_end(tmp_path):
    """A resident cell on four chips through ``bench/run.py``'s
    ``main``: sharded collection, four-shard engine, sharded
    reference."""
    line = _sub(tmp_path, RUN4, """
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            assert run_py.main(["--workload", "four-chip", "--seed",
                                str(2**33 + 7), "--seconds", "1.5",
                                "--trace", "0"], root=root) == 0
        print(out.getvalue().strip().splitlines()[-1])
    """)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["count"] == 4
    assert line["metrics"]["recall"]["value"] > 0.9


def test_four_chip_control_is_not_correct(tmp_path):
    """The control's reference engine, at ``high`` over the sharded
    collection, fails the check."""
    line = _sub(tmp_path, RUN4, """
        line = harness.run(root, "four-chip", 21, 1.5, False,
                           time.perf_counter(),
                           engine_factory=lambda c, rows, dev, spill:
                           reference.ReferenceEngine(dev, "high"))
        print(json.dumps(line))
    """)
    assert line["correct"] is False
    dist = line["checks"]["dist_err_sq"]
    assert dist["value"] > dist["limit"]


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_fullest_chips():
    devs = [_Device({"peak_bytes_in_use": 5}), _Device(None),
            _Device({"peak_bytes_in_use": 9}), _Device({})]
    assert harness.chip_peaks(devs) == [5, None, 9, None]
    assert harness.memory_peak(harness.chip_peaks(devs)) == 9
    assert harness.memory_peak(harness.chip_peaks(devs[:1])) == 5
    assert harness.memory_peak(harness.chip_peaks([_Device(None)])) is None
