"""The harness finds cells, configurations, traffic and metrics by name,
and times and counts requests as its metrics say."""

import os
import threading
import time

import numpy as np
import pytest

from bench import harness, load, spec, tiny


def test_new_files_are_found_by_name(tmp_path, monkeypatch, capsys):
    """A configuration, a traffic mix, a limits file and a metric reader
    dropped into a copy, with new entries in its BENCHMARK.json, make a
    new cell; no file that was there is edited."""
    root = tiny.make_root(tmp_path)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    cfg = tiny.read_json(os.path.join(root, "bench", "configs",
                                      "rw256-4m-disk-tiny.json"))
    cfg["rows"] = 2048
    tiny.write_json(os.path.join(root, "bench", "configs",
                                 "rw256-2k-disk.json"), cfg)
    tr = tiny.read_json(os.path.join(root, "bench", "traffic",
                                     "ng-open.json"))
    tr["rate_qps"] = 25.0
    tiny.write_json(os.path.join(root, "bench", "traffic", "ng-slow.json"), tr)
    tiny.write_json(os.path.join(root, "bench", "limits", "new-cell.json"),
                    tiny.read_json(os.path.join(root, "bench", "limits",
                                                "disk-ng-open.json")))
    with open(os.path.join(root, "bench", "metrics", "answered.py"), "w") as f:
        f.write("def read(run):\n    return len(run.answered)\n")
    bench = tiny.read_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "rw256-2k-disk", "source": "test",
                             "file": "bench/configs/rw256-2k-disk.json",
                             "reduced": ["rows"], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "rw256-2k-disk",
                               "traffic": "ng-slow", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answered", "unit": "queries",
                               "better": "higher",
                               "source": "program_counter", "layer": "test",
                               "moves": "qps", "workloads": ["new-cell"]})
    tiny.write_json(os.path.join(root, "BENCHMARK.json"), bench)

    cell = spec.load(root, "new-cell")
    assert cell.config["rows"] == 2048 and cell.traffic["rate_qps"] == 25.0
    assert [m.name for m in cell.per_layer][-1] == "answered"
    line, _ = tiny.run(root, monkeypatch, capsys, "--workload", "new-cell",
                       "--seed", "3", "--seconds", "1", "--trace", "1")
    assert line["correct"] is True
    assert line["metrics"]["answered"]["value"] == line["attempted"] == 25
    for path, data in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == data, path


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load(tiny.REPO, "no-such-cell")


def _rec(due, done=None, rejected=None):
    r = load.Record(uid=0, query=0, due=due, sent=due)
    if done is not None:
        r.entry, r.done = {"ids": None}, done
    r.rejected = rejected
    return r


def test_p95_counts_a_rejected_request_as_missing():
    answered = [_rec(i, i + 0.1) for i in range(19)]
    win = load.Window(start=0.0, close=20.0,
                      records=answered + [_rec(5.0)], lateness_s=[])
    assert harness.p95(win) == pytest.approx(100.0)
    rejected = _rec(5.0, rejected="queue_full")
    win = load.Window(start=0.0, close=20.0,
                      records=answered[:18] + [_rec(6.0), rejected],
                      lateness_s=[])
    # two of twenty missing: the 95th percentile is a missing one
    assert harness.p95(win) == pytest.approx(
        1e3 * (20.0 - 6.0 + load.ANSWER_WAIT_S))


class _Ticket:
    def __init__(self):
        self.event = threading.Event()

    def result(self, timeout=None):
        assert self.event.wait(timeout)
        return {"ids": np.zeros(1)}


class _StallingFront:
    """Answers at once, but its first submit stalls for ``stall`` s."""

    def __init__(self, stall):
        self.stall, self.calls = stall, 0

    def submit(self, req):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall)
        t = _Ticket()
        t.event.set()
        return t


def test_open_loop_times_from_the_due_time():
    offsets = np.array([0.0, 0.05, 0.10])
    win = load.open_loop(_StallingFront(0.3), lambda rec: rec,
                         np.arange(3), offsets, RuntimeError)
    lat = [r.done - r.due for r in win.records]
    # the stall of the first submit delays the next two past their due
    # times, and their latency counts the wait
    assert lat[1] >= 0.3 - 0.05 - 0.01 and lat[2] >= 0.3 - 0.10 - 0.01
    assert win.lateness_s[1] == pytest.approx(lat[1], abs=0.05)
    assert [r.due - win.start for r in win.records] == pytest.approx(offsets)


def test_poisson_schedule_same_gaps_for_every_seed():
    a = load.poisson_schedule(40.0, 10.0, 1)
    b = load.poisson_schedule(40.0, 10.0, 2**35)
    assert len(a) == len(b) == 400 and a[0] == b[0] == 0.0
    assert not np.array_equal(a, b)
    assert sorted(np.diff(a)) == pytest.approx(sorted(np.diff(b)), abs=0.2)



class _WaitingTicket(_Ticket):
    def result(self, timeout=None):
        if not self.event.wait(timeout):
            raise TimeoutError
        return {"ids": np.zeros(1)}


class _BacklogFront:
    """Answers its requests one after another, ``gap`` s apart, as a
    lane serving a backlog does; request ``lost`` is never answered."""

    def __init__(self, gap, lost):
        self.gap, self.lost, self.n = gap, lost, 0

    def submit(self, req):
        t = _WaitingTicket()
        if self.n != self.lost:
            threading.Timer(self.gap * (self.n + 1), t.event.set).start()
        self.n += 1
        return t


def test_open_loop_waits_while_the_backlog_is_served(monkeypatch):
    """An answer is waited for as long as the one before it keeps
    coming: a backlog served past the wait is late, not lost; only an
    answer that never comes counts as unanswered."""
    monkeypatch.setattr(load, "ANSWER_WAIT_S", 0.25)
    win = load.open_loop(_BacklogFront(0.1, lost=7), lambda rec: rec,
                         np.arange(8), np.zeros(8), RuntimeError)
    assert [r.answered for r in win.records] == [True] * 7 + [False]
    # the seventh answer came 0.7 s after its due time, past the wait
    assert win.records[6].done - win.records[6].due > 0.6
    assert win.close == pytest.approx(win.records[6].done)
