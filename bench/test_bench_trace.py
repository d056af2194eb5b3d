"""The trace reduction, on a trace recorded on the chip and on a made-up
one whose answer is known exactly."""

import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "testdata", "disk-ng-open.xplane.pb.gz")
EXPECTED = os.path.join(HERE, "testdata", "disk-ng-open.expected.json")


def test_recorded_chip_trace():
    """Numbers read off the committed trace by hand (see the expected
    file's ``how``), reproduced by the reduction."""
    with open(EXPECTED) as f:
        exp = json.load(f)
    red = devtrace.reduce_file(TRACE)
    assert red.devices == 1
    assert red.window_s == pytest.approx(exp["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(exp["busy_s"], rel=1e-9)
    assert red.scope_s == pytest.approx(exp["scope_s"], rel=1e-9)
    top = red.breakdown()
    assert [n for n, _ in top["device_ops"][:3]] == exp["top_ops"]
    assert sum(s for _, s in red.gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-9)
    assert {n for n, _ in red.gaps} <= {"bench.engine_query", "bench.front"}


def _ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end)


def _line(name, events):
    return NS(name=name, events=events)


def test_made_up_trace():
    """Window 0..100 ns; ops at 10-30 and 20-40 (overlapping), 60-70, a
    while op around 55-75 that must not count itself, and one op half
    outside the window."""
    host = NS(name="/host:CPU", lines=[_line("python", [
        _ev("bench.window", 0, 100),
        _ev("bench.engine_query", 5, 45),
        _ev("bench.engine_query", 50, 80)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [_ev("jit_step(7)", 0, 50),
                              _ev("jit_loop(8)", 50, 120)]),
        _line("XLA Ops", [
            _ev("%fusion.1 = f32[32,512]{1,0} fusion(...)", 10, 30),
            _ev("%copy.2 = f32[8]{0} copy(...)", 20, 40),
            _ev("%while.3 = (f32[4]) while(...)", 55, 75),
            _ev("%fusion.4 = f32[4]{0} fusion(...)", 60, 70),
            _ev("%fusion.5 = f32[4]{0} fusion(...)", 95, 110)])])
    scopes = {"jit_step(7)": {"fusion.1": ("fusion", "sq_l2"),
                              "copy.2": ("copy", None)},
              "jit_loop(8)": {"while.3": ("while", None),
                              "fusion.4": ("fusion", "l2"),
                              "fusion.5": ("fusion", None)}}
    red = devtrace.reduce(NS(planes=[host, dev]), scopes)
    assert red.window_s == pytest.approx(100e-9)
    # busy: [10, 40] + [60, 70] + [95, 100]
    assert red.busy_s == pytest.approx(45e-9)
    assert red.idle_share == pytest.approx(0.55)
    assert red.scope_s == pytest.approx({"sq_l2": 20e-9, "l2": 10e-9})
    assert red.op_s["jit_step/fusion.1 f32[32,512]"] == pytest.approx(20e-9)
    assert not any("while" in k for k in red.op_s)
    gaps = sorted(red.gaps, key=lambda g: g[1])
    # idle: 0-10 (query), 40-60 (mid 50: second query), 70-95 (mid 82:
    # none)
    assert gaps == [("bench.engine_query", pytest.approx(10e-9)),
                    ("bench.engine_query", pytest.approx(20e-9)),
                    ("bench.front", pytest.approx(25e-9))]


def test_no_window_or_no_device_gives_nothing():
    host = NS(name="/host:CPU", lines=[_line("python", [])])
    assert devtrace.reduce(NS(planes=[host]), {}) is None
