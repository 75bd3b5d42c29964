"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on hand-made planes, and on small traces recorded on a TPU v5e
(3 ms slices of traced runs, cut by ``make_trace_fixture.py``)."""
import gzip
import json
from pathlib import Path

import numpy as np
import pytest

from harness import trace

FIXTURES = sorted((Path(__file__).parent / "data").glob("*_trace.json.gz"))


def test_union_merges_overlaps_and_keeps_gaps():
    iv = trace.union(np.array([0.0, 5.0, 1.0, 10.0, 12.0]),
                     np.array([2.0, 6.0, 3.0, 11.0, 12.5]))
    assert iv == [(0.0, 3.0), (5.0, 6.0), (10.0, 11.0), (12.0, 12.5)]


def _planes():
    host = {"python": [("bench.window", 0, 100), ("bench.call", 0, 60),
                       ("bench.build", 0, 20), ("bench.call", 60, 100)]}
    dev0 = {"XLA Ops": [("fusion.1", 20, 50), ("fusion.2", 40, 55),
                        ("%while.3 = (f32[])", 70, 90),
                        ("%fusion.4 = f32[8]", 75, 80),
                        ("outside", 100, 120)]}
    dev1 = {"XLA Ops": [("fusion.1", 10, 90)]}
    return [("/host:CPU", host), ("/device:TPU:0", dev0),
            ("/device:TPU:1", dev1), ("/device:TPU:0 extra", {})]


def test_reduce_planes_hand_made():
    tr = trace.reduce_planes(_planes())
    assert tr["window_s"] == pytest.approx(100e-9)
    # device 0 busy [20,55) + [70,90) = 55 ns; device 1 busy 80 ns
    assert tr["busy_by_device"]["/device:TPU:0"] == pytest.approx(55e-9)
    assert tr["busy_by_device"]["/device:TPU:1"] == pytest.approx(80e-9)
    assert tr["busy_s"] == pytest.approx(67.5e-9)
    ops = dict(tr["device_ops"])
    assert ops["fusion.1"] == pytest.approx((30 + 80) / 2 * 1e-9)
    assert "outside" not in ops
    # the loop's own time excludes the fusion nested inside it
    assert ops["%while.3"] == pytest.approx(15 / 2 * 1e-9)
    assert ops["%fusion.4"] == pytest.approx(5 / 2 * 1e-9)
    gaps = dict(tr["idle_gaps"])
    # device 0 idles [0,20) in build, [55,60) in call 1, [60,70) and
    # [90,100) in call 2; device 1 idles [0,10) in build, [90,100) in call 2
    assert gaps["build"] == pytest.approx((20 + 10) / 2 * 1e-9)
    assert gaps["call"] == pytest.approx((5 + 10 + 10 + 10) / 2 * 1e-9)


def test_no_window_or_no_device_reads_nothing():
    planes = _planes()
    assert trace.reduce_planes(planes[1:]) is None
    assert trace.reduce_planes(planes[:1]) is None


def _brute_busy(events, w0, w1):
    """Busy time by walking a nanosecond grid (the fixture is short)."""
    grid = np.zeros(int(w1 - w0), bool)
    for _, a, b in events:
        lo, hi = max(int(a - w0), 0), min(int(b - w0), grid.size)
        if hi > lo:
            grid[lo:hi] = True
    return grid.sum() * 1e-9


def test_recorded_traces_exist():
    assert FIXTURES


@pytest.mark.parametrize("fixture", FIXTURES, ids=lambda p: p.name)
def test_recorded_tpu_trace(fixture):
    with gzip.open(fixture, "rt") as f:
        planes = [(p, {k: [tuple(e) for e in v] for k, v in lines.items()})
                  for p, lines in json.load(f)]
    tr = trace.reduce_planes(planes)
    host = dict(planes)["/host:CPU"]
    (w0, w1), = [(a, b) for evs in host.values() for n, a, b in evs
                 if n == trace.WINDOW]
    dev = [(p, l) for p, l in planes if p.startswith("/device:TPU:")]
    assert len(tr["busy_by_device"]) == len(dev) >= 1
    for p, lines in dev:
        assert tr["busy_by_device"][p] == pytest.approx(
            _brute_busy(lines["XLA Ops"], w0, w1), rel=1e-6)
    assert 0 < tr["busy_s"] <= tr["window_s"]
