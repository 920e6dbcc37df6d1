"""The trace reduction: busy time as a union of device intervals, idle
gaps split over the innermost host span open during them."""
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "..", "..", "bench",
                    "testdata")


def test_merge_and_gaps():
    busy = trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert trace.gaps(busy, 0, 10) == [(3, 5), (8, 10)]
    assert trace.clip(busy, 2, 6) == [(2, 3), (5, 6)]


def test_idle_split_over_innermost_span():
    ops = {"/device:TPU:0": [("a", 0, 100), ("b", 300, 400),
                             ("a", 390, 500)]}
    spans = [("bench.window", 0, 1000), ("bench.tick", 0, 600),
             ("bench.inner", 150, 250)]
    t = trace.Trace(ops, {}, spans)
    assert t.window() == (0, 1000)
    assert t.busy_s(0, 1000) == pytest.approx(300e-9)
    idle = t.idle_by_span(0, 1000)
    # gap 100-300: 100 ns inside bench.inner, the rest in bench.tick;
    # gap 500-1000: 100 ns in bench.tick, 400 ns under no span
    assert idle["bench.inner"] == pytest.approx(100e-9)
    assert idle["bench.tick"] == pytest.approx(200e-9)
    assert idle["idle"] == pytest.approx(400e-9)
    ops_s = t.op_seconds(0, 1000)
    assert ops_s["a"] == pytest.approx(210e-9)
    assert trace.top(ops_s, 1) == [["a", pytest.approx(210e-9)]]


def test_recorded_tpu_trace():
    """A trace recorded on one v5e: a jitted reduction of a 1024 x 1024
    matrix run three times inside ``bench.window``, each call inside a
    ``bench.tick`` span and followed by a 10 ms host sleep."""
    t = trace.Trace.load(os.path.join(DATA, "small.xplane.pb"))
    assert list(t.device_ops) == ["/device:TPU:0"]
    lo, hi = t.window()
    assert hi - lo == 33_752_519
    # two whole runs of the program land inside the window on the device
    # clock, which reads about 1.2 ms behind the host's
    assert t.module_seconds(lo, hi) == {
        "jit__lambda(17952180069947267421)": pytest.approx(2.3723e-05)}
    assert t.busy_s(lo, hi) == pytest.approx(2.371e-05)
    idle = t.idle_by_span(lo, hi)
    assert sum(idle.values()) == pytest.approx((hi - lo) * 1e-9 - 2.371e-05)
    assert idle["bench.tick"] == pytest.approx(0.0024375, rel=1e-4)
    top = trace.top(trace.by_label(t.op_seconds(lo, hi)), 1)
    assert top == [["%fusion fusion", pytest.approx(2.368e-05, rel=1e-3)]]
