"""The trace reduction: busy union, idle gaps labelled by the host span they
fall in, and the halves of the window."""
from pathlib import Path

import pytest

from harness import trace

DATA = Path(__file__).parent / "data"


def test_reduce_synthetic_events():
    devices = {"/device:TPU:0": [("a", 0, 10), ("b", 5, 20), ("c", 30, 40),
                                 ("early", -10, -5)]}
    spans = [("dispatch", 0, 2), ("fetch", 2, 25),
             ("dispatch", 25, 26), ("fetch", 26, 50)]
    s = trace.reduce_events(devices, spans)
    assert s.window_s == pytest.approx(50e-9)
    assert s.busy_s == pytest.approx(30e-9)
    assert s.busy_s_halves == pytest.approx((20e-9, 10e-9))
    assert s.chunks == 2
    assert [g[0] for g in s.idle_gaps] == ["fetch", "fetch"]
    assert sorted(g[1] for g in s.idle_gaps) == pytest.approx([10e-9, 10e-9])
    assert s.top_ops[0] == ["b", pytest.approx(15e-9)]


def test_nested_operations_count_once():
    devices = {"/device:TPU:0": [("while", 0, 100), ("a", 10, 30),
                                 ("b", 40, 90), ("c", 50, 60)]}
    spans = [("dispatch", 0, 1), ("fetch", 1, 100)]
    s = trace.reduce_events(devices, spans)
    ops = dict(s.top_ops)
    assert ops == pytest.approx({"while": 30e-9, "a": 20e-9, "b": 40e-9,
                                 "c": 10e-9})
    assert sum(ops.values()) == pytest.approx(s.busy_s)


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce_events({"/device:TPU:0": []}, [("dispatch", 0, 1),
                                                   ("fetch", 1, 2)])


def _covered(intervals, lo, hi):
    """Covered length by a sweep over interval boundaries (an algorithm
    other than the reduction's merge)."""
    edges = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    edges.sort()
    total, depth, last = 0, 0, None
    for x, d in edges:
        if depth > 0:
            total += x - last
        depth += d
        last = x
    return total


def test_reduce_a_trace_recorded_on_the_chip():
    """Three 4-tick chunks of the paper stream at N=10^4 (8 payload lanes)
    traced on a TPU v5e."""
    path = DATA / "paper_stream_3chunks.xplane.pb"
    devices, spans = trace.events_of(str(path))
    assert list(devices) == ["/device:TPU:0"] and devices["/device:TPU:0"]
    assert sorted(n for n, _, _ in spans) == ["dispatch"] * 3 + ["fetch"] * 3
    s = trace.reduce_events(devices, spans)
    lo = min(t for n, t, _ in spans if n == "dispatch")
    hi = max(t for n, _, t in spans if n == "fetch")
    ops = [(a, b) for _, a, b in devices["/device:TPU:0"]]
    assert s.chunks == 3
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_s == pytest.approx(_covered(ops, lo, hi) / 1e9, rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    assert sum(s.busy_s_halves) == pytest.approx(s.busy_s, rel=1e-9)
    assert sum(g for _, g in s.idle_gaps) <= s.window_s - s.busy_s + 1e-12
    assert {g[0] for g in s.idle_gaps} <= {"dispatch", "fetch", "other"}
    assert sum(t for _, t in s.top_ops) <= s.busy_s * (1 + 1e-9)
