"""The trace reduction, on hand-made events and on the small trace
recorded on the chip (``lib/testdata/small.xplane.pb``)."""

import os

import pytest

from benchmarks.lib import trace as tr
from benchmarks.lib.trace import Event, Trace

DATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib", "testdata", "small.xplane.pb")


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.measure([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], [(0, 5)]) == []
    assert tr.gaps([(1, 2), (4, 6)], 0, 7) == [(0, 1), (2, 4), (6, 7)]


def _trace():
    # window 0..10; device busy 1-3 (fusion), 3-4 (all-reduce alone),
    # 5-7 (all-gather under a fusion 5-8); idle 0-1, 4-5, 8-10
    dev = [Event("fusion.1", 1, 3), Event("all-reduce.2", 3, 4),
           Event("fusion.3", 5, 8), Event("all-gather.4", 5, 7),
           Event("fusion.9", 11, 12)]           # outside the window
    host = [Event("bench.window", 0, 10),
            Event("bench.engine.step", 0.5, 4.5),
            Event("bench.collect", 4.5, 5), Event("bench.engine.step", 5, 9)]
    return Trace({"/device:TPU:0": dev}, host)


def test_reduction_on_hand_made_events():
    red = tr.reduce_trace(_trace())
    assert red.window == (0, 10) and red.window_s == 10
    assert red.busy_s == pytest.approx(6)               # 1-4 and 5-8
    assert red.collective_s == pytest.approx(3)         # 3-4 and 5-7
    assert red.collective_exposed_s == pytest.approx(1)  # 3-4 only
    assert red.op_seconds["fusion.3"] == pytest.approx(3)
    assert "fusion.9" not in red.op_seconds
    # idle 0-1 (half under the first step), 4-5 (step then collect), 8-10
    assert red.idle_by_span["engine.step"] == pytest.approx(0.5 + 0.5 + 1)
    assert red.idle_by_span["collect"] == pytest.approx(0.5)
    assert red.idle_by_span["(no span)"] == pytest.approx(0.5 + 1)
    assert tr.busy_inside(red, "engine.step") == [
        (pytest.approx(4), pytest.approx(3)),
        (pytest.approx(4), pytest.approx(3))]
    assert tr.seconds_matching(red, r"^fusion") == pytest.approx(5)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["fusion", pytest.approx(5)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_two_devices_average_and_worst():
    t = _trace()
    t.device_ops["/device:TPU:1"] = [Event("fusion.1", 1, 2)]
    red = tr.reduce_trace(t)
    assert red.busy_s == pytest.approx((6 + 1) / 2)
    assert min(red.busy_s_by_device.values()) == pytest.approx(1)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_chip_trace():
    t = tr.load_xplane(DATA)
    assert len(t.device_ops) == 4, "recorded on the four-chip host"
    red = tr.reduce_trace(t)
    steps = tr.busy_inside(red, "engine.step")
    assert len(steps) == 30         # tools/record_small_trace.py STEPS
    assert 0 < red.busy_s < red.window_s
    # the device tracer may start a few steps after the host's
    assert sum(1 for _, busy in steps if busy > 0) >= 20
    for length, busy in steps:
        assert 0 <= busy <= length
    assert red.idle_by_span["collect"] > 0.02       # 30 sleeps of 1 ms
    assert any("fusion" in name for name in red.op_seconds)
    # the program's sum over the sharded rows is an all-reduce
    assert 0 < red.collective_exposed_s <= red.collective_s < red.busy_s
