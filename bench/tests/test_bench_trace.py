"""The reduction from trace events to busy time, idle gaps and op times."""
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
HOST = "/host:CPU"


def ev(plane, name, start, dur, line="XLA Ops"):
    return Event(plane, line if plane != HOST else "python", name,
                 float(start), float(dur))


def hand_trace():
    """A 1,000 ns window: ops at [100, 300), [250, 400) (overlapping) and
    [600, 700); one op straddles the window's end; one op lies before it.
    The host runs a call span over [50, 550) and a wait over [550, 950)."""
    return [
        ev(HOST, "bench.window", 0, 1000),
        ev(HOST, "bench.run_sweep", 50, 500),
        ev(HOST, "bench.wait", 550, 400),
        ev(HOST, "PjitFunction", 60, 10),
        ev(DEV, "fusion.1", 100, 200),
        ev(DEV, "fusion.2", 250, 150),
        ev(DEV, "flash_kernel", 600, 100),
        ev(DEV, "fusion.1", 950, 100),
        ev(DEV, "early", -500, 100),
    ]


def test_busy_is_the_union_inside_the_window():
    s = trace.reduce(hand_trace(), 1)
    # [100, 400) + [600, 700) + [950, 1000)
    assert s.busy_s == pytest.approx(450e-9)
    assert s.window_s == pytest.approx(1000e-9)


def test_idle_gaps_are_named_by_the_host_span():
    s = trace.reduce(hand_trace(), 1)
    # gaps [0,100) 100, [400,600) 200, [700,950) 250
    assert s.idle_gaps == [("bench.wait", pytest.approx(250e-9)),
                           ("bench.run_sweep", pytest.approx(200e-9)),
                           ("bench.run_sweep", pytest.approx(100e-9))]


def test_device_ops_and_breakdown():
    s = trace.reduce(hand_trace(), 1)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx(300e-9)   # whole durations
    assert "early" not in ops
    b = trace.breakdown(s)
    assert b["device_ops"][0][0] == "fusion.1" and len(b["idle_gaps"]) == 3


def test_busy_is_averaged_over_the_devices_used():
    events = hand_trace() + [ev("/device:TPU:1", "fusion.9", 0, 1000)]
    assert trace.reduce(events, 2).busy_s == pytest.approx(725e-9)
    assert trace.reduce(events, 1).busy_s == pytest.approx(450e-9)


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce([ev(DEV, "x", 0, 1)], 1)
    with pytest.raises(RuntimeError, match="no device plane"):
        trace.reduce([ev(HOST, "bench.window", 0, 10)], 1)


def test_events_round_trip(tmp_path):
    path = tmp_path / "e.json.gz"
    trace.save_events(hand_trace(), path)
    assert trace.load_events(path) == hand_trace()


def test_recorded_chip_trace():
    """20 ms of an mlp-family window traced on one TPU v5e: 374 device and
    host events cut from a run's trace (ops wholly inside the 20 ms)."""
    events = trace.load_events(DATA / "mlp-family-20ms.json.gz")
    s = trace.reduce(events, 1)
    assert s.window_s == pytest.approx(0.020)
    assert s.busy_s == pytest.approx(0.009199211, rel=1e-9)
    # the batch-index gathers of the round scan lead the device time
    assert s.device_ops[0][0].startswith("%fusion.178 = s32[256000]")
    assert s.device_ops[0][1] == pytest.approx(0.005379075, rel=1e-9)
    assert {name for name, _ in s.idle_gaps} == {"bench.run_sweep"}
    assert s.idle_gaps[0][1] == pytest.approx(0.00222965, rel=1e-6)
