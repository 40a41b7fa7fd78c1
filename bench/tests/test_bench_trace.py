"""The trace reduction on hand-made events and on a recorded chip trace."""
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).with_name("fixtures") / "trace_v5e.json"
DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1_000_000


def _ev(plane, name, start_ms, dur_ms, line="XLA Ops"):
    return (plane, line, name, int(start_ms * MS), int(dur_ms * MS))


def test_busy_is_the_union_of_ops_inside_the_window():
    events = [
        _ev(HOST, "bench.window", 10, 100, "python"),
        _ev(HOST, "bench.step", 10, 60, "python"),
        _ev(HOST, "bench.wait", 70, 40, "python"),
        _ev(DEV, "fusion.1", 5, 10),  # half before the window: 5 ms count
        _ev(DEV, "fusion.2", 20, 10),
        _ev(DEV, "copy.3", 25, 10),  # overlaps fusion.2: union 20..35
        _ev(DEV, "fusion.2", 100, 30),  # runs past the window: 10 ms count
    ]
    out = trace.reduce(events)
    assert out["window_s"] == pytest.approx(0.100)
    assert out["busy_s"] == pytest.approx(0.030)
    ops = dict(out["device_ops"])
    assert ops == pytest.approx({"fusion.2": 0.020, "copy.3": 0.010, "fusion.1": 0.005})
    assert [n for n, _ in out["device_ops"]][0] == "fusion.2"
    gaps = dict(out["idle_gaps"])
    # idle: 15..20 and 35..70 in the step, 70..100 waiting
    assert gaps == pytest.approx({"bench.step": 0.040, "bench.wait": 0.030})


def test_busy_averages_over_device_planes_and_a_trace_needs_its_window():
    events = [
        _ev(HOST, "bench.window", 0, 10, "python"),
        _ev(DEV, "a", 0, 10),
        _ev("/device:TPU:1", "a", 0, 4),
    ]
    out = trace.reduce(events)
    assert out["busy_s"] == pytest.approx(0.007)
    assert dict(out["idle_gaps"]) == pytest.approx({"none": 0.003})
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(events[1:])
    assert trace.reduce(events[:1])["busy_s"] == 0.0


def test_recorded_chip_trace_reduces_to_a_consistent_breakdown():
    events = [tuple(e) for e in json.loads(FIXTURE.read_text())]
    out = trace.reduce(events)
    assert 0 < out["busy_s"] <= out["window_s"]
    idle = sum(t for _, t in out["idle_gaps"])
    assert out["busy_s"] + idle == pytest.approx(out["window_s"], rel=1e-6)
    times = [t for _, t in out["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10
    assert out["device_ops"][0][0] == "jit__encode_chunk"  # program names, hash dropped
    assert {n for n, _ in out["idle_gaps"]} <= {"bench.step", "bench.submit", "bench.wait", "none"}
