"""The trace reducer on a small trace recorded on a TPU v5e by
``record_trace.py``: three calls of one jitted program inside a
``bench.window`` span, each after a 20-ms host span ``bench.probe_gap``
that sleeps."""

from pathlib import Path

import pytest

from chip import trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(DATA))


def test_busy_share_is_a_share_of_the_window(reduced):
    assert 0.0 < reduced["busy_s"] < reduced["window_s"]
    # three 20-ms sleeps with the device idle: at least 60 ms idle
    assert reduced["window_s"] - reduced["busy_s"] >= 0.06


def test_device_time_per_program(reduced):
    seconds, calls = reduced["programs"]["jit_bench_probe"]
    assert calls == 3
    # a module brackets its ops: within a percent of their union
    assert 0.0 < seconds <= reduced["busy_s"] * 1.01


def test_longest_gaps_carry_the_host_span_open_in_them(reduced):
    gaps = reduced["gaps"]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    top = gaps[:3]
    assert all(label == "bench.probe_gap" for label, _ in top), gaps
    assert all(0.015 <= s <= 0.5 for _, s in top), gaps


def test_breakdown_lists_programs_and_gaps(reduced):
    b = trace.breakdown(reduced)
    assert b["device_ops"][0][0] == "jit_bench_probe"
    assert len(b["idle_gaps"]) <= 10 and b["idle_gaps"][0][0] == "bench.probe_gap"


def test_union_and_clip():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
