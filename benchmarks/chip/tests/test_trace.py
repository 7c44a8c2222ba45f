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


# a synthetic profile: a 1000-ns window, the device busy 0-100, 400-500 and
# 900-1000, host spans of the benchmark, of the program, and of neither
_HOST = [("bench.window", 0, 1000), ("bench.env_step", 50, 950),
         ("rollout.step", 60, 940), ("chem.enumerate", 150, 350),
         ("predict.featurize", 550, 850), ("other.name", 690, 710)]
_OPS = [("op", 0, 100), ("op", 400, 500), ("op", 900, 1000)]


class _Ev:
    def __init__(self, name, s, e):
        self.name, self.start_ns, self.duration_ns = name, s, e - s


class _Line:
    def __init__(self, name, evs):
        self.name, self.events = name, [_Ev(*e) for e in evs]


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    planes = [_Plane("/device:TPU:0", [_Line(trace.OPS_LINE, _OPS),
                                       _Line(trace.MODULES_LINE,
                                             [("jit_step(3)", s, e)
                                              for _, s, e in _OPS])]),
              _Plane("/host:CPU", [_Line("main", _HOST)])]

    @classmethod
    def from_file(cls, path):
        return cls()


def test_load_keeps_the_program_spans_and_gaps_name_the_innermost(monkeypatch):
    import jax.profiler

    monkeypatch.setattr(jax.profiler, "ProfileData", _Profile)
    events = trace.load("synthetic.xplane.pb")
    assert [h[0] for h in events["host"]] == [h[0] for h in _HOST[:5]]
    red = trace.reduce(events)
    # idle 500-900 (middle 700) lies in the featurisation, 100-400 (middle
    # 250) in the enumeration: the program's spans, not bench.env_step
    assert [g[0] for g in red["gaps"]] == ["predict.featurize", "chem.enumerate"]
    assert [g[1] for g in red["gaps"]] == pytest.approx([400e-9, 300e-9])
    # the device numbers do not depend on the program's spans
    bench_only = dict(events, host=[h for h in events["host"]
                                    if h[0].startswith(trace.SPAN_PREFIX)])
    old = trace.reduce(bench_only)
    assert [g[0] for g in old["gaps"]] == ["bench.env_step"] * 2
    for k in ("busy_s", "window_s", "programs"):
        assert red[k] == old[k]
