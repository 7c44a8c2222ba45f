"""Reading the program's own host spans from a profile: the spans of a
profile recorded here on the CPU, window totals, idle time by innermost
span, and gap labels when the program's spans join the benchmark's."""

import time
from pathlib import Path

import pytest

from chip import program_spans, trace

DATA = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"

# synthetic events: a 1000-ns window, device busy 0-100, 400-500, 900-1000
SYNTH = {
    "ops": {0: [("op", 0, 100), ("op", 400, 500), ("op", 900, 1000)]},
    "modules": {0: [("jit_step(3)", 0, 100), ("jit_step(3)", 400, 500),
                    ("jit_step(3)", 900, 1000)]},
    "host": [("bench.window", 0, 1000), ("bench.env_step", 50, 950),
             ("rollout.step", 60, 940), ("chem.enumerate", 150, 350),
             ("bench.predict", 600, 800)],
}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A profile recorded on the CPU: program spans inside and outside a
    ``bench.window``; returns its file and the spans' own readings."""
    import jax

    from repro.spans import span

    d = tmp_path_factory.mktemp("profile")
    readings = {"chem.enumerate": 0.0, "learner.sample": 0.0}
    jax.profiler.start_trace(str(d))
    with span("chem.enumerate"):            # before the window: not counted
        time.sleep(0.002)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with span("chem.enumerate") as t:
                time.sleep(0.004)
            readings["chem.enumerate"] += t.s
        with span("learner.sample") as t:
            time.sleep(0.003)
        readings["learner.sample"] += t.s
        with jax.profiler.TraceAnnotation("unrelated.name"):
            pass
    jax.profiler.stop_trace()
    return next(d.rglob("*.xplane.pb")), readings


def test_host_spans_of_a_recorded_profile(recorded):
    path, _ = recorded
    names = [h[0] for h in program_spans.host_spans(path)]
    assert names.count("chem.enumerate") == 4 and names.count("learner.sample") == 1
    assert names.count("bench.window") == 1 and "unrelated.name" not in names


def test_window_totals_are_the_spans_inside_the_window(recorded):
    path, readings = recorded
    tot = program_spans.totals(program_spans.host_spans(path))
    assert tot["chem.enumerate"]["n"] == 3 and tot["learner.sample"]["n"] == 1
    # the profiler's clock and the span's own perf_counter reading agree
    for name, s in readings.items():
        assert tot[name]["s"] == pytest.approx(s, abs=2e-4)


def test_window_totals_of_a_run(recorded, monkeypatch):
    from chip import harness

    path, readings = recorded
    monkeypatch.setattr(harness, "TRACE_DIR", path.parent)
    assert program_spans.window_totals({"trace": None}) is None
    tot = program_spans.window_totals({"trace": {}})
    assert tot["learner.sample"]["s"] == pytest.approx(readings["learner.sample"],
                                                       abs=2e-4)


def test_totals_of_synthetic_spans():
    host = [("bench.window", 100, 200), ("chem.enumerate", 90, 120),
            ("chem.enumerate", 110, 130), ("chem.enumerate", 150, 160),
            ("predict.keys", 190, 210)]
    assert program_spans.totals(host) == {
        "chem.enumerate": {"s": pytest.approx(30e-9), "n": 2}}


def test_a_gap_is_labelled_with_the_innermost_span():
    red = trace.reduce(SYNTH)
    assert red["busy_s"] == pytest.approx(300e-9)
    # idle 500-900 (middle 700): the benchmark's span is the innermost;
    # idle 100-400 (middle 250): the program's chemistry span
    assert [g[0] for g in red["gaps"]] == ["bench.predict", "chem.enumerate"]


def test_idle_by_span_sums_idle_time_under_the_innermost_span():
    by = program_spans.idle_by_span(SYNTH)
    assert by == {"rollout.step": pytest.approx(300e-9),
                  "chem.enumerate": pytest.approx(200e-9),
                  "bench.predict": pytest.approx(200e-9)}
    red = trace.reduce(SYNTH)
    assert sum(by.values()) == pytest.approx(red["window_s"] - red["busy_s"])


def test_idle_outside_every_span_is_no_span():
    ev = dict(SYNTH, host=[("bench.window", 0, 1000), ("chem.enumerate", 150, 350)])
    assert program_spans.idle_by_span(ev) == {
        "no span": pytest.approx(500e-9), "chem.enumerate": pytest.approx(200e-9)}


def test_idle_by_span_of_the_recorded_chip_trace():
    events = trace.load(DATA)
    red = trace.reduce(events)
    by = program_spans.idle_by_span(events)
    assert sum(by.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert by["bench.probe_gap"] >= 0.06
