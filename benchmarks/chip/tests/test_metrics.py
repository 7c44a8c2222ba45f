"""The per-layer readers of the program's spans and jit names, on
hand-built contexts: the right number where their input is there, and
nothing where it is not (a program without spans or with one shared jit
name, another driver, an untraced run)."""

import pytest

from chip import harness, program_spans

STEPS, UPDATES = 20, 8
TOTALS = {name: {"s": s, "n": n} for name, s, n in [
    ("chem.enumerate", 24.0, 20), ("chem.fingerprint", 8.0, 20),
    ("predict.keys", 0.5, 20), ("predict.featurize", 16.0, 20),
    ("rollout.select", 0.2, 20), ("rollout.apply", 0.3, 20),
    ("rollout.flush", 0.1, 20), ("learner.sample", 0.16, 8)]}

EXPECTED = {
    "chem_enumerate_ms.train": 1e3 * 24.0 / STEPS,
    "chem_fingerprint_ms.train": 1e3 * 8.0 / STEPS,
    "predict_key_ms.train": 1e3 * 0.5 / STEPS,
    "featurize_ms.train": 1e3 * 16.0 / STEPS,
    "predictor_device_ms.train": 1e3 * 0.004 / STEPS,
    "rollout_bookkeeping_ms.train": 1e3 * 0.6 / STEPS,
    "replay_sample_ms.train": 1e3 * 0.16 / UPDATES,
}


def _ctx():
    return {"driver": "train", "window_s": 50.0,
            "trace": {"programs": {"jit_bde_apply": (0.003, 20),
                                   "jit_ip_apply": (0.001, 20),
                                   "jit_packed_body": (0.09, 20)}},
            "delta": {"chem": {"env_steps": STEPS}, "updates": UPDATES}}


@pytest.fixture
def spans_read(monkeypatch):
    """What ``window_totals`` finds in the run's profile."""
    found = {"totals": TOTALS}
    monkeypatch.setattr(program_spans, "window_totals",
                        lambda ctx: None if ctx["trace"] is None else found["totals"])
    return found


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_the_number(name, spans_read):
    assert harness.metric_reader(name)(_ctx()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_gives_nothing_without_its_input(name, spans_read):
    read = harness.metric_reader(name)
    spans_read["totals"] = {}                            # a program with no spans
    bare = _ctx()
    bare["trace"]["programs"] = {"jit_apply": (0.004, 40)}  # one shared jit name
    assert read(bare) is None
    spans_read["totals"] = TOTALS
    other = _ctx()
    other["driver"] = "serve"
    assert read(other) is None
    idle = _ctx()
    idle["delta"]["chem"]["env_steps"] = 0
    idle["delta"]["updates"] = 0
    assert read(idle) is None
    untraced = _ctx()
    untraced["trace"] = None
    assert read(untraced) is None


def test_the_new_readers_are_in_the_benchmark():
    spec = harness.benchmark_spec()
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in EXPECTED:
        assert by_name[name]["workloads"] == ["train-w64", "train-w64-stream"]
        assert by_name[name]["moves"] == "train_transitions_per_s"


# ------------------------------------------------------------------ #
# the serving cells' readers
# ------------------------------------------------------------------ #
SERVE_STEPS, SLOTS = 40, 64

SERVE_EXPECTED = {
    "chem_enumerate_ms": 1e3 * 24.0 / SERVE_STEPS,
    "chem_fingerprint_ms": 1e3 * 8.0 / SERVE_STEPS,
    "featurize_ms": 1e3 * 16.0 / SERVE_STEPS,
    "serve_q_ms": 1e3 * 0.2 / SERVE_STEPS,
    "device_idle_share": 100.0 * (1 - 0.5 / 25.0),
    "slot_occupancy": 100.0 * 2048 / (SERVE_STEPS * SLOTS),
}


def _serve_ctx():
    return {"driver": "serve", "window_s": 25.0,
            "cfg": {"serve": {"n_slots": SLOTS},
                    "programs": {"q_dispatch": "jit_serve_q_apply"}},
            "trace": {"programs": {"jit_serve_q_apply": (0.2, SERVE_STEPS)},
                      "busy_s": 0.5, "window_s": 25.0},
            "delta": {"service_steps": SERVE_STEPS, "bound": 2048}}


@pytest.mark.parametrize("cell", ["serve", "overload"])
@pytest.mark.parametrize("name", sorted(SERVE_EXPECTED))
def test_serve_reader_gives_the_number(name, cell, spans_read):
    read = harness.metric_reader(f"{name}.{cell}")
    assert read(_serve_ctx()) == pytest.approx(SERVE_EXPECTED[name])


@pytest.mark.parametrize("name", sorted(SERVE_EXPECTED))
def test_serve_reader_gives_nothing_without_its_input(name, spans_read):
    read = harness.metric_reader(f"{name}.serve")
    train = _ctx()
    train["trace"].update(busy_s=1.0, window_s=50.0)
    assert read(train) is None                         # a training cell
    idle = _serve_ctx()
    idle["delta"]["service_steps"] = 0
    if name != "device_idle_share":
        assert read(idle) is None
    if name != "slot_occupancy":
        untraced = _serve_ctx()
        untraced["trace"] = None
        assert read(untraced) is None


def test_the_serving_readers_have_their_entries():
    import json

    import tiny

    spec = json.loads(tiny.SERVING.read_text())
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in SERVE_EXPECTED:
        assert by_name[f"{name}.serve"]["workloads"] == ["serve-w64-steady"]
        assert by_name[f"{name}.serve"]["moves"] == "serve_p95_latency_s"
        assert by_name[f"{name}.overload"]["workloads"] == ["serve-w64-overload"]
        assert by_name[f"{name}.overload"]["moves"] == "serve_completed_rps"
