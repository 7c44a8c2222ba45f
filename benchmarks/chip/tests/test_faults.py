"""A run whose timed path is broken underneath must come out not
correct: the harness's look for a chip is skipped (the CPU, tiny sizes),
the rest of the run is the benchmark's own, and one fault is planted in
the program for each test."""

import time

import pytest


def _run(cell: str, seed: int = 17):
    import jax

    from chip import harness, run

    spec = harness.benchmark_spec()
    c = {x["name"]: x for x in spec["workloads"]}[cell]
    return run.run_cell(spec, c, seed, 3.0, False, jax.devices()[:1],
                        time.perf_counter())


def _failed(checks: dict) -> set:
    return {k for k, c in checks.items() if not c["ok"]}


def test_sound_program_is_correct(tiny):
    result, checks = _run("train-w64")
    assert result["correct"] and not _failed(checks)


def test_update_that_returns_its_state_unchanged(tiny, monkeypatch):
    from repro.core.distributed import DistributedTrainer

    orig = DistributedTrainer._update_once

    def frozen(self, batch, packed):
        p, o = self.params, self.opt_state
        out = orig(self, batch, packed)
        self.params, self.opt_state = p, o
        return out
    monkeypatch.setattr(DistributedTrainer, "_update_once", frozen)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert "change_gap" in _failed(checks)


def test_half_of_the_batch_left_out(tiny, monkeypatch):
    import repro.core.distributed as dist

    orig = dist.densify_batch

    def half(packed):
        return orig({k: v[:, : v.shape[1] // 2] for k, v in packed.items()})
    monkeypatch.setattr(dist, "densify_batch", half)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert _failed(checks) & {"loss_gap", "grad_gap"}


def test_episode_sync_left_out(tiny, monkeypatch):
    from repro.core.distributed import DistributedTrainer

    orig = DistributedTrainer._build_fns

    def no_sync(self):
        orig(self)
        self._sync = lambda tree: tree
    monkeypatch.setattr(DistributedTrainer, "_build_fns", no_sync)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert "change_gap" in _failed(checks)


def test_acting_q_altered_where_it_is_produced(tiny, monkeypatch):
    from repro.core.agent import QNetwork

    orig = QNetwork.apply_stacked_packed
    monkeypatch.setattr(QNetwork, "apply_stacked_packed",
                        lambda self, p, b, f: orig(self, p, b, f) * 1.1)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert "q_gap" in _failed(checks)


def _flip_first_bit(monkeypatch):
    import repro.core.rollout as rollout

    orig = rollout.incremental_fingerprints_grouped

    def flipped(*a, **k):
        out = orig(*a, **k)
        for fp in out:
            fp[:, 0] = 1.0 - fp[:, 0]
        return out
    monkeypatch.setattr(rollout, "incremental_fingerprints_grouped", flipped)


def _alter_bde(monkeypatch):
    from repro.predictors.service import PropertyService

    orig = PropertyService._run_models
    monkeypatch.setattr(PropertyService, "_run_models",
                        lambda self, batch: (lambda b, i: (b * 1.05, i))(
                            *orig(self, batch)))


def test_fingerprint_altered_where_it_is_produced(tiny, monkeypatch):
    _flip_first_bit(monkeypatch)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert "fp_rows_wrong" in _failed(checks)


def test_prediction_altered_where_it_is_produced(tiny, monkeypatch):
    _alter_bde(monkeypatch)
    result, checks = _run("train-w64")
    assert not result["correct"]
    assert "bde_gap" in _failed(checks)


# ------------------------------------------------------------------ #
# the serving cells: an answer altered where it is produced, and a step
# that leaves every request where it was
# ------------------------------------------------------------------ #
SERVE = "serve-w64-steady"


def test_sound_service_is_correct(tiny):
    result, checks = _run(SERVE)
    assert result["correct"] and not _failed(checks)


def test_serving_q_answers_handed_to_the_neighbouring_row(tiny, monkeypatch):
    import numpy as np

    from repro.serving.service import _ServePolicy

    orig = _ServePolicy._dispatch
    monkeypatch.setattr(_ServePolicy, "_dispatch",
                        lambda self: np.roll(orig(self), 1, axis=1))
    result, checks = _run(SERVE)
    assert not result["correct"]
    assert "serve_q_gap" in _failed(checks)


def test_serving_fingerprint_altered_where_it_is_produced(tiny, monkeypatch):
    _flip_first_bit(monkeypatch)
    result, checks = _run(SERVE)
    assert not result["correct"]
    assert "fp_rows_wrong" in _failed(checks)


def test_serving_prediction_altered_where_it_is_produced(tiny, monkeypatch):
    _alter_bde(monkeypatch)
    result, checks = _run(SERVE)
    assert not result["correct"]
    assert "bde_gap" in _failed(checks)


def test_serving_choice_not_the_greedy_one(tiny, monkeypatch):
    import numpy as np

    from repro.serving.service import MoleculeOptService

    monkeypatch.setattr(MoleculeOptService, "_select_action",
                        lambda self, q, worker: int(np.argmin(q)))
    result, checks = _run(SERVE)
    assert not result["correct"]
    assert "action_wrong" in _failed(checks)


@pytest.mark.parametrize("cell", [SERVE, "serve-w64-overload"])
def test_service_step_that_leaves_its_state_unchanged(tiny, monkeypatch, cell):
    from repro.core.rollout import RolloutEngine

    orig = RolloutEngine.step
    live = {"on": False}

    def frozen(self, *a, **k):
        return [] if live["on"] else orig(self, *a, **k)
    monkeypatch.setattr(RolloutEngine, "step", frozen)
    from chip import harness

    run_cls = harness.driver_class("serve")
    warm = run_cls.warm

    def warm_then_freeze(self):
        warm(self)
        live["on"] = True
    monkeypatch.setattr(run_cls, "warm", warm_then_freeze)
    monkeypatch.setattr(harness, "driver_class", lambda name: run_cls)
    result, checks = _run(cell)
    assert not result["correct"]
