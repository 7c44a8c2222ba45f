"""The program's host spans (``repro.spans``): self time under nesting,
totals kept across threads, snapshots that subtract like the chip
benchmark's counters, one span per phase of an env step, the engine's
chemistry seconds read from the same spans, and the predictor programs'
own jit names."""

import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.chem.smiles import from_smiles
from repro.core import (CHEM_MODES, DQNAgent, DQNConfig, EnvConfig, ReplayBuffer,
                        RewardConfig, RolloutEngine)
from repro.core.agent import QNetwork
from repro.spans import snapshot, span

from conftest import OracleService

MOLS = [from_smiles(s) for s in
        ("C1=CC=CC=C1O", "CC1=CC(C)=CC(C)=C1O", "CC1=CC=CC=C1O", "OC1=CC=CC=C1O")]

STEP_SPANS = ("rollout.step", "rollout.q_dispatch", "rollout.select",
              "rollout.predict", "rollout.apply", "rollout.flush",
              "rollout.enumerate", "chem.enumerate", "chem.fingerprint")


def _diff(after: dict, before: dict) -> dict:
    zero = {"s": 0.0, "self_s": 0.0, "n": 0}
    return {k: {f: v[f] - before.get(k, zero)[f] for f in v}
            for k, v in after.items()}


def test_self_time_is_total_less_direct_children():
    before = snapshot()
    with span("test.nest.outer") as outer:
        with span("test.nest.child") as a:
            time.sleep(0.01)
            with span("test.nest.grandchild"):
                time.sleep(0.005)
        with span("test.nest.child") as b:
            time.sleep(0.01)
        time.sleep(0.005)
    d = _diff(snapshot(), before)
    o, c, g = d["test.nest.outer"], d["test.nest.child"], d["test.nest.grandchild"]
    assert o["n"] == 1 and c["n"] == 2 and g["n"] == 1
    assert o["s"] == pytest.approx(outer.s, abs=1e-12)
    assert c["s"] == pytest.approx(a.s + b.s, abs=1e-12)
    assert o["self_s"] == pytest.approx(o["s"] - c["s"], abs=1e-12)
    assert c["self_s"] == pytest.approx(c["s"] - g["s"], abs=1e-12)
    assert g["self_s"] == pytest.approx(g["s"], abs=1e-12)
    assert o["self_s"] >= 0.004


def test_a_span_that_raises_is_still_counted():
    before = snapshot()
    with pytest.raises(ValueError):
        with span("test.raises"):
            raise ValueError("boom")
    with span("test.raises.after") as t:
        pass
    d = _diff(snapshot(), before)
    assert d["test.raises"]["n"] == 1
    # the raising span left the thread's stack: the next one is top-level
    assert d["test.raises.after"]["self_s"] == pytest.approx(t.s, abs=1e-12)


def test_totals_from_four_threads_lose_no_update():
    n_threads, per_thread = 4, 2000
    readings = [[] for _ in range(n_threads)]
    start = threading.Barrier(n_threads)

    def work(i):
        start.wait()
        for _ in range(per_thread):
            with span("test.threads.outer") as o:
                with span("test.threads.inner"):
                    pass
            readings[i].append(o.s)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        before = snapshot()
        threads = [threading.Thread(target=work, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        d = _diff(snapshot(), before)
    finally:
        sys.setswitchinterval(old)
    outer, inner = d["test.threads.outer"], d["test.threads.inner"]
    assert outer["n"] == inner["n"] == n_threads * per_thread
    assert outer["s"] == pytest.approx(sum(map(sum, readings)), rel=1e-9)
    # each thread's stack is its own: self time never goes below zero
    assert outer["self_s"] == pytest.approx(outer["s"] - inner["s"], rel=1e-9)
    assert outer["self_s"] > 0


def test_snapshot_difference_is_what_the_benchmark_delta_computes():
    from benchmarks.chip.harness import delta

    with span("test.delta.a"):
        pass
    before = snapshot()
    with span("test.delta.a"):
        pass
    with span("test.delta.b"):
        pass
    after = snapshot()
    got = delta({"program": after}, {"program": before})["program"]
    assert got == _diff(after, before)
    assert got["test.delta.a"]["n"] == 1 and got["test.delta.b"]["n"] == 1
    assert all(isinstance(v, (int, float)) for s in after.values() for v in s.values())


def _engine(chem):
    engine = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2], MOLS[3]]],
                           EnvConfig(max_steps=3), chem=chem)
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    bufs = [ReplayBuffer(100, seed=2), ReplayBuffer(100, seed=3)]
    return engine, agent, bufs


@pytest.mark.parametrize("chem", CHEM_MODES)
def test_an_env_step_opens_each_phase_span_once(chem):
    engine, agent, bufs = _engine(chem)
    svc = OracleService()
    engine.step(agent, svc, RewardConfig(), bufs)
    before = snapshot()
    engine.step(agent, svc, RewardConfig(), bufs)
    d = _diff(snapshot(), before)
    assert not engine.done
    opened = {k: v["n"] for k, v in d.items() if v["n"]}
    assert {k: opened.get(k, 0) for k in STEP_SPANS} == dict.fromkeys(STEP_SPANS, 1)
    assert set(opened) == set(STEP_SPANS)
    # the step's phases cover it: its self time is the glue between them
    st = d["rollout.step"]
    assert 0.0 <= st["self_s"] < st["s"]


@pytest.mark.parametrize("chem", CHEM_MODES)
def test_chem_stats_are_the_chem_span_readings(chem):
    engine, agent, bufs = _engine(chem)
    before = snapshot()
    engine.run_episode(agent, OracleService(), RewardConfig(), bufs)
    d = _diff(snapshot(), before)
    st = engine.chem_stats()
    assert st["enum_s"] > 0 and st["fp_s"] > 0
    assert st["enum_s"] == pytest.approx(d["chem.enumerate"]["s"], rel=1e-9)
    assert st["fp_s"] == pytest.approx(d["chem.fingerprint"]["s"], rel=1e-9)
    assert st["enum_s"] + st["fp_s"] == pytest.approx(
        d["chem.enumerate"]["s"] + d["chem.fingerprint"]["s"], rel=1e-9)
    engine.reset_chem_stats()
    assert engine.chem_stats()["enum_s"] == 0.0 == engine.chem_stats()["fp_s"]


def test_property_service_spans_padding_and_jit_names():
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS
    from repro.predictors.service import PropertyService, featurize, stack_features

    bde_m, ip_m = AlfabetS(hidden=16, rounds=1), AIMNetS(hidden=16)
    svc = PropertyService(bde_m, bde_m.init(jax.random.PRNGKey(0)),
                          ip_m, ip_m.init(jax.random.PRNGKey(1)))
    before = snapshot()
    svc.predict(MOLS[:3] + [MOLS[0]])         # three distinct, padded to a rung
    svc.predict(MOLS[:2])                     # all cached: no featurize, no models
    d = _diff(snapshot(), before)
    assert d["predict.keys"]["n"] == 2
    assert d["predict.featurize"]["n"] == 1 and d["predict.models"]["n"] == 1
    assert svc.n_predictor_mols == 3
    assert svc.n_predictor_rows_padded == svc._pad_to(3) > 3
    batch = stack_features([featurize(MOLS[0])])
    assert "@jit_bde_apply" in svc._bde_apply.lower(svc.bde_params, batch).as_text()
    assert "@jit_ip_apply" in svc._ip_apply.lower(svc.ip_params, batch).as_text()


def test_serving_q_has_its_own_jit_name():
    from repro.core.rollout import STATE_DIM
    from repro.serving.service import _ServePolicy

    net = QNetwork(hidden=(8,))
    params = net.init(jax.random.PRNGKey(0))
    pol = _ServePolicy(net, params, lambda q, w: 0, n_workers=2)
    x = np.zeros((2, 4, STATE_DIM), np.float32)
    assert "@jit_serve_q_apply" in pol._apply.lower(params, x).as_text()
