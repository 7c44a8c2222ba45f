"""Fleet rollout engine: the acting-path equivalence matrix (every rollout
mode transition-identical to the sequential reference), ragged-fleet and
zero-candidate robustness, shape discipline (no recompiles once capacity
settles), and PropertyService dedupe / bucket selection."""

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # declared in pyproject [test]; degrade to a skip
    HAVE_HYPOTHESIS = False

from repro.chem.smiles import from_smiles
from repro.core import (
    CHEM_MODES, DQNAgent, DQNConfig, EnvConfig, ReplayBuffer, RewardConfig,
    RolloutEngine, TrainerConfig,
)
from repro.core.agent import QNetwork, candidate_capacity, candidate_capacity_table
from repro.core.distributed import ROLLOUT_MODES, DistributedTrainer
from repro.core.jit_stats import jit_cache_size

from conftest import OracleService as _OracleService

MOLS = [from_smiles(s) for s in
        ("C1=CC=CC=C1O", "CC1=CC(C)=CC(C)=C1O", "CC1=CC=CC=C1O", "OC1=CC=CC=C1O")]


def _trainer(sync_mode: str, rollout: str) -> DistributedTrainer:
    cfg = TrainerConfig(
        n_workers=2, mols_per_worker=2, episodes=2, sync_mode=sync_mode,
        rollout=rollout, updates_per_episode=2, train_batch_size=8,
        max_candidates=16, dqn=DQNConfig(epsilon_decay=0.9),
        env=EnvConfig(max_steps=3), seed=0)
    return DistributedTrainer(cfg, MOLS, _OracleService(), RewardConfig(),
                              network=QNetwork(hidden=(64, 32)))


def _transitions(buf: ReplayBuffer):
    return [(t.state_fp.tobytes(), t.steps_left_frac, t.reward, t.done,
             t.next_fps.tobytes(), t.next_steps_left_frac) for t in buf._items]


# ------------------------------------------------------------------ #
# the equivalence matrix: every rollout mode == sequential reference
# ------------------------------------------------------------------ #
def _matrix_trainer(rollout: str, sync_mode: str, W: int, seed: int,
                    chem: str = "full", acting: str = "packed",
                    scenarios=None, reward_cfg=None,
                    updates_per_episode: int = 1) -> DistributedTrainer:
    cfg = TrainerConfig(
        n_workers=W, mols_per_worker=1, episodes=2, sync_mode=sync_mode,
        rollout=rollout, chem=chem, acting=acting,
        updates_per_episode=updates_per_episode,
        train_batch_size=3, max_candidates=16, dqn=DQNConfig(epsilon_decay=0.9),
        env=EnvConfig(max_steps=3), seed=seed, scenarios=scenarios)
    mols = (MOLS * ((W + len(MOLS) - 1) // len(MOLS)))[:W]
    return DistributedTrainer(cfg, mols, _OracleService(),
                              reward_cfg if reward_cfg is not None
                              else RewardConfig(),
                              network=QNetwork(hidden=(32,)))


def _assert_matrix_equivalent(seed: int, W: int, sync_mode: str,
                              episodes: int,
                              chem_modes=CHEM_MODES) -> None:
    """Every (rollout mode x chem mode) cell must produce the identical
    transition stream (and, when training updates run, identical synced
    parameters) as the sequential full-recompute reference."""
    streams, stats, params = {}, {}, {}
    for chem in chem_modes:
        for mode in ROLLOUT_MODES:
            tr = _matrix_trainer(mode, sync_mode, W, seed, chem=chem)
            cell = (mode, chem)
            stats[cell] = [tr.train_episode() for _ in range(episodes)]
            streams[cell] = [_transitions(b) for b in tr.buffers]
            params[cell] = jax.tree_util.tree_leaves(tr.params)
    ref = ("per_worker", chem_modes[0])
    for cell in streams:
        if cell == ref:
            continue
        assert streams[cell] == streams[ref], \
            f"{cell} transition stream diverged from {ref} (W={W}, {sync_mode})"
        for sm, sr in zip(stats[cell], stats[ref]):
            assert sm["mean_final_reward"] == pytest.approx(
                sr["mean_final_reward"], abs=1e-6, nan_ok=True)
            assert sm["loss"] == pytest.approx(sr["loss"], abs=1e-5, nan_ok=True)
        for xm, xr in zip(params[cell], params[ref]):
            np.testing.assert_allclose(np.asarray(xm), np.asarray(xr), atol=1e-6)


@pytest.mark.parametrize("sync_mode", ["episode", "step"])
@pytest.mark.parametrize("W", [1, 4, 8])
def test_rollout_mode_matrix(W, sync_mode):
    _assert_matrix_equivalent(seed=0, W=W, sync_mode=sync_mode, episodes=2)


if HAVE_HYPOTHESIS:
    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(0, 2**20),
           W=st.sampled_from([1, 4, 8]),
           sync_mode=st.sampled_from(["episode", "step"]))
    def test_rollout_mode_matrix_property(seed, W, sync_mode):
        _assert_matrix_equivalent(seed=seed, W=W, sync_mode=sync_mode, episodes=1)
else:
    def test_rollout_mode_matrix_property():
        pytest.importorskip("hypothesis")


# ------------------------------------------------------------------ #
# the objective axis: scenario mixes and raw callables through every
# rollout mode (the fleet-vectorized reward layer vs the per-worker
# scalar reference)
# ------------------------------------------------------------------ #
SCENARIO_MIX = ("antioxidant", "qed", "antioxidant_novel", "plogp")


def _custom_objective(props, initial, current, steps_left):
    """A raw pluggable objective (the serving-style callable contract)."""
    if props.bde is None or props.ip is None:
        return -5.0
    return 0.01 * (props.ip - props.bde) + 0.05 * steps_left \
        + 0.001 * current.num_atoms


@pytest.mark.parametrize("sync_mode", ["episode", "step"])
@pytest.mark.parametrize("objective", ["mix", "callable"])
def test_objective_axis_matrix(objective, sync_mode):
    """Every rollout mode must produce the per_worker reference's exact
    transition stream under (a) a heterogeneous scenario mix — including
    the stateful novelty scenario — and (b) a raw callable objective.
    Worker-major row order in the fleet reward layer is what keeps the
    novelty visit sequence identical across modes."""
    kw = ({"scenarios": SCENARIO_MIX} if objective == "mix"
          else {"reward_cfg": _custom_objective})
    streams, params, losses = {}, {}, {}
    for mode in ROLLOUT_MODES:
        tr = _matrix_trainer(mode, sync_mode, 4, seed=5, chem="incremental",
                             **kw)
        st = [tr.train_episode() for _ in range(2)]
        streams[mode] = [_transitions(b) for b in tr.buffers]
        params[mode] = [np.asarray(x)
                        for x in jax.tree_util.tree_leaves(tr.params)]
        losses[mode] = [s["loss"] for s in st]
    for mode in ROLLOUT_MODES:
        assert streams[mode] == streams["per_worker"], \
            f"{mode}/{objective}: transition stream diverged ({sync_mode})"
        assert losses[mode] == pytest.approx(losses["per_worker"], nan_ok=True)
        for xm, xr in zip(params[mode], params["per_worker"]):
            np.testing.assert_array_equal(
                xm, xr, err_msg=f"{mode}/{objective}: params diverged")


def test_homogeneous_scenario_fleet_bit_identical_to_default_path():
    """THE tentpole determinism gate (single-process side; nd > 1 lives in
    tests/multidevice/test_scenarios.py): a fleet running
    scenarios=("antioxidant",) * W — the registry spec compiled against the
    trainer's RewardConfig — is bit-identical to scenarios=None (the
    pre-refactor scalar Eq. 1 path) in transitions, losses AND params."""
    runs = {}
    for scen in (None, ("antioxidant",) * 4):
        tr = _matrix_trainer("fleet", "episode", 4, seed=0,
                             chem="incremental", scenarios=scen)
        stats = [tr.train_episode() for _ in range(2)]
        runs[scen is None] = (
            [_transitions(b) for b in tr.buffers],
            [s["loss"] for s in stats],
            [np.asarray(x) for x in jax.tree_util.tree_leaves(tr.params)])
    assert runs[True][0] == runs[False][0]
    assert runs[True][1] == runs[False][1]           # exact loss equality
    for a, b in zip(runs[True][2], runs[False][2]):
        np.testing.assert_array_equal(a, b)


def test_mixed_fleet_worker_bit_identical_to_solo_twin():
    """Each worker of a mixed-scenario fleet reproduces the exact per-worker
    transition stream of a homogeneous fleet running only its scenario.
    Updates are off (updates_per_episode=0) so workers stay decoupled —
    with param sync on, every worker's actions legitimately depend on the
    whole fleet's replay; without it the only cross-worker channel left
    would be a reward-layer leak, which is what this pins against."""
    def run(scenarios):
        tr = _matrix_trainer("fleet", "episode", 4, seed=2,
                             chem="incremental", scenarios=scenarios,
                             updates_per_episode=0)
        for _ in range(2):
            tr.train_episode()
        return [_transitions(b) for b in tr.buffers]

    mix = ("antioxidant", "antioxidant_novel")       # cycled: w%2
    mixed = run(mix)
    solos = {name: run((name,)) for name in mix}
    for w in range(4):
        assert mixed[w] == solos[mix[w % 2]][w], \
            f"worker {w} ({mix[w % 2]}) diverged from its solo twin"


# ------------------------------------------------------------------ #
# acting representation matrix: packed / packed_async == dense reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("sync_mode", ["episode", "step"])
def test_acting_mode_matrix(sync_mode):
    """Every (fleet rollout x acting representation) cell must reproduce
    the sequential dense reference bit for bit: the packed u8 planes and
    the async dispatch / pre-drawn selection change the transport and the
    overlap, never the actions, transitions or parameters.  (The main
    rollout matrix above already pins acting="packed" — the trainer
    default — against the dense per_worker reference; this one adds the
    explicit dense and packed_async fleet cells.)"""
    from repro.core import ACTING_MODES

    def run(rollout, acting):
        tr = _matrix_trainer(rollout, sync_mode, 4, seed=3,
                             chem="incremental", acting=acting)
        stats = [tr.train_episode() for _ in range(2)]
        return ([_transitions(b) for b in tr.buffers],
                [np.asarray(x) for x in jax.tree_util.tree_leaves(tr.params)],
                [s["loss"] for s in stats])

    ref_streams, ref_params, ref_losses = run("per_worker", "dense")
    for rollout in ("fleet", "fleet_sharded", "fleet_pipelined"):
        for acting in ACTING_MODES:
            streams, params, losses = run(rollout, acting)
            cell = f"{rollout}/{acting} ({sync_mode})"
            assert streams == ref_streams, f"{cell}: transition streams diverged"
            assert losses == pytest.approx(ref_losses, nan_ok=True), \
                f"{cell}: loss trajectory diverged"
            for xm, xr in zip(params, ref_params):
                np.testing.assert_array_equal(xm, xr,
                                              err_msg=f"{cell}: params diverged")


def test_packed_view_dead_rows_stay_zero():
    """Ragged/finished slots contribute all-zero rows to the sticky packed
    acting buffer: stale bytes from an earlier (larger) step must never
    reach the Q evaluation as garbage bit planes."""
    from repro.core.replay import FP_BYTES

    tr = _trainer("episode", "fleet")               # acting defaults to packed
    view = tr._fleet_policy
    assert view.wants_packed_states
    view.reserve(8)
    view._bits[:] = 0xFF                            # poison: stale planes
    view._frac[:] = 7.0
    rng = np.random.default_rng(0)
    bits0 = rng.integers(0, 256, (3, FP_BYTES), dtype=np.uint8)
    frac0 = rng.random(3).astype(np.float32)
    q = view.fleet_q_values_packed(                 # worker 1 is dead: 0 rows
        [bits0, np.zeros((0, FP_BYTES), np.uint8)],
        [frac0, np.zeros((0,), np.float32)])
    assert q[0].shape == (3,) and q[1].shape == (0,)
    np.testing.assert_array_equal(view._bits[0, :3], bits0)
    assert not view._bits[0, 3:].any() and not view._frac[0, 3:].any()
    assert not view._bits[1].any() and not view._frac[1].any()


# ------------------------------------------------------------------ #
# O(1) dispatch scaling (reference and pipelined step loops)
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("pipelined", [False, True])
def test_fleet_one_q_dispatch_and_one_property_batch_per_step(pipelined):
    tr = _trainer("episode", "fleet_pipelined" if pipelined else "fleet")
    tr.engine.reset()
    policy = tr._fleet_policy
    step = tr.engine.step_pipelined if pipelined else tr.engine.step
    steps = 0
    while not tr.engine.done:
        q0, p0 = tr.n_q_dispatches, tr.service.n_calls
        step(policy, tr.service, tr.reward_cfg, tr.buffers)
        assert tr.n_q_dispatches == q0 + 1          # regardless of n_workers
        assert tr.service.n_calls == p0 + 1
        steps += 1
    assert steps == tr.cfg.env.max_steps


def test_per_worker_path_scales_dispatches_with_workers():
    tr = _trainer("episode", "per_worker")
    env = tr.envs[0]
    env.reset()
    q0 = tr.n_q_dispatches
    env.step(tr._views[0], tr.service, tr.reward_cfg, tr.buffers[0])
    assert tr.n_q_dispatches == q0 + 1  # ... per WORKER, i.e. W per fleet step


# ------------------------------------------------------------------ #
# engine mechanics with a plain single-model agent
# ------------------------------------------------------------------ #
def test_engine_multi_worker_with_shared_agent():
    engine = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2], MOLS[3]]],
                           EnvConfig(max_steps=2))
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    bufs = [ReplayBuffer(100, seed=2), ReplayBuffer(100, seed=3)]
    recs = engine.run_episode(agent, _OracleService(), RewardConfig(), bufs)
    assert len(recs) == 2 * 2 * 2                    # W x mols x steps
    assert {(r.worker, r.slot) for r in recs} == {(0, 0), (0, 1), (1, 0), (1, 1)}
    assert len(bufs[0]) == 4 and len(bufs[1]) == 4   # all transitions threaded
    assert agent.n_q_dispatches == 2                 # one per step, fleet-wide
    for m in engine.final_molecules():
        m.check_valences()
        assert m.has_oh_bond()


def test_slot_index_is_stored_not_scanned():
    engine = RolloutEngine([[MOLS[0], MOLS[1]]], EnvConfig(max_steps=2))
    assert [s.index for s in engine.workers[0]] == [0, 1]
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    recs = engine.step(agent, _OracleService(), RewardConfig())
    assert [r.slot for r in recs] == [0, 1]


# ------------------------------------------------------------------ #
# ragged fleets: uneven worker sizes, early finishers, dead workers
# ------------------------------------------------------------------ #
def test_ragged_worker_sizes_and_early_finishers():
    """Workers may own different slot counts and slots may run out of steps
    at different times; the engine keeps stepping the survivors."""
    engine = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2]]], EnvConfig(max_steps=3))
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    svc, bufs = _OracleService(), [ReplayBuffer(100, seed=2), ReplayBuffer(100, seed=3)]
    engine.step(agent, svc, RewardConfig(), bufs)   # also triggers first enumerate
    engine.workers[1][0].steps_left = 1             # worker 1 finishes next step
    recs2 = engine.step(agent, svc, RewardConfig(), bufs)
    assert any(r.done for r in recs2 if r.worker == 1)
    recs3 = engine.step(agent, svc, RewardConfig(), bufs)
    assert all(r.worker == 0 for r in recs3)        # only worker 0 still live
    while not engine.done:
        engine.step(agent, svc, RewardConfig(), bufs)
    assert len(bufs[0]) == 2 * 3 and len(bufs[1]) == 2  # every transition landed


def test_ragged_fleet_keeps_dense_shape_on_fleet_path():
    """A worker dying mid-episode must not change the dense [W, C, D] jit
    shape: dead rows zero out, capacity is sticky."""
    tr = _trainer("episode", "fleet")
    tr.reserve_candidates(200)                      # settle capacity up front
    engine = tr.engine
    engine.reset()
    engine.step(tr._fleet_policy, tr.service, tr.reward_cfg, tr.buffers)
    # whichever fleet jit the configured acting mode dispatches through
    # (packed by default), its shape set must not grow when workers die
    fleet_jits = (tr._fleet_q, tr._fleet_q_packed)
    n_shapes = tuple(jit_cache_size(f) for f in fleet_jits)
    assert sum(n_shapes) > 0                        # one of them actually ran
    for s in engine.workers[0]:                     # worker 0 finishes early
        s.steps_left = 0
    while not engine.done:
        engine.step(tr._fleet_policy, tr.service, tr.reward_cfg, tr.buffers)
    assert tuple(jit_cache_size(f) for f in fleet_jits) == n_shapes


def test_zero_candidate_slots_die_cleanly(monkeypatch):
    """A slot whose molecule has no legal action stops acting; its in-flight
    transition is completed with an EMPTY successor set and still reaches
    the replay buffer (the double-DQN max values it at zero)."""
    import repro.core.rollout as rollout_mod
    engine = RolloutEngine([[MOLS[0], MOLS[1]]], EnvConfig(max_steps=3))
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    svc, bufs = _OracleService(), [ReplayBuffer(100, seed=2)]
    engine.step(agent, svc, RewardConfig(), bufs)
    # every molecule now has zero candidates: both slots die at the end of
    # the next step even though steps_left would allow a third step
    monkeypatch.setattr(rollout_mod, "enumerate_actions", lambda m, **kw: [])
    engine.step(agent, svc, RewardConfig(), bufs)
    assert engine.done
    assert len(bufs[0]) == 4                        # 2 slots x 2 steps, none lost
    tail = bufs[0]._items[-2:]
    assert all(t.next_fps.shape[0] == 0 and not t.done for t in tail)
    batch = bufs[0].sample(8, max_candidates=16)    # trainable as-is
    assert np.isfinite(batch["rewards"]).all()


def test_all_slots_dead_at_reset(monkeypatch):
    """No legal action anywhere on step one: the engine finishes without a
    single Q dispatch or property batch instead of crashing."""
    import repro.core.rollout as rollout_mod
    monkeypatch.setattr(rollout_mod, "enumerate_actions", lambda m, **kw: [])
    engine = RolloutEngine([[MOLS[0]], [MOLS[1]]], EnvConfig(max_steps=3))
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    svc = _OracleService()
    assert engine.step(agent, svc, RewardConfig(), None) == []
    assert engine.done and svc.n_calls == 0 and agent.n_q_dispatches == 0


def test_pipelined_matches_reference_under_zero_candidate_deaths(monkeypatch):
    """The overlap path must keep the identical transition stream even when
    slots die mid-episode from candidate exhaustion."""
    import repro.core.rollout as rollout_mod
    real = rollout_mod.enumerate_actions

    def gated(m, **kw):   # molecules that grew past 8 heavy atoms are stuck
        return [] if len(m.elements) > 8 else real(m, **kw)

    monkeypatch.setattr(rollout_mod, "enumerate_actions", gated)
    streams = []
    for pipelined in (False, True):
        engine = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2], MOLS[3]]],
                               EnvConfig(max_steps=4))
        agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=7,
                         network=QNetwork(hidden=(32,)))
        bufs = [ReplayBuffer(100, seed=11), ReplayBuffer(100, seed=12)]
        engine.run_episode(agent, _OracleService(), RewardConfig(), bufs,
                           pipelined=pipelined)
        streams.append([_transitions(b) for b in bufs])
    assert streams[0] == streams[1]


def test_quarantined_fleet_revives_clean_next_episode():
    """Self-healing fleet: slots quarantined by terminal chem faults are
    revived by the next episode's reset, and once the fault clears the
    revived fleet's transition stream is BIT-identical to a fresh engine's
    — quarantine leaves no residue in the engine."""
    from repro.core.faults import FaultPlan, FaultRule

    plan = FaultPlan([FaultRule(site="chem", kind="transient", rate=1.0,
                                fail_attempts=1000)], seed=0)
    engine = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2], MOLS[3]]],
                           EnvConfig(max_steps=3), chem="incremental",
                           fault_plan=plan)
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    svc = _OracleService()
    bufs = [ReplayBuffer(100, seed=2), ReplayBuffer(100, seed=3)]
    recs = engine.run_episode(agent, svc, RewardConfig(), bufs)
    st = engine.fault_stats()
    assert st["n_quarantined"] == 4          # rate=1.0: the whole fleet died
    assert recs == [] and all(len(b) == 0 for b in bufs)
    assert all(i["site"] == "chem" and i["action"] == "quarantined"
               for i in st["incidents"])

    engine.fault_plan = None                 # the fault clears; fleet revives

    def episode(eng):
        ag = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=7,
                      network=QNetwork(hidden=(32,)))
        bs = [ReplayBuffer(100, seed=11), ReplayBuffer(100, seed=12)]
        rs = eng.run_episode(ag, _OracleService(), RewardConfig(), bs)
        return rs, [_transitions(b) for b in bs]

    recs2, streams2 = episode(engine)
    fresh = RolloutEngine([[MOLS[0], MOLS[1]], [MOLS[2], MOLS[3]]],
                          EnvConfig(max_steps=3), chem="incremental")
    recs3, streams3 = episode(fresh)
    assert {(r.worker, r.slot) for r in recs2} == \
        {(0, 0), (0, 1), (1, 0), (1, 1)}     # every slot is acting again
    assert streams2 == streams3
    assert engine.fault_stats()["n_quarantined"] == 4   # no new deaths


# ------------------------------------------------------------------ #
# mesh padding: dead workers beyond the live fleet (engine-level; the
# trainer-level nd > 1 equivalence lives in tests/multidevice)
# ------------------------------------------------------------------ #
def test_engine_mesh_padding_is_transition_invisible():
    """An engine padded to a larger mesh width (dead workers own no slots)
    must produce the exact transition stream of the unpadded engine, accept
    per-LIVE-worker buffer lists, and never write a dead worker's buffer."""
    streams = []
    for pad in (None, 4):
        engine = RolloutEngine([[MOLS[0]], [MOLS[1]]], EnvConfig(max_steps=3),
                               pad_workers_to=pad)
        agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=3,
                         network=QNetwork(hidden=(32,)))
        bufs = [ReplayBuffer(100, seed=5), ReplayBuffer(100, seed=6)]
        recs = engine.run_episode(agent, _OracleService(), RewardConfig(), bufs)
        assert engine.n_workers == (pad or 2)
        assert engine.n_live_workers == 2
        assert {r.worker for r in recs} == {0, 1}       # dead workers silent
        streams.append([_transitions(b) for b in bufs])
    assert streams[0] == streams[1]


def test_engine_pad_buffers_validates_length():
    engine = RolloutEngine([[MOLS[0]], [MOLS[1]]], EnvConfig(max_steps=2),
                           pad_workers_to=4)
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=3,
                     network=QNetwork(hidden=(32,)))
    with pytest.raises(ValueError, match="buffers"):
        engine.step(agent, _OracleService(), RewardConfig(),
                    [ReplayBuffer(10, seed=1)] * 3)     # neither live nor padded
    with pytest.raises(ValueError, match="pad_workers_to"):
        RolloutEngine([[MOLS[0]], [MOLS[1]]], pad_workers_to=1)


# ------------------------------------------------------------------ #
# capacity ladders (pure)
# ------------------------------------------------------------------ #
def test_candidate_capacity_table_scales_with_fleet():
    small, big = candidate_capacity_table(4), candidate_capacity_table(512)
    assert len(big) > len(small)                    # finer rungs at large W
    for table in (small, big):
        assert all(b > a for a, b in zip(table, table[1:]))
        assert candidate_capacity(1, table) == table[0]
        assert candidate_capacity(table[-1] + 1, table) >= table[-1] + 1
    # big-fleet rung ratio is bounded: never pads 2x past the previous rung
    ratios = [b / a for a, b in zip(big, big[1:])]
    assert max(ratios[2:]) <= 1.5


def test_service_capacity_table_snaps_to_fleet_batch():
    from repro.predictors.service import capacity_table
    table = capacity_table(512)
    assert table[-1] == 512
    # dedupe drift just below W reuses the exact reserved shape
    assert next(c for c in table if c >= 500) == 512
    assert next(c for c in table if c >= 412) == 512
    table64 = capacity_table(64)
    assert table64[-1] == 64 and table64[0] == 1


# ------------------------------------------------------------------ #
# fleet-sized fingerprint batches: chunked pass is bit-identical
# ------------------------------------------------------------------ #
def test_chunked_fingerprints_bit_identical():
    from repro.chem.actions import enumerate_actions
    from repro.chem.fingerprint import batch_morgan_fingerprints
    cands = [a.result for m in MOLS for a in enumerate_actions(m)]
    assert len(cands) > 64  # spans several chunks below
    ref = batch_morgan_fingerprints(cands, chunk=0)
    for chunk in (17, 64):  # uneven + even chunking, distinct per-chunk m_max
        np.testing.assert_array_equal(
            batch_morgan_fingerprints(cands, chunk=chunk), ref)
    np.testing.assert_array_equal(
        batch_morgan_fingerprints(cands, counts=True, chunk=31),
        batch_morgan_fingerprints(cands, counts=True, chunk=0))


# ------------------------------------------------------------------ #
# incremental candidate chemistry: engine fps, fleet-wide chem cache
# ------------------------------------------------------------------ #
def _fresh_engine(chem, mols=None, max_steps=3):
    return RolloutEngine([list(mols or MOLS[:2])], EnvConfig(max_steps=max_steps),
                         chem=chem)


def test_chem_incremental_candidate_fps_bit_identical():
    """Stepping the full-recompute and incremental engines in lockstep, the
    per-slot candidate fingerprints (dense AND packed rows) are bit-equal at
    every step — the acceptance pin for the §3.6 incremental pass."""
    engines, agents = {}, {}
    for chem in CHEM_MODES:
        engines[chem] = _fresh_engine(chem)
        agents[chem] = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=5,
                                network=QNetwork(hidden=(32,)))
    svc = _OracleService()
    while not engines["full"].done:
        for chem in CHEM_MODES:
            engines[chem].step(agents[chem], svc, RewardConfig())
        for sf, si in zip(engines["full"].workers[0],
                          engines["incremental"].workers[0]):
            np.testing.assert_array_equal(sf.cand_fps, si.cand_fps)
            np.testing.assert_array_equal(sf.cand_fps_packed, si.cand_fps_packed)
            assert [a.detail for a in sf.candidates] == \
                   [a.detail for a in si.candidates]


def test_packed_candidate_rows_match_pack_fp():
    """The one-packbits-per-batch satellite: every packed row equals the
    seed's per-candidate pack_fp, and pending successors alias those rows."""
    from repro.core.replay import pack_fp
    engine = _fresh_engine("full")
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=2,
                     network=QNetwork(hidden=(32,)))
    engine.step(agent, _OracleService(), RewardConfig())
    for s in engine.workers[0]:
        assert s.cand_fps_packed.shape == (s.cand_fps.shape[0], 2048 // 8)
        for r in range(s.cand_fps.shape[0]):
            np.testing.assert_array_equal(s.cand_fps_packed[r],
                                          pack_fp(s.cand_fps[r]))
        if s.pending is not None and s.pending.next_fps is not None:
            assert s.pending.next_fps is s.cand_fps_packed


def test_chem_cache_shared_across_slots_and_episodes():
    """Two slots starting from the SAME molecule chemistry-dedupe in batch;
    restarting the episode serves step-1 enumerations from the cache."""
    engine = RolloutEngine([[MOLS[0]], [MOLS[0]]], EnvConfig(max_steps=2),
                           chem="incremental")
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=9,
                     network=QNetwork(hidden=(32,)))
    svc = _OracleService()
    engine.run_episode(agent, svc, RewardConfig())
    st = engine.chem_stats()
    assert st["entries"] < st["hits"] + st["misses"]  # in-batch dedup worked
    # second episode revisits the shared initial molecule: pure hits at reset
    h0 = st["hits"]
    engine.reset()
    engine.step(agent, svc, RewardConfig())
    assert engine.chem_stats()["hits"] >= h0 + 2


def test_chem_cache_relabel_guard():
    """Isomorphic but differently-labelled parents share a canonical key but
    must NOT share cached candidates (enumeration order depends on the
    labelling): the signature guard forces a recompute, counted separately."""
    from repro.chem.fingerprint import batch_morgan_fingerprints
    from repro.chem.molecule import Molecule
    mol = MOLS[1]
    perm = np.random.default_rng(3).permutation(mol.num_atoms)
    twin = Molecule(mol.elements[perm], mol.bonds[np.ix_(perm, perm)])
    assert twin.canonical_key() == mol.canonical_key()
    engine = _fresh_engine("incremental")
    engine._compute_enum([mol])
    acts, fps, packed = engine._compute_enum([twin])[0]
    st = engine.chem_stats()
    assert st["misses"] == 1 and st["relabel_misses"] == 1 and st["hits"] == 0
    np.testing.assert_array_equal(
        fps, batch_morgan_fingerprints([a.result for a in acts]))


def test_chem_stats_time_accounting():
    engine = _fresh_engine("incremental")
    agent = DQNAgent(DQNConfig(epsilon_initial=1.0), seed=1,
                     network=QNetwork(hidden=(32,)))
    engine.step(agent, _OracleService(), RewardConfig())
    st = engine.chem_stats()
    assert st["mode"] == "incremental"
    assert st["enum_s"] > 0 and st["fp_s"] > 0 and st["env_steps"] == 1
    engine.reset_chem_stats()
    assert engine.chem_stats()["enum_s"] == 0.0


# ------------------------------------------------------------------ #
# PropertyService: dedupe, call accounting, collisions, bucket choice
# ------------------------------------------------------------------ #
@pytest.fixture(scope="module")
def tiny_service():
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS
    from repro.predictors.service import PropertyService
    bde_model, ip_model = AlfabetS(), AIMNetS()
    return PropertyService(
        bde_model, bde_model.init(jax.random.PRNGKey(0)),
        ip_model, ip_model.init(jax.random.PRNGKey(1)))


def test_service_dedupes_within_batch(tiny_service):
    svc = tiny_service
    svc.cache.reset_stats()
    n_mols0 = svc.n_predictor_mols
    a, b = MOLS[0], MOLS[1]
    props = svc.predict([a, b, a, a])                # duplicates in ONE batch
    assert svc.n_predictor_mols == n_mols0 + 2       # featurized a, b once each
    assert svc.cache.misses == 4 and svc.cache.hits == 0
    assert props[0].bde == props[2].bde == props[3].bde
    assert props[0].ip == props[2].ip == props[3].ip
    # second call is pure cache
    n_batches = svc.n_predictor_batches
    props2 = svc.predict([a, b])
    assert svc.n_predictor_batches == n_batches
    assert svc.cache.hits == 2
    assert props2[0].bde == props[0].bde


def test_service_predict_call_accounting(tiny_service):
    """n_predict_calls counts predict() ENTRIES (one per fleet step), not
    molecules; n_predictor_batches counts jit'd model batches (cache hits
    and empty calls run none)."""
    svc = tiny_service
    calls0, batches0 = svc.n_predict_calls, svc.n_predictor_batches
    svc.predict([MOLS[0], MOLS[1], MOLS[2]])         # possibly all cached
    svc.predict([MOLS[0]])
    svc.predict([])
    assert svc.n_predict_calls == calls0 + 3
    svc.predict([MOLS[0], MOLS[1]])                  # cached from above
    assert svc.n_predictor_batches <= batches0 + 1   # at most the first ran


def test_service_iso_key_collision_coalesces(tiny_service):
    """Colliding iso_keys coalesce: the later molecule is featurized ZERO
    times and inherits the earlier one's prediction (documented
    hash-collision semantics — iso_key is an isomorphism-invariant hash,
    not a perfect identifier)."""
    svc = tiny_service
    a = from_smiles("C1=CC=CC=C1O")
    b = from_smiles("CC1=CC(C)=CC(C)=C1O")
    assert a.iso_key() != b.iso_key()
    a._iso_cache = b._iso_cache = 0xC0111DE          # force a fresh colliding key
    n_mols0 = svc.n_predictor_mols
    pa, pb = svc.predict([a, b])
    assert svc.n_predictor_mols == n_mols0 + 1       # b never featurized
    assert pb.ip == pa.ip                            # b coalesced onto a's slot
    assert pb.bde == pa.bde                          # (both have an O-H bond)


def test_fleet_sized_batch_picks_one_bucket_no_recompile_on_second_call():
    """A W=512-sized predict batch pads to the single reserved bucket, and a
    second fleet-sized batch (slightly smaller after dedupe) reuses the same
    compiled shape — zero recompiles."""
    from repro.core.jit_stats import jit_cache_size
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS
    from repro.predictors.service import PropertyService
    bde_model, ip_model = AlfabetS(hidden=16, rounds=1), AIMNetS(hidden=16)
    svc = PropertyService(
        bde_model, bde_model.init(jax.random.PRNGKey(0)),
        ip_model, ip_model.init(jax.random.PRNGKey(1)),
        max_atoms=12, cache=None)
    svc.reserve(512)                                 # what the trainer does at W=512

    def fresh(n, tag):
        out = []
        for i in range(n):
            m = from_smiles("C1=CC=CC=C1O")
            m._iso_cache = tag * 10_000 + i          # force distinct iso keys
            out.append(m)
        return out

    svc.predict(fresh(512, 1))                       # the full fleet batch
    assert svc.n_predictor_batches == 1
    assert jit_cache_size(svc._bde_apply) == 1
    assert jit_cache_size(svc._ip_apply) == 1
    svc.predict(fresh(490, 2))                       # post-dedupe drift
    assert svc.n_predictor_batches == 2
    assert jit_cache_size(svc._bde_apply) == 1       # same bucket, no recompile
    assert jit_cache_size(svc._ip_apply) == 1
