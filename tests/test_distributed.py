"""Distributed trainer: sync semantics, regression of the paper's claims in
miniature, and substrate (optim / checkpoint / roofline parsing)."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.chem.smiles import from_smiles
from repro.core import DQNConfig, EnvConfig, RewardConfig, TrainerConfig
from repro.core.agent import QNetwork
from repro.core.distributed import DistributedTrainer

from conftest import OracleService as _OracleService

MOLS = [from_smiles(s) for s in
        ("C1=CC=CC=C1O", "CC1=CC(C)=CC(C)=C1O", "CC1=CC=CC=C1O", "OC1=CC=CC=C1O")]


def _trainer(sync_mode: str, episodes: int = 3) -> DistributedTrainer:
    cfg = TrainerConfig(
        n_workers=2, mols_per_worker=2, episodes=episodes, sync_mode=sync_mode,
        updates_per_episode=2, train_batch_size=8, max_candidates=16,
        dqn=DQNConfig(epsilon_decay=0.9), env=EnvConfig(max_steps=3), seed=0)
    return DistributedTrainer(cfg, MOLS, _OracleService(), RewardConfig(),
                              network=QNetwork(hidden=(64, 32)))


def _worker_params_equal(trainer) -> bool:
    flat = jax.tree_util.tree_leaves(trainer.params)
    return all(bool(jnp.allclose(x[0], x[i], atol=1e-6))
               for x in flat for i in range(1, x.shape[0]))


def test_episode_sync_equalises_workers():
    tr = _trainer("episode")
    tr.train(2)
    assert _worker_params_equal(tr)


def test_ddp_keeps_workers_identical():
    tr = _trainer("step")
    tr.train(2)
    assert _worker_params_equal(tr)


def test_modes_diverge_before_sync():
    """Local updates differ across workers until the episode sync."""
    tr = _trainer("episode")
    # roll + update WITHOUT sync by invoking internals
    for w, env in enumerate(tr.envs):
        env.run_episode(tr._views[w], tr.service, tr.reward_cfg, tr.buffers[w])
    batch = tr._stacked_sample()
    p2, _, _, _ = tr._local_update(tr.params, tr.target_params, tr.opt_state, batch)
    leaves = jax.tree_util.tree_leaves(p2)
    assert any(not bool(jnp.allclose(x[0], x[1], atol=1e-7)) for x in leaves)


def test_as_agent_roundtrip():
    tr = _trainer("episode")
    tr.train(1)
    agent = tr.as_agent(epsilon=0.0)
    q = agent.q_values(np.zeros((4, 2049), np.float32))
    assert q.shape == (4,) and np.isfinite(q).all()


def test_greedy_optimize_and_ofr():
    from repro.core.distributed import greedy_optimize, optimization_failure_rate
    tr = _trainer("episode")
    tr.train(1)
    recs = greedy_optimize(tr.as_agent(0.0), MOLS, _OracleService(), RewardConfig(),
                           EnvConfig(max_steps=3))
    assert len(recs) == len(MOLS)
    ofr = optimization_failure_rate(recs)
    assert 0.0 <= ofr <= 1.0


# ------------------------------------------------------------------ #
# mesh padding arithmetic + trainer accounting (nd = 1 view; the nd > 1
# equivalence itself is pinned by the tests/multidevice subprocess suite)
# ------------------------------------------------------------------ #
def test_padded_worker_count_arithmetic():
    from types import SimpleNamespace
    from repro.launch.mesh import padded_worker_count
    mesh4 = SimpleNamespace(devices=np.empty(4))
    assert padded_worker_count(6, mesh4) == 8
    assert padded_worker_count(8, mesh4) == 8
    assert padded_worker_count(1, mesh4) == 4
    mesh1 = SimpleNamespace(devices=np.empty(1))
    assert padded_worker_count(7, mesh1) == 7
    with pytest.raises(ValueError, match="positive"):
        padded_worker_count(0, mesh4)


def test_trainer_uses_host_mesh_and_pads_to_it():
    """The trainer's default mesh is launch.mesh.make_host_mesh (ONE
    construction code path) and its padded width tiles that mesh; on this
    1-device host any W — including odd ones that a multi-device mesh
    would pad — stays unpadded."""
    from repro.launch.mesh import make_host_mesh
    tr = _trainer("episode")
    assert tr.mesh.axis_names == make_host_mesh().axis_names == ("data",)
    assert tr.mesh.devices.size == make_host_mesh().devices.size
    assert tr.n_live_workers == tr.cfg.n_workers == 2
    assert tr.n_padded_workers == tr.engine.n_workers == 2
    assert tr.n_padded_workers % tr.mesh.devices.size == 0


def test_host_mesh_axes_are_auto():
    """``jax.make_mesh`` builds Explicit axes, under which the default
    path's vmaps refuse operands that are not placed like the stacked
    params; the host mesh must be Auto at every nd."""
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    assert make_host_mesh().axis_types == (AxisType.Auto,)
    assert make_host_mesh(1).axis_types == (AxisType.Auto,)


def test_trainer_state_starts_sharded_from_worker_zero():
    """Params, target and Adam state are born on the fleet sharding (never
    whole on one device), every worker holding worker 0's initial weights
    and zero moments."""
    from repro.launch.mesh import fleet_sharding
    tr = _trainer("episode")
    want = fleet_sharding(tr.mesh)
    for tree in (tr.params, tr.target_params, tr.opt_state):
        for x in jax.tree_util.tree_leaves(tree):
            assert x.sharding.is_equivalent_to(want, x.ndim)
    keys = jax.random.split(jax.random.PRNGKey(tr.cfg.seed), tr.cfg.n_workers)
    ref = tr.network.init(keys[0])
    for x, r in zip(jax.tree_util.tree_leaves(tr.params),
                    jax.tree_util.tree_leaves(ref)):
        for w in range(tr.n_padded_workers):
            np.testing.assert_array_equal(np.asarray(x[w]), np.asarray(r))
    for x in jax.tree_util.tree_leaves((tr.opt_state.mu, tr.opt_state.nu,
                                        tr.opt_state.step)):
        assert not np.asarray(x).any()


def test_loss_scalar_ignores_dead_padding_rows():
    tr = _trainer("episode")
    tr.n_live_workers = 2                      # live prefix of a padded vector
    assert tr._loss_scalar(np.asarray([1.0, 3.0, 99.0, -7.0])) == 2.0


# ------------------------------------------------------------------ #
# optimizer / checkpoint substrate
# ------------------------------------------------------------------ #
def test_adam_minimises_quadratic():
    from repro.optim import adam
    from repro.optim.adam import apply_updates
    opt = adam(0.1)
    params = {"x": jnp.asarray(5.0), "y": jnp.asarray(-3.0)}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree_util.tree_map(lambda v: 2 * v, params)
        updates, state = opt.update(grads, state, params)
        params = apply_updates(params, updates)
    assert abs(float(params["x"])) < 1e-2 and abs(float(params["y"])) < 1e-2


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import load_pytree, save_pytree
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": np.asarray(3, np.int32)}}
    path = str(tmp_path / "x.npz")
    save_pytree(path, tree)
    out = load_pytree(path, tree)
    np.testing.assert_array_equal(out["a"], tree["a"])
    assert int(out["b"]["c"]) == 3


def test_checkpoint_manager_rotation(tmp_path):
    from repro.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), max_to_keep=2)
    tree = {"w": np.zeros(3, np.float32)}
    for s in (1, 2, 3):
        mgr.save(s, tree)
    assert mgr.latest_step() == 3
    assert sorted(p.name for p in tmp_path.glob("ckpt_*.npz")) == \
        ["ckpt_2.npz", "ckpt_3.npz"]
    assert (tmp_path / "LATEST").read_text().strip() == "3"
    step, out = mgr.restore(tree)
    assert step == 3


# ------------------------------------------------------------------ #
# roofline HLO walker (pinned against known modules)
# ------------------------------------------------------------------ #
def test_hlo_walker_scan_trip_count():
    from repro.roofline.hlo_walk import aggregate

    def body(h, w):
        return jnp.tanh(h @ w), None

    def f(h, ws):
        h, _ = jax.lax.scan(body, h, ws)
        return h

    hs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    agg = aggregate(jax.jit(f).lower(hs, ws).compile().as_text())
    assert agg["flops"] == 7 * 2 * 128 ** 3


def test_hlo_walker_nested_scan():
    from repro.roofline.hlo_walk import aggregate

    def f(h, ws):
        def outer(h, w):
            def inner(hh, _):
                return jnp.tanh(hh @ w), None
            hh, _ = jax.lax.scan(inner, h, None, length=3)
            return hh, None
        h, _ = jax.lax.scan(outer, h, ws)
        return h

    hs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    agg = aggregate(jax.jit(f).lower(hs, ws).compile().as_text())
    assert agg["flops"] == 15 * 2 * 64 ** 3


def test_estimate_hbm_shapes():
    from repro.configs import INPUT_SHAPES, get_config
    from repro.roofline.analysis import estimate_hbm_per_chip
    cfg = get_config("yi-34b")
    est = estimate_hbm_per_chip(cfg, INPUT_SHAPES["train_4k"], tp=16, dp=16,
                                fsdp=True, microbatches=16)
    assert 0 < est["total"] < 16 * 2 ** 30
    est_d = estimate_hbm_per_chip(cfg, INPUT_SHAPES["decode_32k"], tp=16, dp=16)
    assert "cache" in est_d and est_d["total"] > 0
