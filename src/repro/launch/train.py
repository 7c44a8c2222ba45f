"""Training launcher.

Two modes, mirroring the framework's two tiers:

* ``--mode rl`` (default; the paper): distributed DA-MolDQN over an
  antioxidant dataset — workers on the host mesh, per-episode param sync,
  checkpointing, OFR/reward logging.

* ``--mode lm --arch <id>``: train a (reduced or full) model-zoo backbone
  on the SMILES LM corpus with the same train_step the dry-run lowers —
  on CPU use ``--reduced`` (the full configs only make sense on the
  production mesh).

    PYTHONPATH=src python -m repro.launch.train --mode rl --episodes 40
    PYTHONPATH=src python -m repro.launch.train --mode lm --arch stablelm-1.6b \
        --reduced --steps 50
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    from repro.core.distributed import LEARNER_MODES, REPLAY_MODES, ROLLOUT_MODES
    from repro.data.datasets import DATASETS

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("rl", "lm"), default="rl")
    # rl args
    ap.add_argument("--episodes", type=int, default=40)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--mols-per-worker", type=int, default=4)
    ap.add_argument("--sync", choices=("episode", "step"), default="episode")
    ap.add_argument("--rollout", choices=ROLLOUT_MODES, default="fleet",
                    help="acting path (see core.distributed)")
    ap.add_argument("--learner", choices=LEARNER_MODES, default="packed",
                    help="replay->update path (see core.distributed)")
    ap.add_argument("--replay", choices=REPLAY_MODES, default="uniform",
                    help="replay sampling: uniform (reference) or "
                         "prioritized (proportional PER)")
    ap.add_argument("--priority-alpha", type=float, default=0.6)
    ap.add_argument("--priority-beta0", type=float, default=0.4)
    ap.add_argument("--dataset", choices=sorted(DATASETS), default=None,
                    help="multi-start episode stream: draw every episode's "
                         "start molecules from this seeded dataset cursor "
                         "(default: fixed train-split batch)")
    ap.add_argument("--dataset-size", type=int, default=None,
                    help="dataset pool size (default: the dataset's own)")
    ap.add_argument("--scenarios", default=None,
                    help="comma list of scenario-registry names cycled "
                         "across workers (configs/scenarios.py), e.g. "
                         "'antioxidant,qed'; default: the Eq. 1 "
                         "antioxidant objective on every worker")
    ap.add_argument("--ckpt-dir", default=".cache/rl_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=5,
                    help="full trainer-state checkpoint every N episodes "
                         "(bit-exact resume granularity)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from --ckpt-dir and "
                         "continue; the continued run is bit-identical to "
                         "one that never stopped (docs/robustness.md)")
    # lm args
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    enable_compile_cache()
    if args.mode == "rl":
        train_rl(args)
    else:
        train_lm(args)


def train_rl(args) -> None:
    from repro.core import DQNConfig, EnvConfig, RewardConfig, TrainerConfig
    from repro.core.distributed import DistributedTrainer, greedy_optimize, \
        optimization_failure_rate
    from repro.data.datasets import antioxidant_dataset, dataset_property_table, \
        train_test_split
    from repro.data.datasets import load_dataset
    from repro.predictors.training import trained_service

    service, _ = trained_service(verbose=True)
    n_mols = args.workers * args.mols_per_worker
    if args.dataset is not None:
        # multi-start: reward normalisation and evaluation come from the
        # streamed pool itself; the trainer re-draws starts every episode
        pool = load_dataset(args.dataset, count=args.dataset_size)
        train, molecules, dataset_pool = pool, None, pool
    else:
        ds = antioxidant_dataset(600)
        train, test = train_test_split(ds)
        molecules, dataset_pool = train[:n_mols], None
    props = dataset_property_table(train)
    rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])

    cfg = TrainerConfig(
        n_workers=args.workers, mols_per_worker=args.mols_per_worker,
        episodes=args.episodes, sync_mode=args.sync, rollout=args.rollout,
        learner=args.learner, replay=args.replay,
        priority_alpha=args.priority_alpha, priority_beta0=args.priority_beta0,
        dataset=args.dataset, dataset_size=args.dataset_size,
        scenarios=(tuple(args.scenarios.split(","))
                   if args.scenarios else None),
        dqn=DQNConfig(epsilon_decay=0.97))
    trainer = DistributedTrainer(cfg, molecules, service, rcfg,
                                 dataset_pool=dataset_pool)
    mgr = CheckpointManager(args.ckpt_dir)
    if args.resume:
        ep0 = trainer.restore_checkpoint(mgr)
        print(f"resumed from episode {ep0} ({args.ckpt_dir})", flush=True)

    t0 = time.time()
    while trainer.episode < args.episodes:
        st = trainer.train_episode()
        ep = st["episode"]
        if ep % 5 == 0 or ep == args.episodes:
            print(f"[ep {ep:4d}] reward {st['mean_final_reward']:8.3f} "
                  f"loss {st['loss']:10.4f} eps {st['epsilon']:.3f} "
                  f"({time.time()-t0:.0f}s)", flush=True)
        if ep % max(1, args.ckpt_every) == 0 or ep == args.episodes:
            # FULL trainer state (params, opt, replay rings, RNGs, dataset
            # cursor) — what --resume restores bit-exactly
            trainer.save_checkpoint(mgr)

    agent = trainer.as_agent(epsilon=0.0)
    recs = greedy_optimize(agent, list(train[:n_mols]), service, rcfg, cfg.env)
    print(f"train-set OFR: {optimization_failure_rate(recs):.3f}")
    print(f"cache hit rate: {service.cache.hit_rate:.3f}")


def train_lm(args) -> None:
    from repro.chem.smiles import canonical_smiles
    from repro.configs import get_config
    from repro.data.datasets import antioxidant_dataset
    from repro.data.pipeline import lm_batches_from_smiles
    from repro.data.tokenizer import SmilesTokenizer
    from repro.launch.steps import make_train_step
    from repro.models import init_params

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tok = SmilesTokenizer()
    mols = antioxidant_dataset(256)
    smiles = [canonical_smiles(m) for m in mols]
    batches = lm_batches_from_smiles(smiles, tok, args.batch, args.seq)

    params = init_params(cfg, jax.random.PRNGKey(0))
    step, opt = make_train_step(cfg)
    opt_state = opt.init(params)
    jstep = jax.jit(step)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i, batch in zip(range(args.steps), batches):
        if cfg.family == "encdec":
            batch["frames"] = rng.standard_normal(
                (args.batch, cfg.encdec.n_frames, cfg.d_model)).astype(np.float32)
        if cfg.family == "vlm":
            batch["patches"] = rng.standard_normal(
                (args.batch, cfg.vlm.n_patches, cfg.vlm.vision_dim)).astype(np.float32)
        params, opt_state, loss = jstep(params, opt_state, batch)
        if (i + 1) % 10 == 0:
            print(f"[step {i+1:4d}] loss {float(loss):.4f} ({time.time()-t0:.0f}s)",
                  flush=True)
    print(json.dumps({"final_loss": float(loss), "steps": args.steps}))


if __name__ == "__main__":
    main()
