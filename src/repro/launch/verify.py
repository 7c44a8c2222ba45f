"""Multi-device truth run: one (rollout x learner x chem x sync) cell of the
equivalence matrix, executed on an nd-device submesh of a forced host pool.

The sharded trainer paths (``fleet_sharded`` acting, the packed ``shard_map``
learner, the DDP/episode mean syncs) are only *believed* correct until
they run on a mesh with nd > 1 — ``--xla_force_host_platform_device_count``
makes any CPU host into that mesh, but the flag must be set in ``XLA_FLAGS``
**before jax initialises** (the ``launch/dryrun.py`` idiom), hence this
subprocess runner: each invocation is one fresh process, one scenario, one
``.npz`` report.

    PYTHONPATH=src python -m repro.launch.verify --nd 2 --out /tmp/nd2.npz \
        --rollout fleet_sharded --learner packed --chem incremental

Every invocation forces the SAME device pool (``--device-pool``, default 8)
and sizes the trainer's mesh as a SUBMESH over the first ``--nd`` devices.
This is load-bearing for bit-equality: XLA-CPU picks matmul kernels and
thread partitions per *client* device count (a plain one-device f32 GEMM
changes its last bits between a 1-device and a 4-device client), so the
nd=1 reference and the nd=4 run must share one client configuration for
their difference to be *the sharding*, not the backend.

The report carries everything the equivalence matrix pins across nd:

* a per-worker digest of the full replay transition stream,
* the loss and mean-final-reward trajectories,
* every live worker's parameter leaves (exact bits),
* compile accounting (``jit_stats``): compiles during warmup vs compiles
  during the measured episodes (the recompiles-after-warmup gate is 0).

tests/multidevice compares these reports at nd in {1, 2, 4} (plus the
ragged W-not-divisible-by-nd fleets that pad to the mesh with dead slots)
under ``assert_nd_equivalent``: transitions and rewards bit-identical,
losses and parameters within ``ND_RTOL``/``ND_ATOL`` (see there for why).

PR-8 adds two robustness scenario families on the same runner:

* crash-resume: ``--ckpt-dir D`` checkpoints the FULL trainer state after
  every episode; ``--kill-at K`` additionally SIGKILLs the process after
  episode K's checkpoint (having first done post-checkpoint work the crash
  destroys); ``--resume`` restores the latest checkpoint and finishes the
  run, treating its first episode back as the compile-warmup window.  The
  resumed report must be BIT-identical (losses, rewards, transition
  digests, replay-state digests, parameter leaves) to a straight-through
  reference — and carry 0 recompiles after warmup on the resumed process.
* fault injection: ``--faults predict,chem`` arms a seeded FaultPlan
  (property-service timeouts, chem exceptions, pipelined-thread crashes)
  behind a ResilientService retry wrapper.  With faults inside the retry
  budgets the report must be bit-identical to the fault-free run; the
  injected/retry counters in the report prove the faults actually fired.
"""

import os
import sys


DEFAULT_DEVICE_POOL = 8


def _flag_from_argv(name: str, default: int) -> int:
    for i, a in enumerate(sys.argv):
        if a == name and i + 1 < len(sys.argv):
            return int(sys.argv[i + 1])
        if a.startswith(name + "="):
            return int(a.split("=", 1)[1])
    return default


if __name__ == "__main__":
    # MUST precede every jax-importing module (jax locks the device count
    # on first init); deliberately OVERWRITES any inherited XLA_FLAGS so a
    # parent process pinned to a different device count cannot leak it into
    # this scenario.  This is a CPU-only tool: JAX_PLATFORMS is pinned to
    # the forced host pool too, so the concurrent children the equivalence
    # suite starts never reach for an accelerator.  Gated on script
    # execution so merely importing this module (e.g. the CI import
    # smoke-check) has no environment side effects.
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{_flag_from_argv('--device-pool', DEFAULT_DEVICE_POOL)}")
    os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import hashlib
import json
import signal


MOLS_SMILES = ("C1=CC=CC=C1O", "CC1=CC(C)=CC(C)=C1O",
               "CC1=CC=CC=C1O", "OC1=CC=CC=C1O")


def _transition_digest(buf) -> str:
    """SHA-256 over the buffer's full transition stream, every field that
    the in-process equivalence matrix compares (tests/test_rollout.py)."""
    import numpy as np

    h = hashlib.sha256()
    for t in buf._items:
        h.update(t.state_fp.tobytes())
        h.update(np.float64(t.steps_left_frac).tobytes())
        h.update(np.float64(t.reward).tobytes())
        h.update(b"\x01" if t.done else b"\x00")
        h.update(t.next_fps.tobytes())
        h.update(np.float64(t.next_steps_left_frac).tobytes())
    return h.hexdigest()


def _replay_state_digest(buf) -> str:
    """SHA-256 over the buffer's FULL serialised state: the SoA rings,
    per-slot priorities, cursor (pos/size), max-priority and the sample
    RNG — what the crash-resume matrix must reproduce bit-exactly."""
    import numpy as np

    h = hashlib.sha256()
    for k, v in sorted(buf.state_dict().items()):
        h.update(k.encode())
        h.update(np.ascontiguousarray(v).tobytes())
    return h.hexdigest()


# The cross-device-count contract.  XLA compiles the shard_map update per
# resident shard width W/nd, and reassociates float32 sums differently at
# different widths: on XLA-CPU (jax 0.9) an nd=4 run's parameters drift a
# few float32 ulps from nd=1 (5.8e-11 absolute on a layer-0 bias, 3.7e-9 on
# a weight) while every action, transition and reward stays identical.  So
# what the agent DID is compared bit for bit, and the float trajectories
# within a few-ulp tolerance.  Runs at the SAME nd stay bit-identical.
ND_RTOL = 1e-5
ND_ATOL = 1e-7


def assert_nd_equivalent(ref: dict, other: dict, ctx: str) -> None:
    """Hold ``other`` to the reference report ``ref`` (both from
    ``trainer_report``): transition digests, per-worker transition counts
    and rewards exactly; losses and every live worker's parameter leaves
    exactly at equal nd, within ``ND_RTOL``/``ND_ATOL`` across nd."""
    import numpy as np

    cross_nd = int(ref["n_devices"]) != int(other["n_devices"])
    close = (lambda a, b, msg: np.testing.assert_allclose(
        a, b, rtol=ND_RTOL, atol=ND_ATOL, err_msg=msg)) if cross_nd \
        else (lambda a, b, msg: np.testing.assert_array_equal(a, b, err_msg=msg))
    assert list(other["transition_digests"]) == list(ref["transition_digests"]), \
        f"{ctx}: transition streams diverged from the reference"
    np.testing.assert_array_equal(
        other["n_transitions"], ref["n_transitions"],
        err_msg=f"{ctx}: per-worker transition counts diverged")
    np.testing.assert_array_equal(
        other["rewards"], ref["rewards"],
        err_msg=f"{ctx}: reward trajectory diverged")
    close(other["losses"], ref["losses"], f"{ctx}: loss trajectory diverged")
    param_keys = sorted(k for k in ref if k.startswith("param_"))
    assert param_keys == sorted(k for k in other if k.startswith("param_"))
    for k in param_keys:
        close(other[k], ref[k], f"{ctx}: parameter leaf {k} diverged")


def trainer_report(tr) -> dict:
    """The comparable part of a run: mesh size, fleet widths, loss and
    reward trajectories (the trainer's checkpointed logs, so a resumed
    run's report carries the FULL trajectory), transition and replay-state
    digests, and every LIVE worker's parameter leaves (dead mesh-padding
    rows are sliced off so padded and unpadded reports align)."""
    import jax
    import numpy as np

    out = {
        "n_devices": np.int64(tr.mesh.devices.size),
        "n_live_workers": np.int64(tr.n_live_workers),
        "n_padded_workers": np.int64(tr.n_padded_workers),
        "losses": np.asarray(tr.loss_log, np.float64),
        "rewards": np.asarray(tr.reward_log, np.float64),
        "transition_digests": np.asarray(
            [_transition_digest(b) for b in tr.buffers]),
        "replay_state_digests": np.asarray(
            [_replay_state_digest(b) for b in tr.buffers]),
        "n_transitions": np.asarray([len(b) for b in tr.buffers], np.int64),
    }
    for i, leaf in enumerate(jax.tree_util.tree_leaves(tr.params)):
        out[f"param_{i}"] = np.asarray(leaf)[: tr.n_live_workers]
    return out


def _build_fault_plan(args):
    """Seeded FaultPlan from the --faults site list (None when unarmed)."""
    if not args.faults:
        return None
    from repro.core.faults import FaultPlan, FaultRule
    rules = []
    for site in args.faults.split(","):
        site = site.strip()
        if site == "predict":
            # property-service timeouts on a counter schedule, absorbed by
            # the ResilientService retry budget
            rules.append(FaultRule(site="predict", kind="timeout",
                                   every=args.fault_every,
                                   fail_attempts=args.fault_attempts))
        elif site == "chem":
            # content-keyed transient chem exceptions, retried in place
            rules.append(FaultRule(site="chem", kind="transient",
                                   rate=args.fault_rate,
                                   fail_attempts=args.fault_attempts))
        elif site == "pipeline":
            rules.append(FaultRule(site="pipeline", kind="transient",
                                   every=args.fault_every,
                                   fail_attempts=args.fault_attempts))
        else:
            raise SystemExit(f"FAIL: unknown fault site {site!r}")
    return FaultPlan(rules, seed=args.fault_seed)


def run_scenario(args) -> dict:
    """Build the trainer on the forced mesh, train warmup + measured
    episodes, and return the report arrays (see module docstring)."""
    import jax
    import numpy as np

    from repro.chem.smiles import from_smiles
    from repro.core.agent import DQNConfig, QNetwork
    from repro.core.distributed import DistributedTrainer, TrainerConfig
    from repro.core.jit_stats import RecompileCounter
    from repro.core.rollout import EnvConfig
    from repro.core.reward import RewardConfig
    from repro.launch.mesh import make_host_mesh
    # the SHARED deterministic property stub (same class the tier-1 test
    # matrices and chem benches use): jit-free, so the trainer's own jits
    # are the only compiles, and identical answers in every process
    from repro.predictors.service import OracleService

    if jax.device_count() != args.device_pool:
        raise SystemExit(
            f"FAIL: expected a {args.device_pool}-device forced host pool, "
            f"jax sees {jax.device_count()} — XLA_FLAGS was read after jax init?")
    if args.nd > args.device_pool:
        raise SystemExit(f"FAIL: --nd {args.nd} > --device-pool {args.device_pool}")
    mesh = make_host_mesh(args.nd)

    counter = RecompileCounter.install()
    cfg = TrainerConfig(
        n_workers=args.workers, mols_per_worker=args.mols_per_worker,
        episodes=args.warmup + args.episodes, sync_mode=args.sync,
        rollout=args.rollout, learner=args.learner, chem=args.chem,
        acting=args.acting, replay=args.replay,
        priority_alpha=args.priority_alpha, priority_beta0=args.priority_beta0,
        updates_per_episode=args.updates_per_episode,
        train_batch_size=args.batch_size, max_candidates=args.max_candidates,
        scenarios=(tuple(args.scenarios.split(","))
                   if args.scenarios else None),
        dqn=DQNConfig(epsilon_decay=args.epsilon_decay),
        env=EnvConfig(max_steps=args.max_steps), seed=args.seed)
    need = args.workers * args.mols_per_worker
    mols = [from_smiles(MOLS_SMILES[i % len(MOLS_SMILES)]) for i in range(need)]
    hidden = tuple(int(h) for h in args.hidden.split(","))

    plan = _build_fault_plan(args)
    service = OracleService()
    if plan is not None:
        # retry wrapper over the deterministic stub; sleep=None makes the
        # (deterministic, capped) backoff a no-op so scenarios stay fast
        from repro.predictors.service import ResilientService, RetryPolicy
        service = ResilientService(service, RetryPolicy(seed=args.fault_seed),
                                   fault_plan=plan, sleep=None)
    tr = DistributedTrainer(cfg, mols, service, RewardConfig(),
                            mesh=mesh, network=QNetwork(hidden=hidden),
                            fault_plan=plan)
    assert tr.mesh.devices.size == args.nd
    assert tr.engine.n_workers == tr.n_padded_workers
    assert tr.n_padded_workers % args.nd == 0

    mgr = None
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(args.ckpt_dir)
    start_ep = 0
    if args.resume:
        if mgr is None:
            raise SystemExit("FAIL: --resume requires --ckpt-dir")
        start_ep = tr.restore_checkpoint(mgr)

    total = args.warmup + args.episodes

    def run_one() -> None:
        tr.train_episode()
        if mgr is not None and not args.resume:
            # checkpoint cadence: every episode (the writer side of the
            # crash-resume matrix; the resumed side only reads)
            tr.save_checkpoint(mgr)
        if args.kill_at is not None and tr.episode == args.kill_at:
            # post-checkpoint work the crash destroys — resume must
            # reproduce it bit-identically from the last snapshot
            tr.train_episode()
            os.kill(os.getpid(), signal.SIGKILL)

    # a resumed process compiles everything fresh, so its first episode
    # back is its compile-warmup window no matter where the run stopped
    n_warm = (args.warmup - start_ep) if start_ep < args.warmup \
        else (1 if start_ep < total else 0)
    with counter.window() as warm:
        for _ in range(n_warm):
            run_one()
        # one ladder rung of candidate headroom past the warmup high-water
        # mark, so drift in the measured episodes cannot grow the jit shape
        if tr.candidate_capacity:
            tr.reserve_candidates(int(tr.candidate_capacity * 1.3))
    with counter.window() as measured:
        while tr.episode < total:
            run_one()

    fault_stats = tr.engine.fault_stats()
    out = {
        **trainer_report(tr),
        "device_pool": np.int64(jax.device_count()),
        "warmup_compiles": np.int64(warm.count),
        "recompiles_after_warmup": np.int64(measured.count),
        "n_faults_injected": np.int64(plan.n_injected if plan is not None else 0),
        "n_retries": np.int64(getattr(service, "n_retries", 0)),
        "n_timeouts": np.int64(getattr(service, "n_timeouts", 0)),
        "n_quarantined": np.int64(fault_stats["n_quarantined"]),
        "n_chem_retries": np.int64(fault_stats["n_chem_retries"]),
        "n_pipeline_restarts": np.int64(fault_stats["n_pipeline_restarts"]),
        "n_incidents": np.int64(fault_stats["n_incidents"]),
        "meta": np.asarray(json.dumps(vars(args), sort_keys=True)),
    }
    return out


def main() -> None:
    ap = argparse.ArgumentParser(
        description="one multi-device equivalence scenario (see module docstring)")
    ap.add_argument("--nd", type=int, required=True,
                    help="mesh size: submesh over the first nd pool devices")
    ap.add_argument("--device-pool", type=int, default=DEFAULT_DEVICE_POOL,
                    help="forced host device count (set in XLA_FLAGS pre-init; "
                         "IDENTICAL across compared scenarios — see docstring)")
    ap.add_argument("--out", required=True, help="output .npz report path")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--mols-per-worker", type=int, default=2)
    ap.add_argument("--rollout", default="fleet_sharded")
    ap.add_argument("--learner", default="packed")
    ap.add_argument("--chem", default="incremental")
    ap.add_argument("--acting", default="packed",
                    help="fleet acting representation (core.ACTING_MODES)")
    ap.add_argument("--replay", default="uniform",
                    help="replay sampling (core.REPLAY_MODES); prioritized "
                         "with --priority-alpha 0 must match uniform bit "
                         "for bit — the parity scenarios pin exactly that")
    ap.add_argument("--scenarios", default=None,
                    help="comma list of scenario-registry names cycled "
                         "across workers (configs/scenarios.py); "
                         "homogeneous 'antioxidant' must be bit-identical "
                         "to the default path, and each mixed-fleet "
                         "worker to its solo single-scenario twin")
    ap.add_argument("--priority-alpha", type=float, default=0.6)
    ap.add_argument("--priority-beta0", type=float, default=0.4)
    ap.add_argument("--sync", default="episode")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warmup", type=int, default=1,
                    help="episodes before the recompile-gate window opens")
    ap.add_argument("--episodes", type=int, default=2,
                    help="measured episodes (compared across nd)")
    ap.add_argument("--max-steps", type=int, default=3)
    ap.add_argument("--updates-per-episode", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-candidates", type=int, default=16)
    ap.add_argument("--hidden", default="32",
                    help="comma-separated QNetwork hidden sizes")
    ap.add_argument("--epsilon-decay", type=float, default=0.9)
    # crash-resume scenarios (docs/robustness.md)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint the full trainer state here after "
                         "every episode")
    ap.add_argument("--kill-at", type=int, default=None,
                    help="SIGKILL the process after episode K's checkpoint "
                         "(plus uncheckpointed post-crash work)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest --ckpt-dir checkpoint and "
                         "finish the run")
    # deterministic fault injection (core.faults.FaultPlan)
    ap.add_argument("--faults", default=None,
                    help="comma list of armed sites: predict,chem,pipeline")
    ap.add_argument("--fault-every", type=int, default=3,
                    help="serial sites: fault every Nth call")
    ap.add_argument("--fault-rate", type=float, default=0.25,
                    help="keyed sites: fraction of molecule keys that fault")
    ap.add_argument("--fault-attempts", type=int, default=1,
                    help="consecutive failures per scheduled call/key "
                         "(> the retry budget makes the fault terminal)")
    ap.add_argument("--fault-seed", type=int, default=0)
    args = ap.parse_args()

    import numpy as np

    out = run_scenario(args)
    np.savez(args.out, **out)
    print(f"[verify] nd={args.nd} W={args.workers} rollout={args.rollout} "
          f"learner={args.learner} chem={args.chem} acting={args.acting} "
          f"replay={args.replay} sync={args.sync}: "
          f"{int(out['warmup_compiles'])} warmup compiles, "
          f"{int(out['recompiles_after_warmup'])} recompiles after warmup, "
          f"{int(out['n_transitions'].sum())} transitions -> {args.out}",
          flush=True)


if __name__ == "__main__":
    main()
