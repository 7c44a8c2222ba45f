"""The distributed DA-MolDQN trainer (§3.1/§3.2).

The paper extends MT-MolDQN's DDP to a SLURM-launched multi-node setup:
N worker processes, each owning a *batch* of initial molecules and a
private replay buffer, cooperating on ONE general model that is
"synchronized among all processes at the end of every episode".

JAX mapping (DESIGN.md §5): workers are a stacked leading axis sharded over
the mesh's "data" axis via ``shard_map``; the two synchronisation regimes
become two collective placements:

* ``sync_mode="step"``   — MT-MolDQN/DDP: gradients are mean-reduced across
  workers at EVERY optimiser step (params stay replicated across workers).
* ``sync_mode="episode"`` — DA-MolDQN: every worker updates its OWN params
  locally (no per-step collective); parameters (and optimizer moments) are
  mean-reduced once per episode boundary.

Both cross-worker means are implemented as all_gather + an identical
full-worker-axis reduction on every device (``fleet_mean``) rather than
``pmean`` of per-shard means: the reduction order is then independent of
the mesh size, which is what holds nd > 1 runs to the nd = 1 reference
within a few float32 ulps (tests/multidevice, under
``repro.launch.verify.assert_nd_equivalent``) and lets dead mesh-padding
workers be masked out exactly.  The roofline benchmark quantifies the traffic:
episode-sync moves (param_bytes) once per episode instead of (grad_bytes x
updates_per_episode) — the paper's communication-efficiency claim in
collective-bytes form.

Acting (environment rollout, candidate Q evaluation, property prediction)
is host-driven.  Since the fleet-level refactor it is batched across ALL
workers per step through ``repro.core.rollout.RolloutEngine``: one jit'd Q
dispatch over every worker's candidates (per-worker parameters selected by
a vmap'd apply over the stacked ``[W, ...]`` tree) and one property batch
over every worker's chosen successors — O(1) dispatches per step instead
of O(W).  Four acting paths, all pinned seeded-transition-identical by
tests/test_rollout.py:

* ``rollout="per_worker"``      the paper's sequential per-process loop
                                (W dispatches/step) — kept for comparison;
* ``rollout="fleet"``           one Q dispatch per step, through
                                ``shard_map`` over the mesh "data" axis:
                                each device evaluates only its resident
                                workers' ``[W/nd, C, D]`` slice under its
                                resident ``[W/nd, ...]`` params (no
                                collective — acting is embarrassingly
                                data-parallel); one device is a one-device
                                mesh, so every nd runs the same program;
* ``rollout="fleet_sharded"``   a second name for ``"fleet"`` (the same
                                compiled dispatch);
* ``rollout="fleet_pipelined"`` the same dispatch + the engine's
                                double-buffered step: step t+1's candidate
                                enumeration/fingerprinting overlaps step
                                t's property batch (the 512-worker path).

Orthogonally, ``TrainerConfig.acting`` (``ACTING_MODES``) picks the fleet
acting-batch REPRESENTATION: ``"packed"`` ships u8 bit planes assembled
straight from the slots' packed candidate fingerprints and unpacks inside
the jit (~32x less acting H2D traffic; no host f32 candidate buffer at
all), ``"packed_async"`` additionally overlaps the Q round-trip with
pre-drawn action selection and early next-step chemistry, and ``"dense"``
keeps the seed f32 path as the correctness reference.  All pinned
transition-identical by tests/test_rollout.py.

Learning (replay sample -> update step) is the acting refactor's twin,
selected by ``TrainerConfig.learner`` (``LEARNER_MODES``), all three paths
pinned loss-trajectory-identical by tests/test_learner.py:

* ``learner="dense"``            the seed path: host-side dense float32
                                 batches (``ReplayBuffer.sample``), shipped
                                 as ``[W, B, C, FP_BITS+1]`` floats;
* ``learner="packed"``           ``sample_packed`` ships uint8 bit planes
                                 (32x less H2D traffic) and the unpack runs
                                 INSIDE the jit'd update (``packed_batch.
                                 densify_batch``, per device shard);
* ``learner="packed_pipelined"`` packed + double-buffered sampling: a host
                                 sampler thread prepares update k+1's batch
                                 while update k runs on device (the same
                                 overlap idiom as the engine's
                                 ``step_pipelined``; batches are identical
                                 because the buffers are not written between
                                 updates and the single sampler thread draws
                                 the per-buffer RNG streams in order).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, replace

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.chem.chemcache import ChemCache
from repro.chem.molecule import Molecule
from repro.core.agent import (
    DQNAgent, DQNConfig, QNetwork, candidate_capacity, candidate_capacity_table,
    huber, pad_rows,
)
from repro.core.env import BatchedEnv, EnvConfig, StepRecord
from repro.core.packed_batch import densify_batch, packed_nbytes
from repro.core.replay import FP_BYTES, ReplayBuffer
from repro.core.rollout import CHEM_MODES, STATE_DIM, RolloutEngine
from repro.core.reward import RewardConfig
from repro.launch.mesh import fleet_sharding, make_host_mesh, padded_worker_count
from repro.optim import adam
from repro.optim.adam import apply_updates
from repro.predictors.service import PropertyService

ROLLOUT_MODES = ("fleet", "fleet_sharded", "fleet_pipelined", "per_worker")
_FLEET_MODES = ("fleet", "fleet_sharded", "fleet_pipelined")
LEARNER_MODES = ("packed", "packed_pipelined", "dense")
# replay sampling (core.replay.SAMPLING_MODES): "uniform" is the seed path
# and the pinned reference; "prioritized" is proportional PER (Schaul et
# al. 2015) with per-slot priority arrays in the SoA buffers, importance
# weights folded into the loss, and |TD| feedback after every update.
# With all-equal effective priorities (priority_alpha = 0, or before any
# TD feedback differentiates them) prioritized is BIT-identical to
# uniform — same RNG stream, unit weights (tests/test_learner.py,
# tests/multidevice).
REPLAY_MODES = ("uniform", "prioritized")
# fleet acting-batch representation (the learner refactor's acting twin),
# all pinned transition/param-identical by tests/test_rollout.py:
#   "packed"        u8 bit planes assembled straight from the slots'
#                   cand_fps_packed; unpack runs inside the jit (~32x less
#                   acting H2D traffic than dense)
#   "packed_async"  packed + the async Q protocol: the dispatch returns a
#                   device handle, eps-greedy decisions are pre-drawn and
#                   step t+1 chemistry of exploring slots starts while the
#                   device computes (fleet_pipelined covers the Q
#                   round-trip, not just the property batch)
#   "dense"         the seed [W, C, STATE_DIM] f32 path, kept as the
#                   correctness reference
ACTING_MODES = ("packed", "packed_async", "dense")


@dataclass(frozen=True)
class TrainerConfig:
    n_workers: int = 4
    mols_per_worker: int = 4          # "Modification Batch" (Table 1)
    episodes: int = 250               # general model (Table 1)
    sync_mode: str = "episode"        # "episode" (DA-MolDQN) | "step" (DDP)
    rollout: str = "fleet"            # see ROLLOUT_MODES (module docstring)
    learner: str = "packed"           # see LEARNER_MODES (module docstring)
    acting: str = "packed"            # see ACTING_MODES (fleet modes only;
                                      # per_worker always acts dense)
    chem: str = "incremental"         # candidate chemistry: rollout.CHEM_MODES
                                      # ("full" = per-step recompute reference)
    updates_per_episode: int = 4
    train_batch_size: int = 32        # <= Table 2's 512 cap; CPU-scaled
    max_candidates: int = 64          # replay target max truncation
    replay_capacity: int = 4000       # Table 3
    replay: str = "uniform"           # replay sampling: see REPLAY_MODES
    priority_alpha: float = 0.6       # PER proportional exponent (0 = flat)
    priority_beta0: float = 0.4       # importance-weight anneal start
    priority_beta_episodes: int | None = None  # episodes for beta -> 1.0
                                               # (None: cfg.episodes)
    priority_eps: float = 1e-3        # |TD| priority floor
    dataset: str | None = None        # multi-start episode stream: draw each
                                      # episode's start molecules from a
                                      # seeded data.datasets cursor (DATASETS
                                      # name); None = fixed ctor molecules
    dataset_size: int | None = None   # pool size (None: dataset default)
    dataset_seed: int | None = None   # pool+cursor seed (None: cfg.seed)
    scenarios: tuple[str, ...] | None = None
                                      # heterogeneous scenario fleet: registry
                                      # names (configs/scenarios) cycled across
                                      # workers — worker w optimises
                                      # scenarios[w % len]; Eq.1-family names
                                      # take their bde/ip bounds from the
                                      # trainer's reward_cfg.  None = every
                                      # worker runs reward_cfg (the seed path,
                                      # bit-identical to pre-scenario builds)
    pipeline_threads: int | None = None  # fleet_pipelined host pool (None: auto)
    dqn: DQNConfig = field(default_factory=lambda: DQNConfig(epsilon_decay=0.97))
    env: EnvConfig = field(default_factory=EnvConfig)
    seed: int = 0


class _WorkerView:
    """Adapter giving BatchedEnv the per-worker agent interface (the
    pre-fleet sequential path: one jit dispatch PER WORKER per step)."""

    def __init__(self, trainer: "DistributedTrainer", w: int):
        self.t = trainer
        self.w = w

    def q_values(self, states: np.ndarray) -> np.ndarray:
        n = states.shape[0]
        padded = pad_rows(n)
        if padded != n:
            states = np.concatenate(
                [states, np.zeros((padded - n, states.shape[1]), states.dtype)])
        self.t.n_q_dispatches += 1
        q = self.t._q_one(self.t.params, jnp.asarray(states), self.w)
        return np.asarray(q)[:n]

    def select_action(self, q: np.ndarray) -> int:
        return self.t._select_action(q, self.w)


class _FleetView:
    """FleetPolicy over the trainer's stacked per-worker parameters: ONE
    jit dispatch evaluates every worker's candidates under that worker's
    own parameters (vmap'd apply, dense ``[W, Cmax, D]`` layout).

    The candidate axis is padded to a rung of the fleet-adaptive capacity
    ladder (``candidate_capacity_table``) and the batch buffer is a STICKY
    high-water mark: capacity only ever grows, and the jit always sees the
    full buffer, so shapes change O(log C) times per run instead of every
    time the per-step max drifts — the property that keeps W=512 free of
    per-step recompiles.  Every batch is placed on the mesh's "data" axis
    (``fleet_sharding``) next to the already-sharded parameters before the
    one ``shard_map`` dispatch of its representation.

    ``acting`` picks the batch representation (``ACTING_MODES``): the
    dense f32 reference, or the packed u8 bit planes (optionally with the
    async dispatch/fetch split) — the packed modes never materialise a
    dense f32 candidate buffer on the host.
    """

    def __init__(self, trainer: "DistributedTrainer", acting: str = "dense"):
        self.t = trainer
        self.acting = acting
        # engine-facing protocol switches (see rollout.FleetPolicy)
        self.wants_packed_states = acting != "dense"
        self.async_q = acting == "packed_async"
        self._table = candidate_capacity_table(trainer.cfg.n_workers)
        self._dense: np.ndarray | None = None
        self._bits: np.ndarray | None = None
        self._frac: np.ndarray | None = None
        self._cap = 0

    def reserve(self, max_candidates: int) -> None:
        """Pre-grow the batch buffers (ladder-rounded) so a known candidate
        bound never triggers a mid-run growth recompile."""
        cap = candidate_capacity(max_candidates, self._table)
        if cap > self._cap:
            self._cap = cap
            # rows for the PADDED fleet: dead mesh-padding workers keep
            # all-zero rows, so the [W_pad, C, ...] batch tiles the mesh
            W_pad = self.t.n_padded_workers
            if self.wants_packed_states:
                self._bits = np.zeros((W_pad, cap, FP_BYTES), np.uint8)
                self._frac = np.zeros((W_pad, cap), np.float32)
            else:
                self._dense = np.zeros((W_pad, cap, STATE_DIM), np.float32)

    def warm_dispatch(self) -> None:
        """Issue one dummy dispatch so the CURRENT capacity's jit shape is
        compiled eagerly (reserve_candidates counts this as warmup)."""
        n = self.t.engine.n_workers
        if self.wants_packed_states:
            self.fleet_q_fetch(self.fleet_q_dispatch_packed(
                [np.zeros((1, FP_BYTES), np.uint8)] * n,
                [np.zeros((1,), np.float32)] * n))
        else:
            self.fleet_q_values([np.zeros((1, STATE_DIM), np.float32)] * n)

    # ---- dense reference ---------------------------------------- #
    def fleet_q_values(self, per_worker: list[np.ndarray]) -> list[np.ndarray]:
        counts = [x.shape[0] for x in per_worker]
        if not any(counts):
            return [np.zeros((0,), np.float32) for _ in per_worker]
        self.reserve(max(counts))
        dense = self._dense  # never sliced down: shapes only change on growth
        for w, x in enumerate(per_worker):
            dense[w, : x.shape[0]] = x
            dense[w, x.shape[0]:] = 0.0  # clear rows left by the last step
        self.t.n_q_dispatches += 1
        self.t.acting_h2d_bytes += dense.nbytes
        x = jax.device_put(dense, self.t._fleet_in_sharding)
        q = np.asarray(self.t._fleet_q(self.t.params, x))
        return [q[w, :n] for w, n in enumerate(counts)]

    # ---- packed protocol (rollout.FleetPolicy) ------------------- #
    def fleet_q_dispatch_packed(self, bits_pw: list[np.ndarray],
                                frac_pw: list[np.ndarray]):
        """Copy the per-worker packed planes into the sticky buffers and
        dispatch WITHOUT blocking: the returned handle holds the on-device
        ``jax.Array`` (XLA computes asynchronously; ``fleet_q_fetch`` is
        the only synchronisation point)."""
        counts = [b.shape[0] for b in bits_pw]
        if not any(counts):
            return None, counts
        self.reserve(max(counts))
        bits, frac = self._bits, self._frac
        for w, (b, f) in enumerate(zip(bits_pw, frac_pw)):
            n = b.shape[0]
            bits[w, :n] = b
            bits[w, n:] = 0   # dead/finished rows: zero planes, never garbage
            frac[w, :n] = f
            frac[w, n:] = 0.0
        self.t.n_q_dispatches += 1
        self.t.acting_h2d_bytes += bits.nbytes + frac.nbytes
        xb, xf = jax.device_put((bits, frac), self.t._fleet_in_sharding)
        q = self.t._fleet_q_packed(self.t.params, xb, xf)
        return q, counts

    def fleet_q_fetch(self, handle) -> list[np.ndarray]:
        """Block on the device result and slice it back per worker."""
        q, counts = handle
        if q is None:
            return [np.zeros((0,), np.float32) for _ in counts]
        qh = np.asarray(q)
        return [qh[w, :n] for w, n in enumerate(counts)]

    def fleet_q_values_packed(self, bits_pw: list[np.ndarray],
                              frac_pw: list[np.ndarray]) -> list[np.ndarray]:
        return self.fleet_q_fetch(self.fleet_q_dispatch_packed(bits_pw, frac_pw))

    def plan_action(self, n_candidates: int, worker: int) -> int:
        return self.t._plan_action(n_candidates, worker)

    def select_action(self, q: np.ndarray, worker: int) -> int:
        return self.t._select_action(q, worker)


def fleet_q_dispatches(net: QNetwork, mesh: Mesh, *,
                       use_pallas_qnet: bool = False):
    """The fleet acting dispatches, one per batch representation:
    ``(dense, packed)``.

    ``dense(params, x)`` takes ``[W, C, D]`` f32 states, ``packed(params,
    bits, frac)`` the ``[W, C, FP_BITS/8]`` u8 planes and ``[W, C]`` f32
    steps-left (unpacked INSIDE the jit, ~32x less acting H2D traffic);
    both return q ``[W, C]`` under the stacked ``[W, ...]`` params — ONE
    dispatch per environment step regardless of n_workers.  Both run
    through ``shard_map`` over the mesh's "data" axis: each device
    evaluates its resident ``[W/nd, ...]`` slice with no collective, and
    one device is a one-device mesh.  Every operand must be placed on
    ``fleet_sharding(mesh)``.  ``out_shardings`` is pinned like the update
    fns: at nd > 1 the compiler may otherwise mark the output replicated,
    and the flip retraces the dispatch (the recompile counter gates this).
    With ``use_pallas_qnet`` the packed evaluation routes through the
    stacked bit-plane kernel, compiled when the mesh is made of TPU
    devices and the XLA unpack path otherwise."""
    spec_w = P("data")
    on_tpu = mesh.devices.flat[0].platform == "tpu"

    def packed_body(params, bits, frac):
        if use_pallas_qnet:
            from repro.kernels.packed_qnet.ops import packed_qnet_stacked
            return packed_qnet_stacked(params, bits, frac,
                                       impl="pallas" if on_tpu else "xla",
                                       interpret=False)
        return net.apply_stacked_packed(params, bits, frac)

    out_w = NamedSharding(mesh, spec_w)
    dense = jax.jit(shard_map(
        net.apply_stacked, mesh=mesh,
        in_specs=(spec_w, spec_w), out_specs=spec_w), out_shardings=out_w)
    # check_vma off: a pallas_call's output carries no varying-axes
    # annotation, and acting has no collective for the check to guard
    packed = jax.jit(shard_map(
        packed_body, mesh=mesh,
        in_specs=(spec_w, spec_w, spec_w), out_specs=spec_w,
        check_vma=False), out_shardings=out_w)
    return dense, packed


class DistributedTrainer:
    """Trains ONE general model over many molecules with W workers.

    Runs on any single-axis "data" mesh (``launch.mesh.make_host_mesh`` by
    default).  A worker count that does not divide the device count is
    padded to the mesh with dead worker slots: ``n_live_workers`` is the
    configured fleet, ``n_padded_workers`` the stacked/sharded width.  Dead
    slots own no molecules (zero rows in every dense acting batch), ship
    all-zero update batches whose masked gradients are exact no-ops, and
    are excluded from every cross-worker mean — so the live results are
    identical to the unpadded run.  The multi-device equivalence suite
    (tests/multidevice, driven by ``repro.launch.verify`` subprocesses)
    pins transitions, loss trajectories and parameters bit-identical
    across nd in {1, 2, 4} forced host devices.
    """

    def __init__(
        self,
        cfg: TrainerConfig,
        molecules: list[Molecule] | None,
        service: PropertyService,
        reward_cfg: RewardConfig,
        mesh: Mesh | None = None,
        network: QNetwork | None = None,
        dataset_pool: list[Molecule] | None = None,
        fault_plan=None,
    ):
        self.cfg = cfg
        self.service = service
        self.reward_cfg = reward_cfg
        self.fault_plan = fault_plan
        self.network = network or QNetwork()
        W = cfg.n_workers
        need = W * cfg.mols_per_worker

        # multi-start dataset streaming (ROADMAP item 5): with cfg.dataset
        # set, every episode draws its start molecules from a seeded
        # DatasetStream cursor instead of re-using the fixed ctor batch.
        # ``dataset_pool`` lets callers (tests, benches) inject the pool
        # directly; otherwise cfg.dataset names a data.datasets registry
        # entry.  The cursor is drawn ON THE HOST before any rollout-mode
        # branch, so the start schedule is identical across fleet /
        # fleet_sharded / fleet_pipelined / per_worker (tests/test_datasets).
        self._dataset_stream = None
        if cfg.dataset is not None:
            if molecules is not None:
                raise ValueError(
                    "pass molecules=None when cfg.dataset streams the "
                    "episode starts (the fixed batch would be ignored)")
            from repro.data.datasets import DatasetStream, load_dataset
            pool = dataset_pool if dataset_pool is not None else load_dataset(
                cfg.dataset, count=cfg.dataset_size, seed=cfg.dataset_seed)
            dseed = cfg.seed if cfg.dataset_seed is None else cfg.dataset_seed
            self._dataset_stream = DatasetStream(pool, seed=dseed)
            # episode-0 placeholder assignment (rollout_episode re-draws
            # from the cursor before every episode, including the first)
            molecules = [pool[i % len(pool)] for i in range(need)]
        elif molecules is None:
            raise ValueError("molecules=None requires cfg.dataset")
        if len(molecules) < need:
            raise ValueError(f"need {need} molecules for {W}x{cfg.mols_per_worker}, got {len(molecules)}")
        self.molecules = molecules[:need]
        self.start_log: list[tuple[str, ...]] = []  # per-episode start keys
                                                    # (dataset mode only)

        if mesh is None:
            mesh = make_host_mesh()   # the one mesh-construction code path
        self.mesh = mesh
        nd = mesh.devices.size
        # fleets that do not divide the mesh pad to it with DEAD worker
        # slots: a W=6 fleet on a 4-device mesh trains as a padded W=8
        # fleet whose two dead slots own no molecules, zero out of every
        # dense row, and are masked out of every cross-worker mean — the
        # live workers' transitions, losses and parameters are identical
        # to the unpadded run (tests/multidevice pins this at nd in {2,4})
        self.n_live_workers = W
        self.n_padded_workers = padded_worker_count(W, mesh)

        if cfg.rollout not in ROLLOUT_MODES:
            raise ValueError(f"rollout must be one of {ROLLOUT_MODES}, got {cfg.rollout!r}")
        if cfg.learner not in LEARNER_MODES:
            raise ValueError(f"learner must be one of {LEARNER_MODES}, got {cfg.learner!r}")
        if cfg.sync_mode not in ("episode", "step"):
            raise ValueError(f"sync_mode must be 'episode' or 'step', got {cfg.sync_mode!r}")
        if cfg.chem not in CHEM_MODES:
            raise ValueError(f"chem must be one of {CHEM_MODES}, got {cfg.chem!r}")
        if cfg.acting not in ACTING_MODES:
            raise ValueError(f"acting must be one of {ACTING_MODES}, got {cfg.acting!r}")
        if cfg.replay not in REPLAY_MODES:
            raise ValueError(f"replay must be one of {REPLAY_MODES}, got {cfg.replay!r}")

        # size the predictor padding ladder for the fleet-wide per-step batch
        # (one chosen successor per live slot)
        if hasattr(service, "reserve"):
            service.reserve(W * cfg.mols_per_worker)

        # ONE chemistry cache for the whole trainer: entries are shared
        # across workers, episodes and steps (and, for the legacy
        # per_worker path, across its per-worker envs)
        self.chem_cache = ChemCache() if cfg.chem == "incremental" else None
        # fleet engine over the worker molecule partition: one Q dispatch
        # and one property batch per step across ALL workers
        self.engine = RolloutEngine(
            [self.molecules[w * cfg.mols_per_worker : (w + 1) * cfg.mols_per_worker]
             for w in range(W)],
            cfg.env, pipeline_threads=cfg.pipeline_threads,
            chem=cfg.chem, chem_cache=self.chem_cache,
            pad_workers_to=self.n_padded_workers,
            packed_states=cfg.acting != "dense",
            fault_plan=fault_plan)
        # heterogeneous scenario fleet: compile ONE objective per worker
        # (fresh instances — the novelty term's visit counts are per-worker
        # state) and install them as the engine's per-slot defaults.  The
        # per_worker rollout path passes the same instances as each env's
        # reward_cfg, so every mode sees identical objective resolution.
        self.worker_objectives = None
        self.scenario_names: tuple[str, ...] | None = None
        if cfg.scenarios:
            from repro.configs.scenarios import (
                compile_worker_objectives, worker_scenarios)
            base = reward_cfg if isinstance(reward_cfg, RewardConfig) else None
            self.scenario_names = tuple(worker_scenarios(cfg.scenarios, W))
            self.worker_objectives = compile_worker_objectives(
                cfg.scenarios, W, base=base)
            self.engine.set_worker_objectives(self.worker_objectives)
        self._envs: list[BatchedEnv] | None = None  # built lazily (legacy path)
        # storage truncates where sample() would anyway (cfg.max_candidates),
        # so the SoA candidate axis never outgrows what training can see
        self.buffers = [ReplayBuffer(cfg.replay_capacity, seed=cfg.seed + 200 + w,
                                     max_candidates=cfg.max_candidates,
                                     sampling=cfg.replay,
                                     priority_alpha=cfg.priority_alpha,
                                     priority_eps=cfg.priority_eps)
                        for w in range(W)]
        self._worker_rngs = [np.random.default_rng(cfg.seed + 300 + w) for w in range(W)]
        self.n_q_dispatches = 0  # acting-side jit dispatches (both paths)
        self.n_updates = 0       # learner update steps issued
        self.h2d_update_bytes = 0  # host->device bytes shipped by update batches
        self.acting_h2d_bytes = 0  # host->device bytes shipped by fleet Q batches
        self._sampler_pool: ThreadPoolExecutor | None = None  # packed_pipelined

        # stacked per-worker params [W_pad, ...] sharded over "data".  All
        # workers start from worker 0's weights (like DDP broadcast);
        # padding rows replicate them too, so the initial stacked tree is
        # independent of how far the mesh padded the fleet.  The stack and
        # the Adam moments are built by one jit straight into their
        # sharded placement: at W=512 the whole stacked state (~22 GB at
        # paper widths) does not fit on the one device an eager build
        # would use.
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed), W)
        p0 = jax.tree_util.tree_map(
            lambda x: x[0], jax.vmap(self.network.init)(keys[:1]))
        self.opt = adam(cfg.dqn.lr, clip_norm=cfg.dqn.grad_clip)
        W_pad = self.n_padded_workers

        def stack(p0):
            params = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x, (W_pad,) + x.shape), p0)
            return params, jax.vmap(self.opt.init)(params)

        self.params, self.opt_state = jax.jit(
            stack, out_shardings=fleet_sharding(self.mesh))(p0)
        self.target_params = jax.tree_util.tree_map(jnp.copy, self.params)

        self.epsilon = cfg.dqn.epsilon_initial
        self.episode = 0
        # per-episode scalar trajectories, checkpointed with the trainer so
        # a resumed run's report carries the FULL history (crash-resume
        # equivalence diffs these against the straight-through reference)
        self.loss_log: list[float] = []
        self.reward_log: list[float] = []
        self._views = [_WorkerView(self, w) for w in range(W)]
        self._fleet_in_sharding = fleet_sharding(self.mesh)
        self._fleet_policy = _FleetView(self, acting=cfg.acting)
        self._build_fns()

    @property
    def envs(self) -> list[BatchedEnv]:
        """Per-worker single-worker envs for the legacy ``per_worker``
        rollout (and external benchmarks).  Built on first access so the
        default fleet path doesn't enumerate every initial molecule's
        candidates twice at construction."""
        if self._envs is None:
            cfg = self.cfg
            self._envs = [
                BatchedEnv(
                    self.molecules[w * cfg.mols_per_worker : (w + 1) * cfg.mols_per_worker],
                    cfg.env, chem=cfg.chem, chem_cache=self.chem_cache)
                for w in range(cfg.n_workers)
            ]
        return self._envs

    # ------------------------------------------------------------ #
    # jit'd compute
    # ------------------------------------------------------------ #
    def _build_fns(self) -> None:
        net, opt, cfg = self.network, self.opt, self.cfg
        discount = cfg.dqn.discount
        mesh = self.mesh

        def per_worker_loss(p, tp, batch):
            # Returns (loss, |td|): the aux |TD| vector feeds prioritized
            # replay's priority refresh.  Adding the stop_gradient'd aux
            # leaves loss and grads bitwise unchanged, and uniform batches
            # carry no "weights" key, so the uniform jits trace EXACTLY
            # the seed loss — both properties the parity tests pin.
            q_sa = net.apply(p, batch["states"])
            q_next_online = net.apply(p, batch["next_fps"])
            q_next_online = jnp.where(batch["next_mask"] > 0, q_next_online, -jnp.inf)
            a_star = jnp.argmax(q_next_online, axis=-1)
            q_next_target = net.apply(tp, batch["next_fps"])
            v_next = jnp.take_along_axis(q_next_target, a_star[:, None], axis=-1)[:, 0]
            v_next = jnp.where(batch["next_mask"].sum(-1) > 0, v_next, 0.0)
            y = jax.lax.stop_gradient(
                batch["rewards"] + discount * (1.0 - batch["dones"]) * v_next)
            td = q_sa - y
            h = huber(td)
            if "weights" in batch:   # prioritized: importance-weighted mean
                h = h * batch["weights"]
            return jnp.mean(h), jax.lax.stop_gradient(jnp.abs(td))

        spec_w = P("data")
        n_live = self.n_live_workers
        W_pad = self.n_padded_workers
        W_local = W_pad // mesh.devices.size  # workers resident per device

        def fleet_mean(x, keepdims: bool = False):
            """Mean over the LIVE workers of a ``[W_local, ...]`` shard.

            The reduction order must not depend on the mesh size (mean-of-
            in-shard-means drifts in the last bit between nd=1 and nd>1),
            so every device gathers the FULL worker axis and runs the
            identical ``[W_pad, ...]`` reduction locally.  Dead padding
            rows are zeroed before the sum; summing trailing exact zeros
            is a bitwise no-op, which keeps a padded W=6-on-4-devices run
            identical to the unpadded nd=1 W=6 reference.
            """
            full = jax.lax.all_gather(x, "data", axis=0, tiled=True)
            if n_live != W_pad:
                m = (jnp.arange(W_pad) < n_live).astype(x.dtype)
                full = full * m.reshape((-1,) + (1,) * (full.ndim - 1))
            return jnp.sum(full, axis=0, keepdims=keepdims) / n_live

        def shard_live_mask():
            """f32 ``[W_local]``: 1 for live workers resident in this
            shard, 0 for dead mesh-padding workers."""
            rows = jax.lax.axis_index("data") * W_local + jnp.arange(W_local)
            return (rows < n_live).astype(jnp.float32)

        def scan_workers(f, xs):
            """Map ``f`` over the shard's resident workers via ``lax.scan``
            instead of ``vmap``: the per-iteration program is independent of
            W_local, which keeps the update within a few ulps across mesh
            sizes.  (A vmap'd per-worker matmul lowers as a BATCHED dot,
            and XLA lowers batch 1 — one worker per device, nd == W —
            differently from batch n.)  XLA still compiles the loop per
            shard width, and on jax 0.9 XLA-CPU that alone moves a few
            parameter ulps at nd = 4 (``repro.launch.verify.ND_RTOL``)."""
            def step(carry, x):
                return carry, f(*x)
            return jax.lax.scan(step, None, xs)[1]

        def local_update_body(params, target, opt_state, batch):
            # per resident worker, serially within the shard; NO collective
            mask = shard_live_mask()

            def one(p, tp, s, b, m):
                (loss, td), grads = jax.value_and_grad(
                    per_worker_loss, has_aux=True)(p, tp, b)
                if n_live != W_pad:
                    # dead padding slots must not move: zero their grads
                    # (Adam with zero grads and zero moments is an exact
                    # no-op on the params)
                    grads = jax.tree_util.tree_map(lambda g: g * m, grads)
                updates, s2 = opt.update(grads, s, p)
                return apply_updates(p, updates), s2, loss, td
            return scan_workers(one, (params, target, opt_state, batch, mask))

        def ddp_update_body(params, target, opt_state, batch):
            # grads averaged across all LIVE workers (nd-invariant masked
            # mean); every worker — dead padding included — applies the
            # same mean update, so the stacked tree stays replicated
            def gfn(p, tp, b):
                (loss, td), grads = jax.value_and_grad(
                    per_worker_loss, has_aux=True)(p, tp, b)
                return loss, td, grads
            losses, tds, grads = scan_workers(gfn, (params, target, batch))
            gmean = jax.tree_util.tree_map(fleet_mean, grads)
            def one(p, s):
                updates, s2 = opt.update(gmean, s, p)
                return apply_updates(p, updates), s2
            new_p, new_s = scan_workers(one, (params, opt_state))
            return new_p, new_s, losses, tds

        def sync_body(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(fleet_mean(x, keepdims=True), x.shape),
                tree)

        # packed twins: identical update bodies, but the batch arrives as
        # uint8 bit planes and each device unpacks ONLY its resident
        # [W/nd, B, ...] shard inside the jit (no dense H2D transfer)
        def local_update_packed_body(params, target, opt_state, packed):
            return local_update_body(params, target, opt_state,
                                     densify_batch(packed))

        def ddp_update_packed_body(params, target, opt_state, packed):
            return ddp_update_body(params, target, opt_state,
                                   densify_batch(packed))

        # outputs pinned to the canonical worker-sharded placement: without
        # this the compiler may mark some update outputs replicated, and the
        # NEXT update (params/opt re-entering as inputs) retraces on the
        # sharding flip — one compiled train-step shape, not two
        out_w = NamedSharding(mesh, P("data"))
        self._local_update = jax.jit(shard_map(
            local_update_body, mesh=mesh,
            in_specs=(spec_w, spec_w, spec_w, spec_w),
            out_specs=(spec_w, spec_w, spec_w, spec_w),
        ), out_shardings=out_w)
        self._ddp_update = jax.jit(shard_map(
            ddp_update_body, mesh=mesh,
            in_specs=(spec_w, spec_w, spec_w, spec_w),
            out_specs=(spec_w, spec_w, spec_w, spec_w),
            check_vma=False,
        ), out_shardings=out_w)
        self._local_update_packed = jax.jit(shard_map(
            local_update_packed_body, mesh=mesh,
            in_specs=(spec_w, spec_w, spec_w, spec_w),
            out_specs=(spec_w, spec_w, spec_w, spec_w),
        ), out_shardings=out_w)
        self._ddp_update_packed = jax.jit(shard_map(
            ddp_update_packed_body, mesh=mesh,
            in_specs=(spec_w, spec_w, spec_w, spec_w),
            out_specs=(spec_w, spec_w, spec_w, spec_w),
            check_vma=False,
        ), out_shardings=out_w)
        self._sync = jax.jit(shard_map(
            sync_body, mesh=mesh, in_specs=(spec_w,), out_specs=spec_w,
        ), out_shardings=NamedSharding(mesh, P("data")))

        @jax.jit
        def q_one(params, states, w):
            p = jax.tree_util.tree_map(lambda x: x[w], params)
            return net.apply(p, states)
        self._q_one = q_one

        self._fleet_q, self._fleet_q_packed = fleet_q_dispatches(
            net, mesh, use_pallas_qnet=cfg.dqn.use_pallas_qnet)

    # ------------------------------------------------------------ #
    # training
    # ------------------------------------------------------------ #
    def train_episode(self) -> dict:
        """One paper episode: rollouts on all workers, local training
        updates, then (episode mode) the parameter sync."""
        cfg = self.cfg
        records = self.rollout_episode()

        losses = []
        min_fill = min(len(b) for b in self.buffers)
        if min_fill >= cfg.train_batch_size:
            losses = self.run_updates(cfg.updates_per_episode)

        if cfg.sync_mode == "episode":
            self.params = self._sync(self.params)
            self.opt_state = self._sync_opt(self.opt_state)

        self.episode += 1
        if self.episode % cfg.dqn.target_update_episodes == 0:
            self.target_params = jax.tree_util.tree_map(jnp.copy, self.params)
        self.epsilon = max(self.epsilon * cfg.dqn.epsilon_decay, cfg.dqn.epsilon_min)

        flat = [r for recs in records for r in recs]
        final = [r for r in flat if r.done]
        n_invalid = sum(1 for r in flat if not r.conformer_valid)
        st = {
            "episode": self.episode,
            "mean_final_reward": float(np.mean([r.reward for r in final])) if final else float("nan"),
            "loss": float(np.mean(losses)) if losses else float("nan"),
            "epsilon": self.epsilon,
            "invalid_conformer_rate": n_invalid / max(len(flat), 1),
        }
        self.loss_log.append(st["loss"])
        self.reward_log.append(st["mean_final_reward"])
        return st

    def rollout_episode(self) -> list[list[StepRecord]]:
        """One full acting episode for every worker, grouped per worker.

        The fleet modes drive the RolloutEngine: all workers advance in
        lockstep with one Q dispatch + one property batch per step
        ("fleet_pipelined" additionally overlaps next-step chemistry with
        the property batch).
        ``rollout="per_worker"`` replays the paper's sequential per-process
        loop.  All paths draw from the same per-worker RNG streams, so
        they produce identical transitions (tests/test_rollout.py).
        """
        W = self.cfg.n_workers
        if self._dataset_stream is not None:
            # multi-start: the next cursor draw becomes this episode's
            # start assignment, BEFORE the rollout-mode branch — one host
            # cursor, so every mode sees the identical schedule
            self._assign_starts(
                self._dataset_stream.draw(W * self.cfg.mols_per_worker))
        if self.cfg.rollout in _FLEET_MODES:
            flat = self.engine.run_episode(
                self._fleet_policy, self.service, self.reward_cfg,
                self.buffers, pipelined=self.cfg.rollout == "fleet_pipelined")
            records: list[list[StepRecord]] = [[] for _ in range(W)]
            for r in flat:
                records[r.worker].append(r)
            return records
        records = []
        for w, env in enumerate(self.envs):
            # scenario fleets hand each worker ITS compiled objective (the
            # same instance the fleet engine stamps on that worker's slots)
            rc = self.worker_objectives[w] \
                if self.worker_objectives is not None else self.reward_cfg
            recs = env.run_episode(self._views[w], self.service, rc,
                                   self.buffers[w])
            for r in recs:  # single-worker envs stamp worker=0; fix up
                r.worker = w
            records.append(recs)
        return records

    def _assign_starts(self, molecules: list[Molecule]) -> None:
        """Install one episode's start molecules everywhere acting reads
        them: the worker-major partition goes into the fleet engine's live
        worker initials (``run_episode`` resets into them) and the legacy
        per-worker envs are dropped for lazy rebuild from ``self.molecules``.
        The schedule is appended to ``start_log`` so cross-mode determinism
        is directly testable."""
        cfg = self.cfg
        self.molecules = list(molecules)
        self.engine.set_initial_molecules(
            [self.molecules[w * cfg.mols_per_worker : (w + 1) * cfg.mols_per_worker]
             for w in range(cfg.n_workers)])
        self._envs = None
        self.start_log.append(tuple(m.iso_key() for m in self.molecules))

    @property
    def candidate_capacity(self) -> int:
        """Current dense candidate-axis capacity of the active fleet view
        (0 until the first dispatch or ``reserve_candidates``)."""
        return 0 if self.cfg.rollout == "per_worker" \
            else self._fleet_policy._cap

    def reserve_candidates(self, max_candidates: int) -> None:
        """Pre-grow the fleet views' dense candidate capacity (ladder-
        rounded) and compile the resulting dispatch shape eagerly, so a
        known per-worker candidate bound never recompiles mid-run.  Counts
        as warmup: bumps ``n_q_dispatches`` once if it grows.  Only touches
        the view the configured rollout mode actually uses (no-op for the
        per_worker path, which buckets per worker instead)."""
        if self.cfg.rollout == "per_worker":
            return
        view = self._fleet_policy
        before = view._cap
        view.reserve(max_candidates)
        if view._cap != before:
            view.warm_dispatch()

    def _select_action(self, q: np.ndarray, w: int) -> int:
        """Decaying eps-greedy from worker ``w``'s private RNG stream."""
        rng = self._worker_rngs[w]
        if rng.random() < self.epsilon:
            return int(rng.integers(0, q.shape[0]))
        return int(np.argmax(q))

    def _plan_action(self, n_candidates: int, w: int) -> int:
        """The pre-draw half of ``_select_action`` for the async acting
        path: consume worker ``w``'s RNG stream EXACTLY as
        ``_select_action`` would (one uniform, plus the integer draw on
        the explore branch) but without needing Q values — return the
        explored index, or -1 for argmax-once-Q-lands.  The engine
        resolves -1 with the same ``int(np.argmax(q))``, so the chosen
        actions are bit-identical to the sync path's."""
        rng = self._worker_rngs[w]
        if rng.random() < self.epsilon:
            return int(rng.integers(0, n_candidates))
        return -1

    def _sync_opt(self, opt_state):
        """Average the float moments across workers; keep the int step."""
        from repro.optim.adam import OptState
        return OptState(step=opt_state.step, mu=self._sync(opt_state.mu),
                        nu=self._sync(opt_state.nu))

    # ------------------------------------------------------------ #
    # learner: replay sampling + update dispatch (LEARNER_MODES)
    # ------------------------------------------------------------ #
    def _pad_stacked(self, per: list[dict[str, np.ndarray]]
                     ) -> dict[str, np.ndarray]:
        """Stack per-live-worker sample dicts to ``[W_pad, B, ...]``: dead
        mesh-padding workers ship all-zero batches (their masked updates
        are exact no-ops, and their loss rows are sliced off on the host)."""
        if self.n_padded_workers != self.n_live_workers:
            zero = {k: np.zeros_like(v) for k, v in per[0].items()}
            per = per + [zero] * (self.n_padded_workers - self.n_live_workers)
        return {k: np.stack([p[k] for p in per]) for k in per[0]}

    def _beta(self) -> float:
        """PER importance-weight exponent, annealed ``priority_beta0 -> 1``
        over ``priority_beta_episodes`` (default: the full run).  A pure
        host float shipped as array VALUES inside the batch — the schedule
        never enters a traced shape, so sweeping beta costs zero
        recompiles (gated by bench_train --smoke)."""
        cfg = self.cfg
        horizon = cfg.priority_beta_episodes or cfg.episodes
        frac = min(1.0, self.episode / max(1, horizon))
        return cfg.priority_beta0 + (1.0 - cfg.priority_beta0) * frac

    def _sample_kwargs(self) -> dict:
        """Per-draw keyword args: prioritized adds the annealed beta;
        uniform passes NOTHING so the reference call sites stay verbatim."""
        if self.cfg.replay == "prioritized":
            return {"beta": self._beta()}
        return {}

    def _stacked_sample_np(self) -> dict[str, np.ndarray]:
        """Seed path host work: one DENSE float32 sample per worker buffer,
        stacked to ``[W_pad, B, ...]`` (what `_stacked_sample` ships)."""
        kw = self._sample_kwargs()
        return self._pad_stacked(
            [b.sample(self.cfg.train_batch_size, self.cfg.max_candidates, **kw)
             for b in self.buffers])

    def _stacked_sample_packed_np(self) -> dict[str, np.ndarray]:
        """Packed path host work: uint8 bit planes + scalars, stacked to
        ``[W_pad, B, ...]`` — ~32x fewer bytes than ``_stacked_sample_np``
        and no host-side unpack at all.  Draws the SAME per-buffer seeded
        indices as the dense sampler, which is what makes the two learner
        paths loss-trajectory-identical (tests/test_learner.py)."""
        kw = self._sample_kwargs()
        return self._pad_stacked(
            [b.sample_packed(self.cfg.train_batch_size, self.cfg.max_candidates,
                             **kw)
             for b in self.buffers])

    def _ship(self, host_batch: dict[str, np.ndarray]) -> dict[str, jnp.ndarray]:
        self.h2d_update_bytes += packed_nbytes(host_batch)
        return {k: jnp.asarray(v) for k, v in host_batch.items()}

    def _stacked_sample(self) -> dict[str, jnp.ndarray]:
        return self._ship(self._stacked_sample_np())

    def _stacked_sample_packed(self) -> dict[str, jnp.ndarray]:
        return self._ship(self._stacked_sample_packed_np())

    def _update_once(self, batch: dict[str, jnp.ndarray], packed: bool):
        """One optimiser step under the configured sync mode; returns the
        per-worker ``(loss, |td|)`` pair still on device (don't block the
        pipeline — prioritized replay is the only consumer of the td)."""
        if self.cfg.sync_mode == "step":
            fn = self._ddp_update_packed if packed else self._ddp_update
        else:
            fn = self._local_update_packed if packed else self._local_update
        self.params, self.opt_state, loss, td = fn(
            self.params, self.target_params, self.opt_state, batch)
        self.n_updates += 1
        return loss, td

    def _apply_priorities(self, td) -> None:
        """Feed the update's ``[W_pad, B]`` |TD| errors back into the live
        workers' buffers (dead mesh-padding rows carry zero-batch garbage
        and are dropped) — the sample -> update -> reprioritise cycle of
        proportional PER."""
        td_host = np.asarray(td)
        for w, buf in enumerate(self.buffers):
            buf.update_priorities(td_host[w])

    def _loss_scalar(self, loss) -> float:
        """Scalar loss over the LIVE workers of a ``[W_pad]`` loss vector
        (dead mesh-padding rows carry zero-batch garbage).  Computed the
        same way at every mesh size so loss trajectories are comparable
        bit for bit across nd."""
        return float(np.asarray(loss)[: self.n_live_workers].mean())

    def _get_sampler(self) -> ThreadPoolExecutor:
        if self._sampler_pool is None:
            self._sampler_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="replay-sample")
        return self._sampler_pool

    def run_updates(self, n: int) -> list[float]:
        """``n`` optimiser steps from the replay buffers under
        ``cfg.learner``.  ``packed_pipelined`` double-buffers: the sampler
        thread gathers update k+1's packed batch while update k runs on
        device (sound because nothing writes the buffers between updates
        and the single sampler thread drains each buffer's RNG stream in
        order — so every path sees identical batches).

        Prioritized replay forces the SEQUENTIAL order for every learner
        mode, packed_pipelined included: update k's |TD| errors must
        reprioritise the buffers before batch k+1 is drawn, so there is
        nothing sound to overlap — pre-sampling would read stale
        priorities and break the learner-mode equivalence matrix.  (The
        documented cost of PER's sample/update data dependence.)"""
        if n <= 0:
            return []   # before the eager submit below: a zero-update call
            # must not advance the buffers' sample RNG streams
        mode = self.cfg.learner
        prioritized = self.cfg.replay == "prioritized"
        if mode != "packed_pipelined" or prioritized:
            packed = mode != "dense"
            losses = []
            for _ in range(n):
                batch = self._stacked_sample_packed() if packed \
                    else self._stacked_sample()
                loss, td = self._update_once(batch, packed=packed)
                if prioritized:
                    self._apply_priorities(td)
                losses.append(self._loss_scalar(loss))
            return losses
        pool = self._get_sampler()
        fut = pool.submit(self._stacked_sample_packed_np)
        device_losses = []
        for k in range(n):
            host_batch = fut.result()
            if k + 1 < n:
                fut = pool.submit(self._stacked_sample_packed_np)
            # the update dispatch is async: XLA computes while the sampler
            # thread gathers; only the final host conversions block
            device_losses.append(
                self._update_once(self._ship(host_batch), packed=True)[0])
        return [self._loss_scalar(l) for l in device_losses]

    def train(self, episodes: int | None = None, log_every: int = 0) -> list[dict]:
        stats = []
        for _ in range(episodes or self.cfg.episodes):
            st = self.train_episode()
            stats.append(st)
            if log_every and st["episode"] % log_every == 0:
                print(f"[ep {st['episode']}] reward {st['mean_final_reward']:.3f} "
                      f"loss {st['loss']:.4f} eps {st['epsilon']:.3f}")
        return stats

    # ------------------------------------------------------------ #
    # checkpoint / resume (bit-exact)
    # ------------------------------------------------------------ #
    # Everything a continued run's bits depend on, at an EPISODE BOUNDARY:
    # the three stacked device trees, every worker's action RNG, every
    # replay buffer ring (priorities included — their sample RNG rides in
    # the buffer state), the dataset cursor, the episode counter (which
    # alone positions the target-update cadence and the PER beta anneal)
    # and the exact epsilon float.  NOT state: the engine (rebuilt from the
    # start assignment every reset), the chemistry cache and property
    # memo (pure deterministic memos — they change speed, never bits), and
    # the fleet views' sticky batch capacities (the resumed process
    # re-warms its own jit cache).

    def _config_fingerprint(self) -> str:
        """Canonical JSON of the full TrainerConfig — a resume against a
        DIFFERENT config is an operator error, caught loudly at load."""
        import dataclasses
        import json

        def enc(o):
            if isinstance(o, frozenset):
                return sorted(o)
            raise TypeError(f"unserialisable config field: {o!r}")
        return json.dumps(dataclasses.asdict(self.cfg), sort_keys=True,
                          default=enc)

    def state_dict(self) -> dict[str, np.ndarray]:
        """Flat ``{key: array}`` snapshot of the complete training state
        (``repro.checkpoint.save_flat`` layout)."""
        import json
        from repro.checkpoint.checkpoint import rng_state_to_array
        flat: dict[str, np.ndarray] = {}
        flat["meta/config"] = np.frombuffer(
            self._config_fingerprint().encode(), np.uint8).copy()
        flat["meta/episode"] = np.asarray(self.episode, np.int64)
        flat["meta/epsilon"] = np.asarray(self.epsilon, np.float64)
        flat["meta/n_updates"] = np.asarray(self.n_updates, np.int64)
        flat["meta/loss_log"] = np.asarray(self.loss_log, np.float64)
        flat["meta/reward_log"] = np.asarray(self.reward_log, np.float64)
        flat["meta/start_log"] = np.frombuffer(json.dumps(
            [list(t) for t in self.start_log]).encode(), np.uint8).copy()
        for w, rng in enumerate(self._worker_rngs):
            flat[f"rng/worker_{w}"] = rng_state_to_array(rng)
        for name, tree in (("params", self.params),
                           ("target", self.target_params),
                           ("opt", self.opt_state)):
            for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
                flat[f"{name}/{i}"] = np.asarray(leaf)
        for w, buf in enumerate(self.buffers):
            for k, v in buf.state_dict().items():
                flat[f"replay/{w}/{k}"] = v
        if self._dataset_stream is not None:
            for k, v in self._dataset_stream.state_dict().items():
                flat[f"dataset/{k}"] = v
        if self.worker_objectives is not None:
            # scenario objectives carry mutable state (novelty visit
            # counts) — snapshot it per worker so a resumed mixed fleet
            # keeps the exact intrinsic-bonus schedule
            for w, obj in enumerate(self.worker_objectives):
                flat[f"scenario/{w}"] = np.frombuffer(json.dumps(
                    obj.state_dict(), sort_keys=True).encode(),
                    np.uint8).copy()
        return flat

    def load_state_dict(self, flat) -> None:
        """Restore a :meth:`state_dict` snapshot; the continued run is
        bit-identical to one that never stopped (tests/multidevice
        crash-resume matrix)."""
        import json
        from repro.checkpoint.checkpoint import (
            CheckpointError, rng_state_from_array)
        got = bytes(np.asarray(flat["meta/config"], np.uint8)).decode()
        want = self._config_fingerprint()
        if got != want:
            raise CheckpointError(
                "checkpoint was written under a different TrainerConfig — "
                "resume requires the identical configuration")
        self.episode = int(flat["meta/episode"])
        self.epsilon = float(flat["meta/epsilon"])
        self.n_updates = int(flat["meta/n_updates"])
        self.loss_log = [float(x) for x in
                         np.asarray(flat["meta/loss_log"], np.float64)]
        self.reward_log = [float(x) for x in
                           np.asarray(flat["meta/reward_log"], np.float64)]
        self.start_log = [tuple(x) for x in json.loads(
            bytes(np.asarray(flat["meta/start_log"], np.uint8)).decode())]
        for w in range(len(self._worker_rngs)):
            self._worker_rngs[w] = rng_state_from_array(flat[f"rng/worker_{w}"])
        shard = lambda x: jax.device_put(x, fleet_sharding(self.mesh))
        for name, attr in (("params", "params"), ("target", "target_params"),
                           ("opt", "opt_state")):
            live = getattr(self, attr)
            treedef = jax.tree_util.tree_structure(live)
            leaves = []
            for i, ref in enumerate(jax.tree_util.tree_leaves(live)):
                key = f"{name}/{i}"
                if key not in flat:
                    raise CheckpointError(f"checkpoint missing leaf {key!r}")
                arr = np.asarray(flat[key])
                if tuple(arr.shape) != tuple(ref.shape):
                    raise CheckpointError(
                        f"leaf {key!r}: checkpoint shape {arr.shape} != "
                        f"live shape {tuple(ref.shape)}")
                # host -> shards directly: no full copy on one device
                leaves.append(shard(arr.astype(ref.dtype, copy=False)))
            setattr(self, attr, jax.tree_util.tree_unflatten(treedef, leaves))
        for w, buf in enumerate(self.buffers):
            prefix = f"replay/{w}/"
            sub = {k[len(prefix):]: v for k, v in flat.items()
                   if k.startswith(prefix)}
            if not sub:
                raise CheckpointError(f"checkpoint missing replay state "
                                      f"for worker {w}")
            buf.load_state_dict(sub)
        if self._dataset_stream is not None:
            sub = {k[len("dataset/"):]: v for k, v in flat.items()
                   if k.startswith("dataset/")}
            if not sub:
                raise CheckpointError(
                    "trainer streams episode starts but the checkpoint "
                    "carries no dataset cursor")
            self._dataset_stream.load_state_dict(sub)
        if self.worker_objectives is not None:
            # cfg.scenarios rides the config fingerprint, so a matching
            # checkpoint always carries every worker's scenario state
            for w, obj in enumerate(self.worker_objectives):
                key = f"scenario/{w}"
                if key not in flat:
                    raise CheckpointError(
                        f"trainer runs a scenario fleet but the checkpoint "
                        f"carries no objective state for worker {w}")
                obj.load_state_dict(json.loads(
                    bytes(np.asarray(flat[key], np.uint8)).decode()))

    def save_checkpoint(self, manager, step: int | None = None) -> int:
        """Snapshot into a ``repro.checkpoint.CheckpointManager`` (flat
        layout); returns the step label (default: the episode counter)."""
        label = self.episode if step is None else int(step)
        manager.save(label, self.state_dict(), flat=True)
        return label

    def restore_checkpoint(self, manager, step: int | None = None) -> int:
        """Load the latest (or given) snapshot from a manager; returns the
        restored episode counter."""
        _, flat = manager.restore_flat(step)
        self.load_state_dict(flat)
        return self.episode

    # ------------------------------------------------------------ #
    # evaluation / export
    # ------------------------------------------------------------ #
    def mean_params(self) -> dict:
        """The general model: worker-averaged parameters."""
        synced = self._sync(self.params)
        return jax.tree_util.tree_map(lambda x: np.asarray(x[0]), synced)

    def as_agent(self, epsilon: float = 0.0, seed: int = 1234) -> DQNAgent:
        """Materialise the general model as a single-model DQNAgent."""
        agent = DQNAgent(replace(self.cfg.dqn, epsilon_initial=epsilon), seed=seed,
                         network=self.network)
        mp = self.mean_params()
        agent.params = jax.tree_util.tree_map(jnp.asarray, mp)
        agent.target_params = jax.tree_util.tree_map(jnp.copy, agent.params)
        agent.epsilon = epsilon
        return agent


def greedy_optimize(
    agent: DQNAgent,
    molecules: list[Molecule],
    service: PropertyService,
    reward_cfg: RewardConfig,
    env_cfg: EnvConfig = EnvConfig(),
    seed: int = 0,
) -> list[StepRecord]:
    """Greedy (eps as configured in ``agent``) rollout over a molecule
    batch; returns final-step records — the paper's 'optimize the N
    antioxidants with the trained model' evaluation."""
    env = BatchedEnv(molecules, env_cfg, seed=seed)
    last: list[StepRecord] = []
    while not env.done:
        recs = env.step(agent, service, reward_cfg, buffer=None)
        if recs:
            last = recs
    return last


def optimization_failure_rate(records: list[StepRecord], *, bde_max: float = 76.0,
                              ip_min: float = 145.0) -> float:
    """Eq. 2: OFR = 1 - S/A (success = BDE < 76 and IP > 145)."""
    if not records:
        return 1.0
    ok = sum(
        1 for r in records
        if r.bde is not None and r.ip is not None and r.bde < bde_max and r.ip > ip_min
    )
    return 1.0 - ok / len(records)

