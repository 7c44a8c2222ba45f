"""Training cells: the DA-MolDQN fleet's own loop, ``train_episode``.

Set-up builds one ``DistributedTrainer`` from the seed, warms every shape
the window uses (the fleet-Q dispatch at the reserved candidate capacity,
every padded predictor batch), and runs the warm episode through the
window's own call.  That episode is the one the reference follows: its
ten acting dispatches (initial weights), its predictor answers, its first
learner call (every update from the initial weights) and the episode
sync.  The window then calls ``train_episode`` until ``--seconds`` have
passed; no episode starts after that.

What a driver provides (``harness.driver_class`` finds ``drivers/<name>.py``
by the workload's ``driver`` and takes its ``Run``):

    Run(cfg, wl, seed, spans)   ``pseed``, the program's seed; ``capture``,
                                what the timed path produced; ``shapes``,
                                the number of shapes set-up warmed; ``cap``
    build(), warm()             set-up
    counters()                  a snapshot; the window's ``delta`` of two
                                is in every reader's ``ctx``
    window(seconds)             ``{"window_s", "attempted", "failed", ...}``,
                                in ``ctx["w"]``
    free()                      drop the program's device state
    check(cfg, pseed, capture, rng) -> (numbers, aux)   against the reference
    readings(cfg, pseed, capture, rng) -> dict          for ``control.py``
    control_seconds             the window ``control.py`` drives before
                                ``readings`` (0: none)

Traffic parameters (``workloads/<cell>.json``, key ``traffic``):

    starts            "train_split" (the paper's 256 antioxidant training
                      molecules, fixed each episode) or "stream" (fresh
                      starts each episode from the ``dataset`` pool)
    dataset, dataset_size   the pool a stream draws from
    reserve_candidates      the candidate capacity warmed in set-up
"""

from __future__ import annotations

import math
import time

import numpy as np

from .. import control, correct, harness
from . import common


class TrainRun:
    check = staticmethod(correct.check_train)
    readings = staticmethod(control.train_readings)
    control_seconds = 0

    def __init__(self, cfg: dict, wl: dict, seed: int, spans: harness.Spans):
        self.cfg, self.wl, self.seed, self.spans = cfg, wl, seed, spans
        self.pseed = harness.program_seed(seed)
        self.capture: dict = {}
        self.rows = 0              # candidate rows evaluated by acting
        self.next_rows = 0         # useful successor rows the learner read
        self.transitions = 0
        self._recording = False
        self._hooked_policy = False

    # -------------------------------------------------------------- #
    def build(self) -> None:
        from repro.core import RewardConfig, TrainerConfig
        from repro.core.agent import DQNConfig
        from repro.core.distributed import DistributedTrainer
        from repro.core.env import EnvConfig
        from repro.data.datasets import (antioxidant_dataset,
                                         dataset_property_table,
                                         train_test_split)
        from repro.predictors.service import PropertyService

        cfg, traffic = self.cfg, self.wl["traffic"]
        net, bde_m, ip_m, w = common.make_weights(cfg, self.pseed)
        self.inner_service = PropertyService(bde_m, w["bde"], ip_m, w["ip"],
                                             max_atoms=cfg["predictors"]["max_atoms"])
        self.service = common.SpannedService(self.inner_service, self.spans)
        ds = antioxidant_dataset(cfg["data"]["antioxidant_count"])
        train, _ = train_test_split(ds)
        props = dataset_property_table(train)
        rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])
        t, lr = cfg["trainer"], cfg["learner"]
        kw = dict(n_workers=t["n_workers"], mols_per_worker=t["mols_per_worker"],
                  updates_per_episode=t["updates_per_episode"],
                  train_batch_size=t["train_batch_size"],
                  max_candidates=t["max_candidates"],
                  replay_capacity=t["replay_capacity"], rollout=t["rollout"],
                  acting=t["acting"], learner=t["learner"], chem=t["chem"],
                  sync_mode=t["sync_mode"], episodes=t["episodes"],
                  env=EnvConfig(max_steps=t["max_steps"], max_atoms=t["max_atoms"]),
                  dqn=DQNConfig(lr=lr["lr"], discount=lr["discount"],
                                grad_clip=lr["clip"],
                                epsilon_decay=t["epsilon_decay"]),
                  seed=self.pseed)
        if traffic["starts"] == "stream":
            kw.update(dataset=traffic["dataset"],
                      dataset_size=traffic["dataset_size"])
            mols = None
        else:
            need = t["n_workers"] * t["mols_per_worker"]
            mols = [train[i % len(train)] for i in range(need)]
        self.trainer = tr = DistributedTrainer(TrainerConfig(**kw), mols,
                                               self.service, rcfg, network=net)
        self.net = net
        # spans around the layers, installed as instance attributes
        self._rollout_episode = tr.rollout_episode
        tr.rollout_episode = self._rollout
        self._run_updates = tr.run_updates
        tr.run_updates = self._updates
        self._engine_step = tr.engine.step
        tr.engine.step = self._env_step
        for buf in tr.buffers:
            buf.sample_packed = self._sampler(buf.sample_packed)
        tr.reserve_candidates(traffic["reserve_candidates"])
        self.shapes = common.warm_predictor_shapes(self.inner_service, ds)

    # ---- hooks ---------------------------------------------------- #
    def _rollout(self):
        with self.spans.span("bench.rollout_episode"):
            records = self._rollout_episode()
        self.transitions += sum(len(r) for r in records)
        return records

    def _env_step(self, policy, *args, **kwargs):
        if not self._hooked_policy:
            dispatch = policy.fleet_q_values_packed

            def q_packed(bits_pw, frac_pw):
                with self.spans.span("bench.q_dispatch"):
                    q = dispatch(bits_pw, frac_pw)
                self.rows += sum(b.shape[0] for b in bits_pw)
                if self._recording:
                    self._record_dispatch(bits_pw, frac_pw, q)
                return q
            policy.fleet_q_values_packed = q_packed
            self._hooked_policy = True
        with self.spans.span("bench.env_step"):
            return self._engine_step(policy, *args, **kwargs)

    def _record_dispatch(self, bits_pw, frac_pw, q) -> None:
        eng = self.trainer.engine
        cands = [[a for s in eng.workers[w] if s.steps_left > 0
                  for a in s.candidates] for w in range(len(bits_pw))]
        self.capture.setdefault("dispatches", []).append({
            "bits": [np.array(b) for b in bits_pw],
            "frac": [np.array(f) for f in frac_pw],
            "q": [np.array(x) for x in q], "cands": cands})

    def _sampler(self, sample_packed):
        def sample(batch_size, max_candidates=160, **kw):
            out = sample_packed(batch_size, max_candidates, **kw)
            C = out["next_bits"].shape[-2]
            self.next_rows += int(np.where(out["dones"] > 0, 0,
                                           np.minimum(out["next_counts"], C)).sum())
            if self._recording:
                self.capture["batches"][-1].append(out)
            return out
        return sample

    def _updates(self, n: int):
        with self.spans.span("bench.run_updates"):
            if not self._recording:
                return self._run_updates(n)
            # the warm episode: one update per call (the same program and
            # batches as one call of n), reading the first gradient from
            # the optimizer's state after the first
            import jax
            import jax.numpy as jnp

            losses = []
            for u in range(n):
                self.capture["batches"].append([])
                losses += self._run_updates(1)
                if u == 0:
                    mu = self.trainer.opt_state.mu
                    self.capture["grad_norms"] = np.stack([
                        np.asarray(jnp.sqrt(jnp.sum(jnp.square(x),
                                                    axis=tuple(range(1, x.ndim)))))
                        for x in jax.tree_util.tree_leaves(mu)], axis=1) \
                        / (1.0 - self.cfg["learner"]["b1"])
            self.capture["losses"] = np.asarray(losses)
            return losses

    # -------------------------------------------------------------- #
    def warm(self) -> None:
        """The warm episode: fills replay, compiles the learner and sync,
        and is the stretch the reference follows."""
        import jax
        import jax.numpy as jnp

        tr = self.trainer
        p0 = jax.tree_util.tree_map(jnp.copy, tr.params)
        self.capture["batches"] = []
        self.service.record = []
        self._recording = True
        st = tr.train_episode()
        self._recording = False
        self.capture["predictions"] = self.service.record
        self.service.record = None
        if not self.capture.get("losses", np.zeros(0)).size:
            raise harness.BenchError("the warm episode made no learner update")
        self.capture["change_norms"] = np.stack([
            np.asarray(jnp.sqrt(jnp.sum(jnp.square(a - b),
                                        axis=tuple(range(1, a.ndim)))))
            for a, b in zip(jax.tree_util.tree_leaves(tr.params),
                            jax.tree_util.tree_leaves(p0))], axis=1)
        del p0
        self.capture["batches"] = [
            {k: np.stack([b[k] for b in per]) for k in per[0]}
            for per in self.capture["batches"]]
        self.warm_stats = st
        self.cap = tr.candidate_capacity

    def counters(self) -> dict:
        tr = self.trainer
        return {"chem": common.chem_counters(tr.engine),
                "spans": self.spans.snapshot(),
                "rows": self.rows, "next_rows": self.next_rows,
                "updates": tr.n_updates, "transitions": self.transitions,
                "predict_mols": self.inner_service.n_predictor_mols,
                "predict_batches": self.inner_service.n_predictor_batches,
                "episodes": tr.episode}

    def window(self, seconds: float) -> dict:
        """Whole episodes until ``seconds`` have passed."""
        tr = self.trainer
        t0 = time.perf_counter()
        attempted = failed = 0
        while time.perf_counter() - t0 < seconds:
            t_ep = time.perf_counter()
            st = tr.train_episode()
            harness.log(f"[episode] {st['episode']} {time.perf_counter() - t_ep:.3f} s "
                        f"loss {st['loss']!r} reward {st['mean_final_reward']!r}")
            attempted += 1
            if not (math.isfinite(st["loss"])
                    and math.isfinite(st["mean_final_reward"])):
                failed += 1
        return {"window_s": time.perf_counter() - t0, "attempted": attempted,
                "failed": failed}

    def free(self) -> None:
        """Drop the program's device state before the reference runs."""
        import gc

        self.trainer = None
        self.inner_service = None
        self.service = None
        gc.collect()


Run = TrainRun
