#!/usr/bin/env python3
"""Run DA-MolDQN's training and serving paths once on a TPU and check them.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip path only

One chip, four phases, all in this one process (a chip belongs to one
process at a time, so nothing here starts a child that touches JAX):

1. predictors: train the BDE and IP predictors from the committed corpus
   into ``<out>/predictors`` (emptied first, so nothing is loaded from an
   earlier run) and print the step count and held-out relative error;
2. training: ``DistributedTrainer`` at W=64 with the paper's Q-network
   widths, ``EnvConfig()`` and the default modes (fleet rollout, packed
   acting, packed learner, incremental chemistry), for 3 episodes; every
   episode's reward and loss must be finite;
3. kernel parity: one packed fleet-Q dispatch through the compiled Pallas
   kernel against the XLA path (float32 matmuls) on states from the replay
   buffers; the largest absolute difference must be <= 1e-5;
4. serving: a ``MoleculeOptService`` over the trained general model and
   predictors answers 8 seeded requests; every one must end terminal.

``--chips 4`` runs W=64 on a one-chip mesh and on the four-chip mesh and
holds them to the cross-device-count contract
(``repro.launch.verify.assert_nd_equivalent``), then one W=512 episode on
the four chips (a fleet one chip cannot hold) with each chip's peak memory.
Phases are functions of their sizes, so they can be called one at a time
(``python3 -c "import chip_smoke; ..."``) or rehearsed small on the CPU.

Any failed check raises.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed.  Without a TPU the script exits non-zero
before any phase.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
TERMINAL = ("completed", "degraded", "deadline_exceeded", "shed", "failed")
PARITY_TOL = 1e-5
PREDICTOR_STEPS = 1500      # ensure_trained's default


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(min_count: int = 1):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"FAIL: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this script runs on the chip")
    if len(devices) < min_count:
        sys.exit(f"FAIL: {min_count} TPU chips needed, JAX sees {len(devices)}")
    return devices


def peak_bytes(device) -> int | None:
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


# ------------------------------------------------------------------ #
# phases (each takes its sizes, so a CPU rehearsal can shrink them)
# ------------------------------------------------------------------ #
def train_predictors(out: Path, steps: int):
    from repro.predictors.training import trained_service

    cache = out / "predictors"
    shutil.rmtree(cache, ignore_errors=True)
    t0 = time.perf_counter()
    service, metrics = trained_service(str(cache), steps=steps)
    errs = {k: metrics[k]["rel_err_mean"] for k in ("bde", "ip")}
    log(f"[predictors] {steps} steps each | held-out mean relative error "
        f"BDE {errs['bde']:.4%} IP {errs['ip']:.4%} | "
        f"{time.perf_counter() - t0:.1f}s")
    if not all(math.isfinite(e) for e in errs.values()):
        raise RuntimeError(f"predictor held-out error not finite: {errs}")
    return service


def build_trainer(service, n_workers: int, *, network=None, mesh=None,
                  episodes: int = 3):
    """The default trainer (``TrainerConfig`` defaults but the fleet size)
    over the antioxidant train split, cycled to the fleet's molecule
    count, with Eq. 1 normalised on that split."""
    from repro.core import RewardConfig, TrainerConfig
    from repro.core.distributed import DistributedTrainer
    from repro.data.datasets import (antioxidant_dataset,
                                     dataset_property_table, train_test_split)

    train, _ = train_test_split(antioxidant_dataset(600))
    props = dataset_property_table(train)
    rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])
    cfg = TrainerConfig(n_workers=n_workers, episodes=episodes)
    need = n_workers * cfg.mols_per_worker
    mols = [train[i % len(train)] for i in range(need)]
    return DistributedTrainer(cfg, mols, service, rcfg, mesh=mesh,
                              network=network)


def run_episodes(tr, episodes: int, tag: str) -> list[dict]:
    stats = []
    for _ in range(episodes):
        t0 = time.perf_counter()
        st = tr.train_episode()
        finite = math.isfinite(st["mean_final_reward"]) \
            and math.isfinite(st["loss"])
        log(f"[{tag}] episode {st['episode']} reward "
            f"{st['mean_final_reward']!r} loss {st['loss']!r} finite {finite} "
            f"| {time.perf_counter() - t0:.1f}s")
        if not finite:
            raise RuntimeError(f"{tag}: episode {st['episode']} reward or "
                               f"loss not finite: {st}")
        stats.append(st)
    return stats


def train_fleet(service, n_workers: int, episodes: int, network=None):
    import jax

    tr = build_trainer(service, n_workers, network=network,
                       episodes=episodes)
    W = tr.n_live_workers
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(tr.params)) // W
    log(f"[train] W={W} x {tr.cfg.mols_per_worker} molecules, "
        f"hidden {tr.network.hidden}, {n_params} params per worker, "
        f"max_steps {tr.cfg.env.max_steps}, rollout {tr.cfg.rollout}, "
        f"acting {tr.cfg.acting}, learner {tr.cfg.learner}, "
        f"chem {tr.cfg.chem}, mesh {tr.mesh.devices.size} device(s)")
    run_episodes(tr, episodes, "train")
    log(f"[train] {episodes} episodes x {tr.cfg.env.max_steps} env steps, "
        f"{tr.n_updates} updates, {tr.n_q_dispatches} Q dispatches | "
        f"peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    return tr


def kernel_parity(tr, n_rows: int = 128) -> float:
    """The packed fleet-Q dispatch with ``use_pallas_qnet`` against the
    XLA dispatch under float32 matmuls, on replay-buffer states."""
    import jax
    import numpy as np

    from repro.core.distributed import fleet_q_dispatches
    from repro.launch.mesh import fleet_sharding

    samples = [b.sample_packed(n_rows, tr.cfg.max_candidates)
               for b in tr.buffers]
    bits = np.stack([s["state_bits"] for s in samples])
    frac = np.stack([s["state_frac"] for s in samples])
    xb, xf = jax.device_put((bits, frac), fleet_sharding(tr.mesh))
    _, kernel = fleet_q_dispatches(tr.network, tr.mesh, use_pallas_qnet=True)
    _, xla = fleet_q_dispatches(tr.network, tr.mesh)
    compiled = kernel.lower(tr.params, xb, xf).compile()
    on_chip = tr.mesh.devices.flat[0].platform == "tpu"
    if on_chip and "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("the Pallas fleet dispatch holds no TPU kernel")
    q_kernel = np.asarray(compiled(tr.params, xb, xf))
    with jax.default_matmul_precision("highest"):
        q_xla = np.asarray(xla(tr.params, xb, xf))
    diff = float(np.max(np.abs(q_kernel - q_xla)))
    log(f"[kernel] packed fleet Q W={bits.shape[0]} C={n_rows}: Pallas "
        f"kernel (compiled, interpret=False) vs XLA max |diff| {diff!r} "
        f"(limit {PARITY_TOL}), |q| max {float(np.max(np.abs(q_xla)))!r}")
    if not diff <= PARITY_TOL:
        raise RuntimeError(f"kernel parity {diff} > {PARITY_TOL}")
    return diff


def serve(service, network, params, n_requests: int = 8, seed: int = 0):
    import jax
    import jax.numpy as jnp

    from repro.serving import (MoleculeOptService, ServeConfig, StreamConfig,
                               drive_open_loop, seeded_request_stream)

    params = jax.tree_util.tree_map(jnp.asarray, params)
    svc = MoleculeOptService(network, params, service,
                             cfg=ServeConfig(n_slots=8, seed=seed))
    arrivals = seeded_request_stream(StreamConfig(n_requests=n_requests,
                                                  seed=seed))
    svc.reserve_candidates(256)
    t0 = time.perf_counter()
    drive_open_loop(svc, arrivals)
    wall = time.perf_counter() - t0
    for r in sorted(svc.results, key=lambda r: r.request_id):
        log(f"[serve] {r.request_id} {r.status} steps {r.steps_used} "
            f"best {r.best_reward!r} {r.best_smiles}")
    statuses = [r.status for r in svc.results]
    log(f"[serve] {len(statuses)}/{n_requests} requests terminal in "
        f"{wall:.2f}s | {svc.stats()['status_counts']}")
    if len(statuses) != n_requests or \
            any(s not in TERMINAL for s in statuses):
        raise RuntimeError(f"not every request reached a terminal status: "
                           f"{statuses}")
    bad = [r.request_id for r in svc.results if r.status == "completed"
           and not math.isfinite(r.best_reward)]
    if bad:
        raise RuntimeError(f"completed requests without a finite reward: {bad}")


def nd_comparison(n_workers: int = 64, episodes: int = 2,
                  network=None) -> None:
    """W=``n_workers`` on a one-chip mesh against the four-chip mesh,
    held to the cross-nd contract."""
    import gc

    import numpy as np

    from repro.launch.mesh import make_host_mesh
    from repro.launch.verify import (ND_ATOL, ND_RTOL, assert_nd_equivalent,
                                     trainer_report)
    from repro.predictors.service import OracleService

    reports = {}
    for nd in (1, 4):
        tr = build_trainer(OracleService(), n_workers, network=network,
                           mesh=make_host_mesh(nd), episodes=episodes)
        run_episodes(tr, episodes, f"nd={nd} W={n_workers}")
        reports[nd] = trainer_report(tr)
        del tr
        gc.collect()
    ref, got = reports[1], reports[4]
    keys = sorted(k for k in ref if k.startswith("param_"))
    worst = max(float(np.max(np.abs(got[k] - ref[k]))) for k in keys)
    log(f"[nd] W={n_workers} nd=4 vs nd=1: transitions identical "
        f"{list(got['transition_digests']) == list(ref['transition_digests'])}"
        f", rewards identical {np.array_equal(got['rewards'], ref['rewards'])}"
        f", max |loss diff| "
        f"{float(np.max(np.abs(got['losses'] - ref['losses'])))!r}"
        f", max |param diff| {worst!r} (contract rtol {ND_RTOL} atol {ND_ATOL})")
    assert_nd_equivalent(ref, got, f"W={n_workers} nd=4 vs nd=1")


def large_fleet(n_workers: int = 512, network=None) -> None:
    """One episode of a fleet one chip cannot hold, on the four-chip
    mesh, with each chip's peak memory."""
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.predictors.service import OracleService

    tr = build_trainer(OracleService(), n_workers, network=network,
                       mesh=make_host_mesh(4), episodes=1)
    run_episodes(tr, 1, f"nd=4 W={n_workers}")
    log(f"[nd] W={n_workers} on 4 chips: {tr.n_updates} updates | "
        f"peak_bytes_in_use per device "
        f"{[peak_bytes(d) for d in jax.devices()]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--out", default=str(REPO / "chiprun_out" / "chip_smoke"),
                    help="directory for the in-run predictor weights")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    if not (REPO / "src" / "repro").is_dir():
        sys.exit(f"FAIL: {REPO} holds no repro package (src/repro)")
    sys.path.insert(0, str(REPO / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[setup] {len(devices)} x {devices[0].device_kind}, compile cache "
        f"{enable_compile_cache()}")
    if args.chips == 4:
        nd_comparison()
        large_fleet()
    else:
        service = train_predictors(Path(args.out), PREDICTOR_STEPS)
        tr = train_fleet(service, 64, episodes=3)
        kernel_parity(tr)
        serve(service, tr.network, tr.mean_params())
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
