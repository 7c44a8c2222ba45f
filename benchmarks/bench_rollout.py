"""Fleet-level rollout acting paths, scaled to the paper's 512 molecules.

Per worker count the bench rolls identical seeded episodes under the acting
paths and reports

* Q-network jit dispatches per environment step (fleet target: exactly 1),
* predictor batches per environment step (``PropertyService`` §3.6 stats;
  cache disabled so every step predicts),
* XLA recompiles during the measured episodes (``RecompileCounter``; the
  shape-discipline claim is that this is ZERO after warmup — at any W),
* end-to-end steps per second and the speedup of the new pipelined+sharded
  path over the PR-1 fleet engine,
* acting seconds per step (time inside Q evaluation + property prediction),
* acting H2D bytes per step (``Trainer.acting_h2d_bytes``): the dense f32
  ``[W, C, 2049]`` batch vs the packed u8 ``[W, C, 256]`` bit planes, same
  engine mode, same episode stream — plus the dense/packed reduction.

Every cell is (rollout mode, acting representation).  W=64 still includes
the seed sequential per-worker path; at W in {256, 512} it would be
pathologically slow (W dispatches + W predictor batches per step), so only
the ``fleet`` engine and the ``fleet_pipelined`` (sharded dispatch +
overlapped chemistry) path are compared, under the packed / packed_async /
dense acting representations.

``python benchmarks/bench_rollout.py --smoke`` runs the CI gate: W=16,
pipelined path with packed acting, randomly-initialised predictors (no
training needed), and FAILS if any XLA compile happens after warmup, if
the dispatch count is not exactly one per step, or if packed acting ships
more than 0.05x the dense acting H2D bytes per step.  The gate also runs
a mixed-scenario cell (heterogeneous objectives cycled across the fleet
through the vectorized reward layer) which must hold the same
zero-recompile / one-dispatch bar and reports its steps/s overhead vs the
homogeneous fleet.  The gate is
mesh-size-agnostic: CI also runs it under
``XLA_FLAGS=--xla_force_host_platform_device_count=2`` (the
multidevice-smoke job), which shards the fleet over nd=2 host devices and
must hold the same zero-recompile bar.
"""

from __future__ import annotations

import os
import sys
import time

if __package__ in (None, ""):  # `python benchmarks/bench_rollout.py --smoke`
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import emit, services
from repro.core import DQNConfig, EnvConfig, TrainerConfig
from repro.core.agent import QNetwork
from repro.core.distributed import DistributedTrainer
from repro.core.jit_stats import RecompileCounter
from repro.predictors.service import PropertyService

MAX_STEPS = 3
# (rollout mode, acting representation) cells per worker count: the
# sequential path only where it is affordable; (fleet, dense) vs
# (fleet, packed) isolates the acting representation at every W
PLANS = (
    (64, (("per_worker", "dense"), ("fleet", "dense"), ("fleet", "packed"),
          ("fleet_pipelined", "packed"),
          ("fleet_pipelined", "packed_async"))),
    (256, (("fleet", "dense"), ("fleet", "packed"),
           ("fleet_pipelined", "packed"))),
    (512, (("fleet", "dense"), ("fleet", "packed"),
           ("fleet_pipelined", "packed"),
           ("fleet_pipelined", "packed_async"))),
)


def _uncached_service(base: PropertyService) -> PropertyService:
    """Share the trained predictor params; fresh stats, no LRU cache so the
    per-step batch counts are structural, not workload-dependent."""
    return PropertyService(base.bde_model, base.bde_params,
                           base.ip_model, base.ip_params, cache=None)


def _instrument_acting(tr: DistributedTrainer, svc: PropertyService) -> dict:
    """Accumulate wall time spent in Q evaluation + property prediction
    (both synchronous: results are converted to numpy before returning)."""
    acting = {"s": 0.0}

    def timed(fn):
        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            out = fn(*a, **kw)
            acting["s"] += time.perf_counter() - t0
            return out
        return wrapper

    # dense entry point + the packed split pair; the sync packed path
    # (fleet_q_values_packed) routes through the instance-patched
    # dispatch/fetch attributes, so wrapping the pair covers it too
    pol = tr._fleet_policy
    pol.fleet_q_values = timed(pol.fleet_q_values)
    pol.fleet_q_dispatch_packed = timed(pol.fleet_q_dispatch_packed)
    pol.fleet_q_fetch = timed(pol.fleet_q_fetch)
    for view in tr._views:
        view.q_values = timed(view.q_values)
    svc.predict = timed(svc.predict)
    return acting


def _measure(tr: DistributedTrainer, svc: PropertyService, counter,
             warmup: int, episodes: int) -> dict:
    """Warm up (jit shapes + capacity reserve), then measure."""
    acting = _instrument_acting(tr, svc)
    for _ in range(warmup):
        tr.rollout_episode()
    # reserve one ladder rung of headroom past the warmup high-water mark so
    # candidate-count drift in the measured episodes cannot grow the shape
    if tr.candidate_capacity:
        tr.reserve_candidates(int(tr.candidate_capacity * 1.3))

    tr.n_q_dispatches = 0
    tr.acting_h2d_bytes = 0
    b0, c0 = svc.n_predictor_batches, svc.n_predict_calls
    acting["s"] = 0.0
    mark = counter.count
    t0 = time.perf_counter()
    for _ in range(episodes):
        tr.rollout_episode()
    dt = time.perf_counter() - t0

    n_steps = episodes * MAX_STEPS
    return {
        "steps_per_s": n_steps / dt,
        "q_dispatches_per_step": tr.n_q_dispatches / n_steps,
        "predict_calls_per_step": (svc.n_predict_calls - c0) / n_steps,
        "predictor_batches_per_step": (svc.n_predictor_batches - b0) / n_steps,
        "acting_s_per_step": acting["s"] / n_steps,
        "acting_h2d_bytes_per_step": tr.acting_h2d_bytes / n_steps,
        "recompiles": counter.delta_since(mark),
    }


def _trainer(W: int, mode: str, mols, svc, rcfg, net,
             acting: str = "packed", scenarios=None) -> DistributedTrainer:
    cfg = TrainerConfig(
        n_workers=W, mols_per_worker=1, episodes=1, sync_mode="episode",
        rollout=mode, acting=acting, train_batch_size=8, max_candidates=16,
        dqn=DQNConfig(), env=EnvConfig(max_steps=MAX_STEPS), seed=0,
        scenarios=scenarios)
    return DistributedTrainer(cfg, mols, svc, rcfg, network=net)


def run(scale: str = "quick") -> None:
    counter = RecompileCounter.install()
    base, train, _, rcfg, _ = services()
    warmup = 2  # covers the jit shapes the measured episodes revisit
    net = QNetwork(hidden=(128, 32))

    for W, cells in PLANS:
        # small-W episodes are cheap: buy variance reduction where it costs
        # little (a 6-step sample on a shared box is hopelessly noisy)
        episodes = (6 if W <= 64 else 2) if scale == "quick" else (10 if W <= 64 else 4)
        mols = (train * (W // len(train) + 1))[:W]
        speed: dict[tuple, float] = {}
        acting_per_step: dict[tuple, float] = {}
        h2d: dict[tuple, float] = {}
        for mode, acting in cells:
            svc = _uncached_service(base)
            tr = _trainer(W, mode, mols, svc, rcfg, net, acting=acting)
            m = _measure(tr, svc, counter, warmup, episodes)
            speed[mode, acting] = m["steps_per_s"]
            acting_per_step[mode, acting] = m["acting_s_per_step"]
            h2d[mode, acting] = m["acting_h2d_bytes_per_step"]
            key = f"rollout.w{W}.{mode}.{acting}"
            emit(f"{key}.q_dispatches_per_step",
                 round(m["q_dispatches_per_step"], 2), "calls",
                 f"seed path: {W}" if mode == "per_worker" else "fleet target: exactly 1")
            emit(f"{key}.predict_calls_per_step",
                 round(m["predict_calls_per_step"], 2), "calls")
            emit(f"{key}.predictor_batches_per_step",
                 round(m["predictor_batches_per_step"], 2), "calls")
            emit(f"{key}.recompiles_after_warmup",
                 m["recompiles"], "compiles", "shape discipline target: 0")
            emit(f"{key}.steps_per_s",
                 round(m["steps_per_s"], 3), "steps/s")
            emit(f"{key}.acting_ms_per_step",
                 round(m["acting_s_per_step"] * 1e3, 1), "ms",
                 "Q dispatch + property predict only")
            emit(f"{key}.acting_h2d_bytes_per_step",
                 int(m["acting_h2d_bytes_per_step"]), "B",
                 "fleet Q input batches shipped host -> device")
        if ("per_worker", "dense") in speed:
            emit(f"rollout.w{W}.fleet_speedup",
                 round(speed["fleet", "dense"] / speed["per_worker", "dense"], 2),
                 "x", "fleet engine vs sequential per-worker acting, end to end")
        emit(f"rollout.w{W}.acting_h2d_reduction",
             round(h2d["fleet", "dense"] / h2d["fleet", "packed"], 1), "x",
             "packed u8 candidate planes vs dense f32 batches; "
             "acceptance target at W=512: >= 10x")
        emit(f"rollout.w{W}.packed_acting_speedup",
             round(speed["fleet", "packed"] / speed["fleet", "dense"], 2), "x",
             "same fleet engine, packed vs dense acting representation")
        emit(f"rollout.w{W}.pipelined_speedup",
             round(speed["fleet_pipelined", "packed"] / speed["fleet", "packed"], 2),
             "x", "pipelined+sharded path vs the fleet engine, end to end")
        emit(f"rollout.w{W}.pipelined_acting_speedup",
             round(acting_per_step["fleet", "packed"]
                   / acting_per_step["fleet_pipelined", "packed"], 2),
             "x", "overlapped chemistry hides part of the property batch")
        if ("fleet_pipelined", "packed_async") in speed:
            emit(f"rollout.w{W}.async_acting_speedup",
                 round(speed["fleet_pipelined", "packed_async"]
                       / speed["fleet_pipelined", "packed"], 2), "x",
                 "eager Q dispatch overlapped with selection + early chem")


# ------------------------------------------------------------------ #
# CI smoke gate: zero recompiles after warmup on the pipelined path
# ------------------------------------------------------------------ #
def smoke(W: int = 16) -> None:
    """Fast, training-free shape-discipline gate (random predictor params:
    recompile behaviour only depends on shapes, not weights)."""
    import jax

    from repro.core import RewardConfig
    from repro.data.datasets import antioxidant_dataset, dataset_property_table
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS

    counter = RecompileCounter.install()
    bde_model, ip_model = AlfabetS(), AIMNetS()
    svc = PropertyService(bde_model, bde_model.init(jax.random.PRNGKey(0)),
                          ip_model, ip_model.init(jax.random.PRNGKey(1)),
                          cache=None)
    mols = antioxidant_dataset(W)
    props = dataset_property_table(mols)
    rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])
    net = QNetwork(hidden=(64, 32))
    tr = _trainer(W, "fleet_pipelined", mols, svc, rcfg, net, acting="packed")

    mark0 = counter.count
    m = _measure(tr, svc, counter, warmup=2, episodes=2)
    warmup_compiles = counter.count - mark0 - m["recompiles"]

    # dense-acting reference on the same workload: the identical episode
    # stream (the acting representations are bit-equivalent), so the byte
    # ratio compares like shapes.  gate: packed ships <= 0.05x the bytes.
    svc_d = _uncached_service(svc)
    tr_d = _trainer(W, "fleet", mols, svc_d, rcfg, net, acting="dense")
    m_d = _measure(tr_d, svc_d, counter, warmup=1, episodes=2)
    h2d_ratio = (m["acting_h2d_bytes_per_step"]
                 / max(m_d["acting_h2d_bytes_per_step"], 1e-9))

    emit(f"rollout.smoke.w{W}.devices", jax.device_count(), "devices",
         "mesh size the fleet acted on (nd; force with XLA_FLAGS)")
    emit(f"rollout.smoke.w{W}.warmup_compiles", warmup_compiles, "compiles")
    emit(f"rollout.smoke.w{W}.recompiles_after_warmup", m["recompiles"],
         "compiles", "gate: must be 0")
    emit(f"rollout.smoke.w{W}.q_dispatches_per_step",
         round(m["q_dispatches_per_step"], 2), "calls", "gate: must be 1.0")
    emit(f"rollout.smoke.w{W}.steps_per_s", round(m["steps_per_s"], 3),
         "steps/s", "pipelined packed acting, random predictor params")
    emit(f"rollout.smoke.w{W}.packed_acting_h2d_bytes_per_step",
         int(m["acting_h2d_bytes_per_step"]), "B")
    emit(f"rollout.smoke.w{W}.dense_acting_h2d_bytes_per_step",
         int(m_d["acting_h2d_bytes_per_step"]), "B")
    emit(f"rollout.smoke.w{W}.acting_h2d_ratio", round(h2d_ratio, 4), "frac",
         "packed / dense acting bytes per step; gate: <= 0.05")

    # mixed-scenario cell (PR 10): the SAME pipelined fleet, heterogeneous
    # objectives cycled across workers through the fleet-vectorized reward
    # layer.  The reward layer is NumPy-side, so the shape-discipline gate
    # must hold unchanged (0 recompiles after warmup, 1 Q dispatch/step);
    # the steps/s ratio vs the homogeneous fleet is the layer's overhead.
    mix = ("antioxidant", "qed", "plogp", "antioxidant_novel")
    svc_m = _uncached_service(svc)
    tr_m = _trainer(W, "fleet_pipelined", mols, svc_m, rcfg, net,
                    acting="packed", scenarios=mix)
    m_m = _measure(tr_m, svc_m, counter, warmup=2, episodes=2)
    mixed_overhead = (m["steps_per_s"] / max(m_m["steps_per_s"], 1e-9)) - 1.0
    emit(f"rollout.smoke.w{W}.mixed.steps_per_s",
         round(m_m["steps_per_s"], 3), "steps/s",
         f"scenarios={','.join(mix)} cycled across {W} workers")
    emit(f"rollout.smoke.w{W}.mixed.recompiles_after_warmup",
         m_m["recompiles"], "compiles", "gate: must be 0")
    emit(f"rollout.smoke.w{W}.mixed.q_dispatches_per_step",
         round(m_m["q_dispatches_per_step"], 2), "calls", "gate: must be 1.0")
    emit(f"rollout.smoke.w{W}.mixed_overhead_frac",
         round(mixed_overhead, 4), "frac",
         "mixed-fleet slowdown vs homogeneous (steps/s ratio - 1)")

    if warmup_compiles <= 0:
        raise SystemExit("smoke self-check failed: warmup compiled nothing — "
                         "the recompile counter is not observing this process")
    if m["recompiles"] != 0:
        raise SystemExit(
            f"FAIL: {m['recompiles']} XLA compile(s) during measured episodes "
            f"(shape discipline broken on the pipelined path)")
    if m["q_dispatches_per_step"] != 1.0:
        raise SystemExit(
            f"FAIL: {m['q_dispatches_per_step']} Q dispatches/step (expected 1)")
    if h2d_ratio > 0.05:
        raise SystemExit(
            f"FAIL: packed acting ships {h2d_ratio:.4f}x the dense H2D "
            f"bytes/step (gate: <= 0.05)")
    if m_m["recompiles"] != 0:
        raise SystemExit(
            f"FAIL: {m_m['recompiles']} XLA compile(s) during the measured "
            f"mixed-scenario episodes (objectives leaked into jit shapes)")
    if m_m["q_dispatches_per_step"] != 1.0:
        raise SystemExit(
            f"FAIL: mixed fleet made {m_m['q_dispatches_per_step']} Q "
            f"dispatches/step (expected 1)")
    print(f"SMOKE PASS: W={W} on {jax.device_count()} device(s), "
          f"{warmup_compiles} warmup compiles, 0 recompiles after warmup "
          f"(homogeneous AND mixed-scenario), 1 Q dispatch/step, "
          f"packed/dense acting H2D ratio {h2d_ratio:.4f}, "
          f"mixed-fleet overhead {mixed_overhead:+.1%}")


def measure_acting_h2d(W: int = 512, episodes: int = 1) -> dict:
    """Measured acting H2D bytes/step at the paper's fleet size, dense vs
    packed on the SAME fleet engine.  Training-free (random predictor
    params): the byte counters are structural — they depend on the sticky
    buffer shapes the episode stream reaches, not on predictor weights."""
    import jax

    from repro.core import RewardConfig
    from repro.data.datasets import antioxidant_dataset, dataset_property_table
    from repro.predictors.gnn import AlfabetS
    from repro.predictors.ip_net import AIMNetS

    counter = RecompileCounter.install()
    bde_model, ip_model = AlfabetS(), AIMNetS()
    base = PropertyService(bde_model, bde_model.init(jax.random.PRNGKey(0)),
                           ip_model, ip_model.init(jax.random.PRNGKey(1)),
                           cache=None)
    mols = antioxidant_dataset(W)
    props = dataset_property_table(mols)
    rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])
    net = QNetwork(hidden=(64,))

    bytes_per_step: dict[str, float] = {}
    for acting in ("dense", "packed"):
        svc = _uncached_service(base)
        tr = _trainer(W, "fleet", mols, svc, rcfg, net, acting=acting)
        m = _measure(tr, svc, counter, warmup=1, episodes=episodes)
        bytes_per_step[acting] = m["acting_h2d_bytes_per_step"]
        emit(f"rollout.h2d.w{W}.{acting}.acting_h2d_bytes_per_step",
             int(m["acting_h2d_bytes_per_step"]), "B",
             "fleet engine, measured byte counter")
    reduction = bytes_per_step["dense"] / max(bytes_per_step["packed"], 1e-9)
    emit(f"rollout.h2d.w{W}.acting_h2d_reduction", round(reduction, 1), "x",
         "measured; acceptance target at W=512: >= 10x")
    return {"dense_bytes_per_step": bytes_per_step["dense"],
            "packed_bytes_per_step": bytes_per_step["packed"],
            "reduction": reduction}


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: W=16 pipelined path, fail on recompiles")
    ap.add_argument("--w", type=int, default=16, help="smoke worker count")
    ap.add_argument("--scale", choices=("quick", "full"), default="quick")
    args = ap.parse_args()
    if args.smoke:
        smoke(args.w)
    else:
        run(args.scale)
