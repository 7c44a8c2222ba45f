"""Molecule-optimization serving launcher (docs/serving.md).

Stands up a ``MoleculeOptService`` — bounded admission queue, continuous
batching over RolloutEngine slots, circuit breaker over the property tier
— and replays a seeded open-loop request stream against it, printing the
per-request terminal results and the service counters.

    PYTHONPATH=src python -m repro.launch.serve_molopt \
        --slots 8 --requests 32 --rate 2.0 --deadline-frac 0.3

By default properties come from the deterministic ``OracleService`` stub
(no predictor training, seconds to start); ``--trained`` trains/loads the
real BDE+IP predictors and serves through them.  ``--faults`` arms a
seeded ``FaultPlan`` over the predict/chem/request sites, exercising the
whole degradation ladder: retries, per-request quarantine, breaker trips
into degraded serving, half-open recovery.
"""

from __future__ import annotations

import argparse
import json
import time

import jax

from repro.core.agent import QNetwork
from repro.launch.compile_cache import enable_compile_cache
from repro.core.faults import FaultPlan, FaultRule
from repro.predictors.service import OracleService, ResilientService, RetryPolicy
from repro.serving import (MoleculeOptService, ServeConfig, StreamConfig,
                           drive_open_loop, latency_stats,
                           seeded_request_stream)


def build_service(args) -> MoleculeOptService:
    net = QNetwork()
    params = net.init(jax.random.PRNGKey(args.seed))
    plan = None
    if args.faults:
        plan = FaultPlan([
            FaultRule(site="predict", kind="crash", every=args.fault_every,
                      fail_attempts=args.fault_attempts),
            FaultRule(site="chem", kind="crash", rate=args.fault_rate),
            FaultRule(site="request", kind="transient", rate=args.fault_rate,
                      fail_attempts=1),
        ], seed=args.fault_seed)
    if args.trained:
        from repro.predictors.training import trained_service
        inner, _ = trained_service(verbose=True)
    else:
        inner = OracleService()
    prop = ResilientService(inner, RetryPolicy(max_retries=1, seed=args.seed),
                            fault_plan=plan, sleep=None)
    return MoleculeOptService(
        net, params, prop, fault_plan=plan,
        cfg=ServeConfig(n_slots=args.slots, max_queue=args.max_queue,
                        shed_policy=args.shed_policy, epsilon=args.epsilon,
                        seed=args.seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-queue", type=int, default=32)
    ap.add_argument("--shed-policy", choices=("reject_new", "evict_oldest"),
                    default="reject_new")
    ap.add_argument("--epsilon", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=2.0,
                    help="mean arrivals per service step")
    ap.add_argument("--deadline-frac", type=float, default=0.3)
    ap.add_argument("--invalid-every", type=int, default=0,
                    help="poison every Nth request with unparseable SMILES")
    ap.add_argument("--trained", action="store_true",
                    help="serve through the trained BDE+IP predictors "
                         "instead of the oracle stub")
    ap.add_argument("--faults", action="store_true",
                    help="arm a seeded FaultPlan (predict/chem/request)")
    ap.add_argument("--fault-every", type=int, default=7)
    ap.add_argument("--fault-rate", type=float, default=0.05)
    ap.add_argument("--fault-attempts", type=int, default=4)
    ap.add_argument("--fault-seed", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", action="store_true",
                    help="print machine-readable results instead of a table")
    args = ap.parse_args()

    enable_compile_cache()
    svc = build_service(args)
    arrivals = seeded_request_stream(StreamConfig(
        n_requests=args.requests, rate=args.rate, seed=args.seed,
        deadline_frac=args.deadline_frac, invalid_every=args.invalid_every))
    svc.reserve_candidates(256)          # warmup: compile off the clock

    t0 = time.perf_counter()
    drive_open_loop(svc, arrivals)
    wall = time.perf_counter() - t0

    if args.json:
        print(json.dumps({"results": [r.as_dict() for r in svc.results],
                          "stats": svc.stats()}, indent=2, default=str))
        return
    print(f"{'request':10s} {'status':18s} {'steps':>5s} {'deg':>3s} "
          f"{'lat':>6s} {'wall_ms':>8s}  best")
    for r in sorted(svc.results, key=lambda r: r.request_id):
        best = "-" if r.best_reward is None else \
            f"{r.best_reward:+.4f} {r.best_smiles}"
        err = f"  [{r.error[:48]}]" if r.error else ""
        print(f"{r.request_id:10s} {r.status:18s} {r.steps_used:5d} "
              f"{r.degraded_steps:3d} {r.latency:6.1f} "
              f"{r.wall_latency_s * 1e3:8.1f}  {best}{err}")
    st = svc.stats()
    lat = latency_stats(svc.results)
    print(f"\n{args.requests} requests in {wall:.2f}s "
          f"({args.requests / wall:.1f} req/s) | statuses "
          f"{st['status_counts']} | p50/p99 wall "
          f"{lat['p50_wall_ms']:.1f}/{lat['p99_wall_ms']:.1f} ms")
    print(f"service steps {st['n_service_steps']} | Q dispatches "
          f"{st['n_q_dispatches']} | queue {st['queue']} | breaker "
          f"{st['breaker']}")


if __name__ == "__main__":
    main()
