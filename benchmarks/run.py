"""Benchmark runner: one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run                 # everything, quick
    PYTHONPATH=src python -m benchmarks.run --only env,cache
    PYTHONPATH=src python -m benchmarks.run --scale full
    PYTHONPATH=src python -m benchmarks.run --bench-json BENCH_PR7.json
    PYTHONPATH=src python -m benchmarks.run --trajectory    # diff the series

Prints ``name,value,unit[,derived]`` CSV; writes experiments/bench/results.json.

``--bench-json PATH`` instead runs the training-free smoke benches plus the
W=512 measured acting-bytes cell and writes a standing perf-trajectory
snapshot (steps/s, updates/s, acting H2D bytes/step, chem cache hit rate,
recompiles-after-warmup) to PATH — the committed ``BENCH_*.json`` series
that lets successive PRs be compared on one box.
"""

from __future__ import annotations

import argparse
import os
import time
import traceback

from benchmarks.common import RESULTS, emit, save_results

BENCHES = ("env", "fingerprint", "cache", "rollout", "train", "models",
           "properties", "qed_plogp", "sync_modes", "kernels", "roofline")


def bench_json(path: str) -> None:
    """Write the perf-trajectory snapshot (see module docstring): smoke
    benches only — training-free, minutes not hours — plus the measured
    W=512 dense-vs-packed acting H2D cell, the W=8 fault-injection gate
    (training under a seeded FaultPlan bit-identical to fault-free, zero
    recompiles with retries active), the W=512 multi-start end-to-end
    training cell (dataset streaming + prioritized replay), and the W=8
    serving cell (request throughput/latency + the serve determinism
    gates, written as the snapshot's ``serve`` section).  Finishes by
    printing the per-metric delta table of the whole committed
    BENCH_*.json series, this snapshot included."""
    import json
    import platform

    import jax

    from benchmarks import bench_env, bench_rollout, bench_serve, bench_train

    bench_rollout.smoke(16)
    bench_train.smoke(8)
    bench_env.smoke(16)
    fs = bench_train.fault_smoke(8)
    h2d = bench_rollout.measure_acting_h2d(512)
    ms = bench_train.multistart(512)
    sv = bench_serve.serve_cell(8)

    def val(key):
        return RESULTS[key]["value"] if key in RESULTS else None

    snapshot = {
        "schema": "bench-snapshot-v1",
        "host": {"platform": platform.platform(),
                 "backend": jax.default_backend(),
                 "devices": jax.device_count()},
        "summary": {
            "rollout_steps_per_s_w16_pipelined_packed":
                val("rollout.smoke.w16.steps_per_s"),
            "rollout_steps_per_s_w16_mixed_scenarios":
                val("rollout.smoke.w16.mixed.steps_per_s"),
            "mixed_scenario_overhead_frac_w16":
                val("rollout.smoke.w16.mixed_overhead_frac"),
            "learner_updates_per_s_w8_packed_pipelined":
                val("train.smoke.w8.updates_per_s"),
            "acting_h2d_bytes_per_step_w512_dense":
                int(h2d["dense_bytes_per_step"]),
            "acting_h2d_bytes_per_step_w512_packed":
                int(h2d["packed_bytes_per_step"]),
            "acting_h2d_reduction_w512": round(h2d["reduction"], 1),
            "learner_h2d_reduction_w8": val("train.smoke.w8.h2d_reduction"),
            "chem_cache_hit_rate_w16": val("env.smoke.w16.cache_hit_rate"),
            "multistart_steps_per_s_w512": round(ms["steps_per_s"], 2),
            "multistart_episode_wall_s_w512": round(ms["episode_wall_s"], 2),
            "multistart_unique_starts_w512": int(ms["unique_starts"]),
            "prioritized_recompiles_after_warmup":
                val("train.smoke.w8.prioritized_recompiles_after_warmup"),
            "fault_smoke_n_faults_injected_w8": int(fs["n_faults_injected"]),
            "fault_smoke_n_retries_w8": int(fs["n_retries"]),
            "fault_smoke_bit_identical_w8": int(fs["bit_identical"]),
            "serve_requests_per_s_w8": sv["requests_per_s"],
            "serve_p99_latency_ms_w8": sv["p99_latency_ms"],
            "serve_deterministic_w8": int(sv["deterministic"]),
            "recompiles_after_warmup": max(
                int(v["value"]) for k, v in RESULTS.items()
                if k.endswith("recompiles_after_warmup")),
        },
        "metrics": dict(sorted(RESULTS.items())),
        "serve": sv,
    }
    with open(path, "w") as f:
        json.dump(snapshot, f, indent=2, default=str)
        f.write("\n")
    print(f"\n[bench-json] wrote {path}")
    print_trajectory(os.path.dirname(os.path.abspath(path)) or ".")


def print_trajectory(root: str = ".") -> None:
    """Load the committed BENCH_*.json series and print the per-metric
    delta table between consecutive snapshots (the diffable perf
    trajectory).  Fails loudly — malformed snapshots raise, an empty
    series exits nonzero."""
    from benchmarks.common import diff_bench_trajectory, load_bench_trajectory

    snaps = load_bench_trajectory(root)
    if not snaps:
        raise SystemExit(
            f"no BENCH_*.json snapshots under {root!r} — run "
            f"`benchmarks/run.py --bench-json BENCH_PR<n>.json` first")
    names = ", ".join(s["name"] for s in snaps)
    print(f"\n[trajectory] {len(snaps)} snapshot(s): {names}")
    rows = diff_bench_trajectory(snaps)
    if not rows:
        print("[trajectory] single snapshot — nothing to diff yet")
        return
    width = max(len(r["metric"]) for r in rows)
    last_pair = None
    for r in rows:
        pair = (r["from"], r["to"])
        if pair != last_pair:
            print(f"\n  {pair[0]} -> {pair[1]}")
            last_pair = pair
        if r["delta_pct"] is None:
            change = "new" if r["old"] is None else \
                ("dropped" if r["new"] is None else "--")
        else:
            change = f"{r['delta_pct']:+8.1f}%"
        print(f"    {r['metric']:<{width}}  {r['old']!s:>12} -> "
              f"{r['new']!s:>12}  {change}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma list of bench names")
    ap.add_argument("--scale", choices=("quick", "full"), default="quick")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="write the perf-trajectory snapshot to PATH and exit "
                         "(smoke benches + measured W=512 acting bytes + the "
                         "W=512 multi-start training cell)")
    ap.add_argument("--trajectory", action="store_true",
                    help="print the committed BENCH_*.json series as a "
                         "per-metric delta table and exit (no benches run)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.trajectory:
        print_trajectory(".")
        return
    if args.bench_json:
        bench_json(args.bench_json)
        return

    names = args.only.split(",") if args.only else list(BENCHES)
    t0 = time.time()
    failures = []
    for name in names:
        print(f"\n# --- bench: {name} ({args.scale}) ---", flush=True)
        try:
            mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
            mod.run(args.scale)
        except Exception as e:  # noqa: BLE001 -- report, continue
            traceback.print_exc()
            failures.append(name)
            emit(f"{name}.FAILED", str(e)[:120], "error")
    emit("bench.total_wall", round(time.time() - t0, 1), "s")
    save_results()
    if failures:
        raise SystemExit(f"benchmarks failed: {failures}")


if __name__ == "__main__":
    main()
