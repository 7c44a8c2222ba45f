#!/usr/bin/env python3
"""The knee of a serving cell: the highest arrival rate at which the
router's queue stays bounded over a window.  The benchmark's runs do not
run this; its answer is written into the serving workloads' ``knee_rps``.

    python3 benchmarks/chip/knee.py --workload serve-w64-overload \\
        --seed 1 --rates 10 12 14 16 --seconds 40 [--out .bench/knee]

One process (the chip belongs to it).  For each rate, a fresh service is
built, warmed and primed as a run's set-up does, then driven by the cell's
driver for ``--seconds`` at that rate with the window ending at
``--seconds``.  After every service step the queue's depth is noted.  Each
rate's line (on standard output and in ``<out>/<cell>.jsonl``) gives the
arrivals, the completed requests and rate, the queue's depth at each
quarter of the window and its most, and the latencies of the completed
requests.  The queue is bounded where its depth at the end is under one
wave of slots and not above its depth at the half.
"""

from __future__ import annotations

import argparse
import copy
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chip"

import numpy as np  # noqa: E402

from . import harness  # noqa: E402
from .harness import log  # noqa: E402


def one_rate(cell: dict, seed: int, rate: float, seconds: float) -> dict:
    wl = copy.deepcopy(harness.workload(cell["name"]))
    wl["traffic"].update(knee_rps=rate, load=1.0, end="window", sample_steps=0)
    cfg = harness.config(cell["config"])
    run = harness.driver_class(wl["driver"])(cfg, wl, seed, harness.Spans())
    run.build()
    run.warm()
    svc = run.svc
    depth: list[tuple[float, int]] = []
    step = svc.step
    t0 = [0.0]

    def noted():
        out = step()
        depth.append((time.perf_counter() - t0[0], len(svc.queue)))
        return out
    svc.step = noted
    t0[0] = time.perf_counter()
    w = run.window(seconds)
    slots = cfg["serve"]["n_slots"]
    run.free()
    at = [([d for t, d in depth if t <= f * w["window_s"]] or [0])[-1]
          for f in (0.25, 0.5, 0.75, 1.0)]
    lat = [done - arr for arr, done, st in w["records"] if st == "completed"]
    completed = len(lat)
    return {"cell": cell["name"], "seed": seed, "rate_rps": rate,
            "arrivals": w["arrivals"], "completed": completed,
            "completed_rps": completed / w["window_s"], "window_s": w["window_s"],
            "steps": len(depth), "queue_at_quarters": at,
            "queue_most": max((d for _, d in depth), default=0),
            "bounded": bool(at[3] < slots and at[3] <= at[1]),
            "latency_p50_s": float(np.percentile(lat, 50)) if lat else None,
            "latency_p95_s": float(np.percentile(lat, 95)) if lat else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--out", default=".bench/knee")
    args = ap.parse_args(argv)
    harness.add_program_to_path()
    harness.enable_compile_cache()
    spec = harness.benchmark_spec()
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    harness.require_chip(cell["chips"])
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for rate in args.rates:
            r = one_rate(cell, args.seed, rate, args.seconds)
            line = json.dumps(r)
            f.write(line + "\n")
            f.flush()
            print(line, flush=True)
            log(f"[knee] {rate} req/s: {r['completed_rps']:.3f} completed/s, queue "
                f"{r['queue_at_quarters']}, bounded {r['bounded']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
