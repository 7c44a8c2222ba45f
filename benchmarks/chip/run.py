#!/usr/bin/env python3
"""The chip benchmark's one entry point.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process loads the cell's configuration, traffic and driver (found by
name under ``configs/``, ``workloads/`` and ``drivers/``), builds the
system under test from the checkout's ``src/repro`` with weights drawn
from ``--seed``, warms every shape the window uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference (the driver's ``check``), and prints one JSON object as the
last line of standard output.  Every metric but ``setup_s`` is read by
its file under ``metrics/``.  With ``--trace 1`` the window is profiled
and the cell's per-layer metrics are reported instead of its end-to-end
ones.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero before measuring and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chip"

import numpy as np  # noqa: E402

from . import correct, flops, harness, peaks, trace  # noqa: E402
from .harness import BenchError, log, metric  # noqa: E402

# ------------------------------------------------------------------ #
def run_cell(spec: dict, cell: dict, seed: int, seconds: float, traced: bool,
             devices, t_start: float) -> dict:
    """Set up, measure and check one cell; returns the result object and
    the checks.  ``devices`` are the ones the cell runs on (the command
    passes only TPUs)."""
    wl = harness.workload(cell["name"])
    cfg = harness.config(cell["config"])
    driver = wl["driver"]
    spans = harness.Spans()
    compiles = harness.CompileCounter()
    run = harness.driver_class(driver)(cfg, wl, seed, spans)
    run.build()
    run.warm()
    before = run.counters()
    c0 = compiles.count
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {cell['name']} seed {seed} ({run.pseed}) set-up "
        f"{setup_s:.3f} s, {run.shapes} shapes warmed")
    with harness.maybe_trace(traced) as tdir:
        with spans.span("bench.window"):
            w = run.window(seconds)
        n_compiles = compiles.count - c0
        after = run.counters()
    log(f"[window] {w['window_s']:.3f} s, compiles inside the window: "
        f"{n_compiles}")
    dev = harness.device_info(devices)
    ctx = {"cell": cell["name"], "driver": driver, "cfg": cfg, "wl": wl,
           "w": w, "window_s": w["window_s"], "seconds": seconds,
           "delta": harness.delta(after, before),
           "peaks": peaks.peaks_for(dev["kind"]), "flops": flops,
           "trace": None, "cap": run.cap}
    result = {"correct": False, "attempted": 0, "failed": 0, "metrics": {},
              "device": dev}
    result["attempted"], result["failed"] = w["attempted"], w["failed"]
    if traced:
        red = trace.reduce(trace.load(harness.trace_file(tdir)))
        ctx["trace"] = red
        result["device"]["busy_s"] = red["busy_s"]
        result["device"]["window_s"] = red["window_s"]
        result["breakdown"] = trace.breakdown(red)
        for m in harness.per_layer_metrics(spec, cell["name"]):
            v = harness.metric_reader(m["name"])(ctx)
            if v is not None:
                result["metrics"][m["name"]] = metric(v, m["unit"])
    else:
        for m in harness.end_to_end_metrics(spec, cell["name"]):
            v = setup_s if m["name"] == "setup_s" else \
                harness.metric_reader(m["name"])(ctx)
            if v is None:
                raise BenchError(f"the reader of {m['name']!r} found nothing "
                                 f"in cell {cell['name']!r}")
            result["metrics"][m["name"]] = metric(v, m["unit"])
    # the comparison, once the window has closed and the program's
    # device state is gone
    rng = np.random.default_rng([int(seed), 0xC4EC])
    capture = run.capture
    pseed = run.pseed
    run.free()
    t0 = time.perf_counter()
    numbers, aux = run.check(cfg, pseed, capture, rng)
    checks = correct.judge(numbers, cfg["limits"]["numbers"])
    log(f"[check] reference comparison {time.perf_counter() - t0:.3f} s over "
        + ", ".join(f"{v} {k}" for k, v in aux["counts"].items()))
    result["correct"] = bool(w["failed"] == 0 and all(c["ok"] for c in checks.values()))
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = harness.benchmark_spec()
        cells = {c["name"]: c for c in spec["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no cell {args.workload!r} in BENCHMARK.json")
        cell = cells[args.workload]
        harness.add_program_to_path()
        cache = harness.enable_compile_cache()
        devices = harness.require_chip(cell["chips"])
    except BenchError as e:
        log(f"FAIL: {e}")
        return 2
    log(f"[setup] {len(devices)} x {devices[0].device_kind}, compile cache {cache}")
    result, checks = run_cell(spec, cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_START)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
