#!/usr/bin/env python3
"""Readings that set the limits of ``correct``: the program's own over
many seeds, the control's, and planted faults'.  The benchmark's runs do
not run this.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1 2 3 \\
        [--out chiprun_out/control]

One process (the chip belongs to it) and, per seed, one program built and
driven as a run drives it: for training through the warm episode, for
serving through a window of the workload's ``control_seconds`` at the
cell's own load.  Then, against the float32 reference of that seed:

* ``program``  the numbers ``correct`` compares (the lower readings);
* ``control``  the same numbers with the reference computed one precision
  step lower (float8 e4m3 matmul operands) in the program's place;
* faults planted in the reference put in the program's place.  Training:
  ``half_batch`` (each update's mean over half its rows), ``no_sync`` (the
  episode sync left out), ``answers_swapped`` (each Q answer, and each
  prediction, handed to the neighbouring row).  A state left unchanged reads
  1 on ``grad_gap`` and ``change_gap`` by definition and needs no run; the
  control reads 1 on ``bde_gap`` and ``ip_gap``, shares of its own gap.
  Serving: ``answers_swapped`` (each slot's Q rows, and each prediction,
  handed to the neighbouring row), ``fingerprint_flipped`` (one bit of every
  sampled row), ``prediction_scaled`` (every BDE and IP answer times 1.05),
  ``choice_shifted`` (each greedy choice one row on).  The control reads 1
  on ``serve_q_gap``, ``bde_gap`` and ``ip_gap``, shares of its own gap.

The driver's ``readings`` names which of these a cell reads.

Each seed's readings are one JSON line in ``<out>/<cell>.jsonl`` and on
standard output.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    __package__ = "chip"

import numpy as np  # noqa: E402

from . import correct as C, harness  # noqa: E402
from .harness import log  # noqa: E402


def train_readings(cfg: dict, pseed: int, capture: dict, rng) -> dict:
    prog, aux = C.check_train(cfg, pseed, capture, rng)
    learn_ref, q_ref = aux["learn_ref"], aux["q_ref"]
    pred_ref, pred_ctl, pp = aux["pred_ref"], aux["pred_ctl"], aux["pred_prog"]
    q_ctl = C.ref_acting(cfg, pseed, capture, "fp8")
    learn_ctl = C.ref_learner(cfg, pseed, capture["batches"], "fp8")

    def preds(x: dict) -> dict:
        return {f"{k}_gap": C.control_share(x[k], pred_ctl[k], pred_ref[k])
                for k in ("bde", "ip")}
    control = {"q_gap": C.q_gap(q_ctl, q_ref),
               **C.learner_numbers(learn_ctl, learn_ref, cfg), **preds(pred_ctl)}
    B = capture["batches"][0]["state_bits"].shape[1]
    half = C.learner_numbers(
        C.ref_learner(cfg, pseed, capture["batches"], "highest", rows=B // 2),
        learn_ref, cfg)
    nosync = C.learner_numbers(
        C.ref_learner(cfg, pseed, capture["batches"], "highest", sync=False),
        learn_ref, cfg)
    swapped = {"q_gap": C.q_gap([[np.roll(q, 1) for q in d["q"]]
                                 for d in capture["dispatches"]], q_ref),
               **preds({k: np.roll(v, 1) for k, v in pp.items()})}
    return {"program": prog, "control": control, "half_batch": half,
            "no_sync": nosync, "answers_swapped": swapped,
            "counts": aux["counts"]}


def serve_readings(cfg: dict, pseed: int, capture: dict, rng) -> dict:
    prog, aux = C.check_serve(cfg, pseed, capture, rng)
    rows, q_ref, q_ctl = aux["rows"], aux["q_ref"], aux["q_ctl"]
    pred_ref, pred_ctl, pp = aux["pred_ref"], aux["pred_ctl"], aux["pred_prog"]

    def preds(x: dict) -> dict:
        return {f"{k}_gap": C.control_share(x[k], pred_ctl[k], pred_ref[k])
                for k in ("bde", "ip")}
    segments = np.split(rows["q"], np.cumsum(rows["segments"])[:-1]) \
        if rows["segments"] else []
    swapped_q = (np.concatenate([np.roll(q, 1) for q in segments])
                 if segments else rows["q"])[aux["pick"]]
    flipped = aux["fp_prog"].copy()
    flipped[:, 0] ^= 0x80
    return {"program": prog,
            "control": {"serve_q_gap": C.q_share(q_ctl, q_ctl, q_ref),
                        **preds(pred_ctl)},
            "answers_swapped": {"serve_q_gap": C.q_share(swapped_q, q_ctl, q_ref),
                                **preds({k: np.roll(v, 1) for k, v in pp.items()})},
            "fingerprint_flipped": {"fp_rows_wrong": C.fp_rows_wrong(
                flipped, aux["fp_ref"])},
            "prediction_scaled": preds({k: v * 1.05 for k, v in pp.items()}),
            "choice_shifted": {"action_wrong": C.action_wrong(capture, shift=1)},
            "counts": aux["counts"]}


def one_seed(cell: dict, seed: int) -> dict:
    """One seed's readings, through the cell's driver: its set-up, a
    window of ``control_seconds`` at the cell's load where it has one, and
    its ``readings``."""
    wl = harness.workload(cell["name"])
    cfg = harness.config(cell["config"])
    spans = harness.Spans()
    run = harness.driver_class(wl["driver"])(cfg, wl, seed, spans)
    t0 = time.perf_counter()
    run.build()
    run.warm()
    if run.control_seconds:
        run.window(run.control_seconds)
    t_prog = time.perf_counter() - t0
    capture, pseed = run.capture, run.pseed
    run.free()
    rng = np.random.default_rng([int(seed), 0xC4EC])
    out = run.readings(cfg, pseed, capture, rng)
    out.update(cell=cell["name"], seed=seed, program_s=t_prog,
               reference_s=time.perf_counter() - t0 - t_prog)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default="chiprun_out/control")
    args = ap.parse_args(argv)
    harness.add_program_to_path()
    harness.enable_compile_cache()
    spec = harness.benchmark_spec()
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    harness.require_chip(cell["chips"])
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{args.workload}.jsonl", "a") as f:
        for seed in args.seeds:
            r = one_seed(cell, seed)
            line = json.dumps(r)
            f.write(line + "\n")
            f.flush()
            print(line, flush=True)
            log(f"[control] seed {seed}: program {r['program']} control "
                f"{r['control']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
