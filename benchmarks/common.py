"""Shared benchmark plumbing."""

from __future__ import annotations

import glob
import json
import os
import re
import time
from contextlib import contextmanager

RESULTS: dict[str, dict] = {}

# the committed perf-trajectory snapshot format (BENCH_PR<n>.json series,
# written by `benchmarks/run.py --bench-json`)
BENCH_SNAPSHOT_SCHEMA = "bench-snapshot-v1"
_BENCH_NAME = re.compile(r"BENCH_PR(\d+)\.json")
_BENCH_SECTIONS = ("host", "summary", "metrics")
# the serve section (PR 9) is OPTIONAL — earlier snapshots in the series
# predate serving — but when present it must carry the full metrics block
_SERVE_REQUIRED = ("requests_per_s", "p50_latency_ms", "p99_latency_ms",
                   "completed", "degraded", "shed", "deadline_exceeded",
                   "failed", "recompiles_after_warmup")


class BenchTrajectoryError(ValueError):
    """A committed BENCH_*.json snapshot is unreadable as part of the
    series — wrong name, malformed JSON, wrong schema, missing sections.
    Raised LOUDLY instead of silently yielding an empty trajectory."""


def load_bench_trajectory(root: str = ".") -> list[dict]:
    """Discover the committed ``BENCH_*.json`` snapshots under ``root``,
    validate each against ``bench-snapshot-v1``, and return them ordered
    chronologically (by PR number — numeric, so PR10 sorts after PR9).

    Every snapshot dict gains ``name`` (basename) and ``pr`` (int) keys
    next to its ``host``/``summary``/``metrics`` sections.  Any snapshot
    that does not parse or validate raises :class:`BenchTrajectoryError`
    naming the file and the defect — a truncated or hand-mangled snapshot
    must fail the trajectory, not vanish from it."""
    snaps = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_*.json"))):
        base = os.path.basename(path)
        m = _BENCH_NAME.fullmatch(base)
        if not m:
            raise BenchTrajectoryError(
                f"{path}: unrecognised snapshot name (expected "
                f"BENCH_PR<n>.json — the series is ordered by PR number)")
        try:
            with open(path) as f:
                data = json.load(f)
        except json.JSONDecodeError as e:
            raise BenchTrajectoryError(f"{path}: malformed JSON: {e}") from e
        if not isinstance(data, dict):
            raise BenchTrajectoryError(f"{path}: snapshot is not an object")
        if data.get("schema") != BENCH_SNAPSHOT_SCHEMA:
            raise BenchTrajectoryError(
                f"{path}: schema {data.get('schema')!r}, expected "
                f"{BENCH_SNAPSHOT_SCHEMA!r}")
        for key in _BENCH_SECTIONS:
            if not isinstance(data.get(key), dict):
                raise BenchTrajectoryError(
                    f"{path}: missing or non-object {key!r} section")
        if "serve" in data:
            if not isinstance(data["serve"], dict):
                raise BenchTrajectoryError(
                    f"{path}: non-object 'serve' section")
            missing = [k for k in _SERVE_REQUIRED if k not in data["serve"]]
            if missing:
                raise BenchTrajectoryError(
                    f"{path}: serve section missing {missing} — a partial "
                    f"serve cell must fail the trajectory, not blend in")
        snaps.append({"name": base, "pr": int(m.group(1)), **data})
    snaps.sort(key=lambda s: s["pr"])
    return snaps


def diff_bench_trajectory(snaps: list[dict]) -> list[dict]:
    """Per-summary-metric deltas between consecutive snapshots of a
    :func:`load_bench_trajectory` series.  Each row: ``from``/``to``
    snapshot names, ``metric``, ``old``/``new`` values, and ``delta_pct``
    when both values are finite numbers (None for new/dropped metrics)."""
    rows = []
    for prev, cur in zip(snaps, snaps[1:]):
        for metric in sorted(set(prev["summary"]) | set(cur["summary"])):
            old = prev["summary"].get(metric)
            new = cur["summary"].get(metric)
            delta = None
            if isinstance(old, (int, float)) and isinstance(new, (int, float)) \
                    and not isinstance(old, bool) and old:
                delta = 100.0 * (new - old) / abs(old)
            rows.append({"from": prev["name"], "to": cur["name"],
                         "metric": metric, "old": old, "new": new,
                         "delta_pct": delta})
    return rows


def emit(name: str, value, unit: str, derived: str = "") -> None:
    RESULTS[name] = {"value": value, "unit": unit, "derived": derived}
    print(f"{name},{value},{unit}" + (f",{derived}" if derived else ""), flush=True)


@contextmanager
def timer():
    box = {}
    t0 = time.perf_counter()
    yield box
    box["s"] = time.perf_counter() - t0


def save_results(path: str = "experiments/bench/results.json") -> None:
    """Merge this run's metrics into the results file (a partial run — e.g.
    ``--only rollout`` — must not clobber the other benches' entries)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    merged: dict[str, dict] = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                merged = json.load(f)
        except (OSError, json.JSONDecodeError):
            merged = {}
    merged.update(RESULTS)
    with open(path, "w") as f:
        json.dump(merged, f, indent=2, default=str)


def services():
    """Shared predictor service + dataset + reward config."""
    from repro.core import RewardConfig
    from repro.data.datasets import antioxidant_dataset, dataset_property_table, \
        train_test_split
    from repro.predictors.training import trained_service

    service, metrics = trained_service()
    ds = antioxidant_dataset(600)
    train, test = train_test_split(ds)
    props = dataset_property_table(train)
    rcfg = RewardConfig.from_dataset(props["bde"], props["ip"])
    return service, train, test, rcfg, metrics
