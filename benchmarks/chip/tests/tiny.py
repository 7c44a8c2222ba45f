"""A copy of the benchmark tree with tiny configurations, for the CPU
tests: the same files, widths and fleet cut so that a cell builds, runs
and is checked in seconds.  Never a measurement."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
             "source": "placeholder for CPU tests"}


def make_tree(tmp: Path) -> Path:
    """Copy ``BENCHMARK.json`` and the benchmark directory under ``tmp``,
    shrink every configuration and traffic mix, and return the copy's
    benchmark directory."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    for path in (here / "configs").glob("*.json"):
        c = json.loads(path.read_text())
        c["qnet"]["hidden"] = [32, 16]
        c["predictors"].update(bde_hidden=16, ip_hidden=16)
        if "trainer" in c:
            c["trainer"].update(n_workers=2, mols_per_worker=2, max_steps=3,
                                train_batch_size=4, updates_per_episode=2)
        path.write_text(json.dumps(c))
    return here


def point_harness_at(monkeypatch, here: Path) -> None:
    """Make the imported harness read the copy's files."""
    from chip import harness, peaks

    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "ROOT", here.parents[1])
    monkeypatch.setattr(harness, "OUT", here.parents[1] / ".bench")
    monkeypatch.setattr(harness, "TRACE_DIR", here.parents[1] / ".bench" / "trace")
    monkeypatch.setitem(peaks.PEAKS, "cpu", CPU_PEAKS)
