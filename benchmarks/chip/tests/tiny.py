"""A copy of the benchmark tree with tiny configurations, for the CPU
tests: the same files, widths and fleet cut so that a cell builds, runs
and is checked in seconds.  Never a measurement."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]

# the serving cells' entries, held out of BENCHMARK.json until they are
# measured on the chip; the tiny copy's BENCHMARK.json has them
SERVING = HERE / "tests" / "data" / "serving_cells.json"

CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
             "source": "placeholder for CPU tests"}


def make_tree(tmp: Path) -> Path:
    """Copy ``BENCHMARK.json``, with the serving cells' entries added, and
    the benchmark directory under ``tmp``, shrink every configuration and
    traffic mix, and return the copy's benchmark directory."""
    here = tmp / "benchmarks" / "chip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in json.loads(SERVING.read_text()).items():
        spec[key] += [e for e in entries if e not in spec[key]]
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    for path in (here / "configs").glob("*.json"):
        c = json.loads(path.read_text())
        c["qnet"]["hidden"] = [32, 16]
        c["predictors"].update(bde_hidden=16, ip_hidden=16)
        if "trainer" in c:
            c["trainer"].update(n_workers=2, mols_per_worker=2, max_steps=3,
                                train_batch_size=4, updates_per_episode=2)
        if "serve" in c:
            c["serve"].update(n_slots=4, max_steps=3)
        path.write_text(json.dumps(c))
    for path in (here / "workloads").glob("*.json"):
        w = json.loads(path.read_text())
        if w["driver"] == "serve":
            w["traffic"].update(knee_rps=10.0, warm_requests=8, drain_grace_s=5,
                                reserve_candidates=256, sample_steps=3,
                                sample_from=6, control_seconds=1.5)
        path.write_text(json.dumps(w))
    return here


def point_harness_at(monkeypatch, here: Path) -> None:
    """Make the imported harness read the copy's files."""
    from chip import harness, peaks

    monkeypatch.setattr(harness, "HERE", here)
    monkeypatch.setattr(harness, "ROOT", here.parents[1])
    monkeypatch.setattr(harness, "OUT", here.parents[1] / ".bench")
    monkeypatch.setattr(harness, "TRACE_DIR", here.parents[1] / ".bench" / "trace")
    monkeypatch.setitem(peaks.PEAKS, "cpu", CPU_PEAKS)
