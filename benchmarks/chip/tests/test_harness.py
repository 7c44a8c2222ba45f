"""The harness at tiny sizes on the CPU: every cell builds, runs and is
checked through the harness's own functions; a workload added as a file
is found by its name; the command itself refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import tiny as t

CELLS = ["train-w64", "train-w64-stream"]


def _run(cell: str, seed: int, seconds: float = 3.0):
    import jax

    from chip import harness, run

    spec = harness.benchmark_spec()
    c = {x["name"]: x for x in spec["workloads"]}[cell]
    return run.run_cell(spec, c, seed, seconds, False, jax.devices()[:1],
                        time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_runs_and_is_correct_at_tiny_size(tiny, cell):
    from chip import harness

    result, checks = _run(cell, 2**33 + 11)
    spec = harness.benchmark_spec()
    want = {m["name"] for m in harness.end_to_end_metrics(spec, cell)}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


def test_a_workload_added_as_a_file_is_found_by_name(tiny):
    from chip import harness

    wl = json.loads((tiny / "workloads" / "train-w64.json").read_text())
    wl["traffic"]["reserve_candidates"] = 512
    (tiny / "workloads" / "train-w64-extra.json").write_text(json.dumps(wl))
    bench = tiny.parents[1] / "BENCHMARK.json"
    spec = json.loads(bench.read_text())
    spec["workloads"].append({"name": "train-w64-extra", "config": "damoldqn-fleet",
                              "traffic": "train-split-extra", "chips": 1,
                              "why": "a later cell added as data"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "train-w64" in m.get("workloads", []):
            m["workloads"].append("train-w64-extra")
    bench.write_text(json.dumps(spec))
    assert harness.workload("train-w64-extra")["traffic"]["reserve_candidates"] == 512
    names = {m["name"] for m in harness.per_layer_metrics(harness.benchmark_spec(),
                                                          "train-w64-extra")}
    assert "chem_host_share.train" in names
    result, checks = _run("train-w64-extra", 5)
    assert result["correct"], checks
    assert result["attempted"] > 0


def test_every_per_layer_metric_has_a_reader():
    from chip import harness

    for m in harness.benchmark_spec()["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def _command(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "train-w64",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_without_a_chip_exits_nonzero_with_no_result():
    p = _command(t.ROOT)
    assert p.returncode != 0
    assert "FAIL" in p.stderr and "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_command_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copytree(t.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(t.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
