"""The harness at tiny sizes on the CPU: every cell builds, runs and is
checked through the harness's own functions; a workload, a driver and a
metric reader are found by their files, and an unknown name says which
file it looked for; the serving driver's records give its end-to-end
numbers by hand; the command itself refuses to run without a TPU."""

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import tiny as t

CELLS = ["train-w64", "train-w64-stream", "serve-w64-steady", "serve-w64-overload"]


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


def test_a_driver_its_check_and_a_reader_are_found_by_file():
    from chip import correct, harness

    assert harness.driver_class("train").check is correct.check_train
    assert harness.driver_class("serve").check is correct.check_serve
    for m in harness.benchmark_spec()["end_to_end"]:
        if m["name"] != "setup_s":
            assert callable(harness.metric_reader(m["name"]))


@pytest.mark.parametrize("kind,load", [("drivers", "driver_class"),
                                       ("metrics", "metric_reader")])
def test_an_unknown_name_says_which_file_it_looked_for(kind, load):
    from chip import harness

    with pytest.raises(harness.BenchError) as e:
        getattr(harness, load)("no-such-name")
    assert f"benchmarks/chip/{kind}/no-such-name.py" in str(e.value)


def test_transitions_reader_is_the_old_formula_on_a_recorded_context():
    from chip import harness

    # the context a train-w64 run records: two episodes of 2,560
    # transitions in a 41.5-s window
    ctx = {"driver": "train", "delta": {"transitions": 5120},
           "w": {"window_s": 41.51930480299727, "attempted": 2, "failed": 0},
           "window_s": 41.51930480299727}
    read = harness.metric_reader("train_transitions_per_s")
    assert read(ctx) == ctx["delta"]["transitions"] / ctx["w"]["window_s"]


def test_run_names_no_driver_check_or_end_to_end_metric():
    from chip import harness

    text = (t.HERE / "run.py").read_text()
    spec = harness.benchmark_spec()
    names = {c["driver"] for c in (harness.workload(w["name"])
                                   for w in spec["workloads"])}
    names |= {m["name"] for m in spec["end_to_end"]} - {"setup_s"}
    names |= {"check_train", "check_serve", "TrainRun", "ServeRun"}
    assert not [n for n in names if f'"{n}"' in text or f".{n}" in text
                or f"{n}(" in text], names


@pytest.mark.parametrize("cell", ["serve-w64-steady", "serve-w64-overload"])
def test_serve_driver_warm_window_check_and_its_records(tiny, cell):
    import numpy as np

    from chip import harness

    wl = harness.workload(cell)
    cfg = harness.config(wl["config"])
    run = harness.driver_class("serve")(cfg, wl, 2**34 + 5, harness.Spans())
    run.build()
    run.warm()
    w = run.window(3.0)
    recs = w["records"]
    assert w["attempted"] == len(recs) > 0 and w["failed"] == 0
    assert all(done >= arrived for arrived, done, _ in recs)
    lat = sorted(done - arrived for arrived, done, _ in recs)
    # the 95th percentile between order statistics, by hand
    pos = 0.95 * (len(lat) - 1)
    lo = int(pos)
    hand = lat[lo] + (lat[min(lo + 1, len(lat) - 1)] - lat[lo]) * (pos - lo)
    ctx = {"driver": "serve", "w": w, "window_s": w["window_s"]}
    assert harness.metric_reader("serve_p95_latency_s")(ctx) == pytest.approx(hand)
    done = sum(1 for r in recs if r[2] == "completed")
    assert harness.metric_reader("serve_completed_rps")(ctx) == \
        pytest.approx(done / w["window_s"])
    if wl["traffic"]["end"] == "drain":
        assert len(recs) == w["arrivals"]      # every arrival is terminal
        assert w["window_s"] >= max(a for a, _, _ in recs)
    else:
        assert w["window_s"] >= 3.0
    capture, pseed = run.capture, run.pseed
    assert 0 < len(capture["steps"]) <= wl["traffic"]["sample_steps"]
    run.free()
    numbers, aux = run.check(cfg, pseed, capture, np.random.default_rng(3))
    limits = cfg["limits"]["numbers"]
    assert set(numbers) == set(limits)
    assert all(v <= limits[k] for k, v in numbers.items()), numbers
    assert aux["counts"]["Q rows"] > 0 and aux["counts"]["choices"] > 0


def test_every_seed_gets_the_same_arrivals_and_work_in_another_order(tiny):
    from chip import harness

    wl = harness.workload("serve-w64-steady")
    cfg = harness.config(wl["config"])
    runs = [harness.driver_class("serve")(cfg, wl, s, harness.Spans())
            for s in (2**31 + 7, 2**33 + 1)]
    for run in runs:
        run.starts = [f"C{'C' * i}O" for i in range(40)]
    (ta, ra), (tb, rb) = (run.schedule(6.0) for run in runs)
    assert list(ta) == list(tb) and len(ra) == len(ta) > 0
    assert sorted(r.smiles for r in ra) == sorted(r.smiles for r in rb)
    assert sorted(r.budget for r in ra) == sorted(r.budget for r in rb)
    assert [r.smiles for r in ra] != [r.smiles for r in rb]
    cap = cfg["serve"]["max_steps"]
    assert runs[0].budgets == {r.request_id: min(r.budget, cap) for r in ra}


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
