"""The control and the planted faults, at a size a test run holds: the
program passes every limit, and the control (the reference one precision
step lower in the program's place) and each fault fail at least one."""

import pytest


def _limits(cell: str) -> dict:
    from chip import harness

    spec = harness.benchmark_spec()
    c = {x["name"]: x for x in spec["workloads"]}[cell]
    return harness.config(c["config"])["limits"]["numbers"]


def _fails(readings: dict, limits: dict) -> set:
    return {k for k, v in readings.items() if v > limits[k]}


@pytest.mark.parametrize("cell,faults", [
    ("train-w64", ("control", "half_batch", "no_sync", "answers_swapped")),
    ("serve-w64-steady", ("control", "answers_swapped", "fingerprint_flipped",
                          "prediction_scaled", "choice_shifted")),
])
def test_control_and_faults_fail_where_the_program_passes(tiny, cell, faults):
    from chip import control, harness

    spec = harness.benchmark_spec()
    c = {x["name"]: x for x in spec["workloads"]}[cell]
    r = control.one_seed(c, 23)
    limits = _limits(cell)
    assert not _fails(r["program"], limits), r["program"]
    for name in faults:
        assert _fails(r[name], limits), (name, r[name])
