"""The limits of ``correct`` against the chip readings they were set from
(``data/limit_readings.jsonl``: one line per seed and cell, as
``control.py`` writes them).

For each number, L is the largest reading of the program over every seed
and cell.  U is the least of: the control's least reading, where that is
at least 3 L; each planted fault's least reading, where that is at least
10 L; and 1, the reading of a state left unchanged on the two norm gaps.
Each limit lies at least 1.2 L and at most U / 1.2.  No chip is needed."""

import json
from pathlib import Path

import pytest

DATA = Path(__file__).resolve().parent / "data" / "limit_readings.jsonl"
CELLS = ("train-w64", "train-w64-stream")
FAULTS = ("half_batch", "no_sync", "answers_swapped")
UNCHANGED = ("grad_gap", "change_gap")
MIN_SEEDS = 16


def _readings() -> list[dict]:
    return [json.loads(x) for x in DATA.read_text().splitlines() if x.strip()]


def _limits() -> dict:
    from chip import harness

    return harness.config("damoldqn-fleet")["limits"]["numbers"]


def bounds(lines: list[dict], name: str) -> tuple[float, float]:
    """(L, U) of ``name`` over ``lines``."""
    low = max(r["program"][name] for r in lines)
    ctl = min(r["control"][name] for r in lines)
    ups = [ctl] if ctl >= 3 * low else []
    for f in FAULTS:
        if name in lines[0][f]:
            v = min(r[f][name] for r in lines)
            if v >= 10 * low:
                ups.append(v)
    if name in UNCHANGED and 1.0 >= 3 * low:
        ups.append(1.0)
    assert ups, f"{name}: neither the control nor a fault reads far enough above L"
    return low, min(ups)


def test_readings_cover_both_cells():
    lines = _readings()
    for cell in CELLS:
        seeds = {r["seed"] for r in lines if r["cell"] == cell}
        assert len(seeds) >= MIN_SEEDS and 4100000001 in seeds, (cell, sorted(seeds))
    assert len({(r["cell"], r["seed"]) for r in lines}) == len(lines)


@pytest.mark.parametrize("name", ["q_gap", "loss_gap", "grad_gap", "change_gap",
                                  "bde_gap", "ip_gap"])
def test_limit_lies_between_the_readings(name):
    low, up = bounds(_readings(), name)
    limit = _limits()[name]
    assert limit >= 1.2 * low, (name, limit, low)
    assert limit <= up / 1.2, (name, limit, up)


def test_fingerprints_are_exact():
    assert _limits()["fp_rows_wrong"] == 0
    assert all(r["program"]["fp_rows_wrong"] == 0 for r in _readings())


def test_every_seed_passes_the_program_and_fails_the_control_and_faults():
    limits = _limits()
    for r in _readings():
        tag = (r["cell"], r["seed"])
        assert all(v <= limits[k] for k, v in r["program"].items()), (tag, r["program"])
        for what in ("control",) + FAULTS:
            assert any(v > limits[k] for k, v in r[what].items()), (tag, what, r[what])
