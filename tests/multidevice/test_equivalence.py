"""The multi-device truth run: the (rollout x learner x chem x sync)
equivalence matrix re-run at nd in {2, 4} forced host devices and pinned to
the nd = 1 reference of the same seed under the cross-nd contract
(``repro.launch.verify.assert_nd_equivalent``: transitions and rewards
bit-identical, losses and parameters within a few float32 ulps) — with the
recompiles-after-warmup gate held at 0, plus the ragged fleets (W not divisible by nd) that pad to the mesh with
dead worker slots.

Each cell spawns one ``repro.launch.verify`` subprocess per mesh size (the
XLA_FLAGS-before-jax-init constraint; see mdhelpers).  The four cells cover
every rollout mode, every learner mode, both chem modes, both sync modes
and every acting representation (packed / packed_async / dense) at least
once; the in-process tier-1 matrices (tests/test_rollout.py,
tests/test_learner.py) already pin all mode pairs against each other at
nd = 1, so cross-mode x cross-nd coverage composes.
"""

import pytest

from mdhelpers import assert_equivalent, run_cells

# every rollout mode, learner mode, chem mode, sync mode AND acting
# representation (packed / packed_async / dense) appears >= once
CELLS = (
    dict(rollout="fleet_sharded", learner="packed", chem="incremental",
         sync="episode", acting="packed"),
    dict(rollout="fleet_pipelined", learner="packed_pipelined",
         chem="incremental", sync="step", acting="packed_async"),
    dict(rollout="fleet", learner="dense", chem="full", sync="episode",
         acting="dense"),
    dict(rollout="per_worker", learner="dense", chem="full", sync="step",
         acting="dense"),
)
_GATED = ("fleet", "fleet_sharded", "fleet_pipelined")  # recompile-gated modes


@pytest.mark.parametrize(
    "cell", CELLS,
    ids=lambda c: (f"{c['rollout']}-{c['learner']}-{c['chem']}-"
                   f"{c['acting']}-{c['sync']}"))
def test_matrix_cell_identical_across_nd(tmp_path, cell):
    res = run_cells(tmp_path, (1, 2, 4), **cell)
    assert int(res[1]["warmup_compiles"]) > 0   # the counter observes children
    for nd in (2, 4):
        assert int(res[nd]["n_devices"]) == nd  # the child really ran sharded
        assert_equivalent(res[1], res[nd], f"nd={nd} {cell}")
        if cell["rollout"] in _GATED:
            assert int(res[nd]["recompiles_after_warmup"]) == 0, \
                f"nd={nd} {cell}: sharded path recompiled after warmup"
    if cell["rollout"] in _GATED:
        assert int(res[1]["recompiles_after_warmup"]) == 0


def test_prioritized_parity_across_nd(tmp_path):
    """The uniform-parity invariant at mesh scale: prioritized replay with
    alpha = 0 (flat effective priorities forever) must be BIT-identical —
    transitions, losses, parameters — to the uniform sampler's nd = 1
    reference, at nd in {1, 2, 4}, with the recompile gate held at 0."""
    cell = dict(rollout="fleet_sharded", learner="packed",
                chem="incremental", sync="episode", acting="packed")
    uni_dir, pri_dir = tmp_path / "uniform", tmp_path / "prioritized"
    uni_dir.mkdir()
    pri_dir.mkdir()
    uni = run_cells(uni_dir, (1,), replay="uniform", **cell)
    pri = run_cells(pri_dir, (1, 2, 4), replay="prioritized",
                    priority_alpha=0.0, **cell)
    for nd in (1, 2, 4):
        assert int(pri[nd]["recompiles_after_warmup"]) == 0, \
            f"prioritized nd={nd} recompiled after warmup"
        assert_equivalent(uni[1], pri[nd],
                          f"prioritized(alpha=0) nd={nd} vs uniform nd=1")


def test_prioritized_alpha_active_self_consistent_across_nd(tmp_path):
    """alpha > 0 prioritized training is its own cross-nd equivalence
    class: nd in {2, 4} must reproduce its OWN nd = 1 reference bit for
    bit (while genuinely diverging from the uniform trajectory — checked
    in-process by tests/test_learner.py)."""
    res = run_cells(tmp_path, (1, 2, 4), replay="prioritized",
                    priority_alpha=0.6, rollout="fleet_sharded",
                    learner="packed", chem="incremental", sync="episode",
                    acting="packed")
    for nd in (2, 4):
        assert int(res[nd]["n_devices"]) == nd
        assert int(res[nd]["recompiles_after_warmup"]) == 0
        assert_equivalent(res[1], res[nd], f"prioritized(alpha=0.6) nd={nd}")


@pytest.mark.parametrize("sync", ["episode", "step"])
def test_ragged_fleet_pads_to_mesh(tmp_path, sync):
    """W = 6 on a 4-device mesh: two dead padding slots, and results
    identical to the unpadded nd = 1 W = 6 run — the masked cross-worker
    means ignore the dead slots in BOTH sync regimes."""
    res = run_cells(tmp_path, (1, 4), workers=6, sync=sync)
    assert int(res[1]["n_padded_workers"]) == 6     # nd=1: no padding
    assert int(res[4]["n_live_workers"]) == 6
    assert int(res[4]["n_padded_workers"]) == 8     # padded to the mesh
    assert int(res[4]["recompiles_after_warmup"]) == 0
    assert_equivalent(res[1], res[4], f"ragged W=6 nd=4 sync={sync}")
