"""The yardstick's operation and byte counts against hand counts, and
the peaks table's refusal of an unknown device."""

import pytest

from chip import flops, peaks

HIDDEN = (1024, 512, 128, 32)


def test_qnet_forward_flops_match_hand_count():
    weights = 2049 * 1024 + 1024 * 512 + 512 * 128 + 128 * 32 + 32 * 1
    assert weights == 2_692_128
    assert flops.qnet_forward_flops(1, 2049, HIDDEN) == 2 * 2_692_128
    assert flops.qnet_forward_flops(7, 2049, HIDDEN) == 7 * 2 * 2_692_128
    assert flops.qnet_params(2049, HIDDEN) == 2_693_825


def test_qnet_backward_skips_the_input_gradient():
    first = 2049 * 1024
    assert flops.qnet_backward_flops(1, 2049, HIDDEN) == \
        2 * (2 * 2_692_128 - first)


def test_learner_update_counts_states_successors_and_state():
    fwd, first = 2 * 2_692_128, 2049 * 1024
    bwd = 2 * (2 * 2_692_128 - first)
    fl, nb = flops.learner_update(64, 32, 100, 2049, HIDDEN)
    assert fl == 64 * ((32 + 2 * 100) * fwd + 32 * bwd)
    assert nb == 64 * (7 * 2_693_825 * 4 + (32 + 100) * (256 + 4))


def test_fleet_dispatch_bytes_are_dominated_by_stacked_params():
    fl, nb = flops.fleet_q_packed(64, 512, 2049, HIDDEN)
    assert fl == 64 * 512 * 2 * 2_692_128
    assert nb == 64 * 2_693_825 * 4 + 64 * 512 * (256 + 4) + 64 * 512 * 4


def test_predictor_flops_match_hand_count():
    # AlfabetS(128, 3) over 40 padded atoms, 16 atom features
    embed = 2 * 40 * 16 * 128
    rnd = 3 * (2 * 40 * 128 * 128 + 2 * 40 * 40 * 128) + 2 * 40 * 128 * 128
    head = 2 * 40 * 128 * 64 + 2 * 40 * 64
    assert flops.alfabet_flops(40, 16, 128, 3) == embed + 3 * rnd + head
    ip = 2 * 40 * 24 * 128 + 2 * 40 * 128 * 128 + 2 * 128 * 64 + 2 * 64
    assert flops.aimnet_flops(40, 16, 8, 128) == ip


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
