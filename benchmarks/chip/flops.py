"""Operations and bytes of the device work, computed from shapes.

These are the yardstick's own counts: they do not depend on what
implements the work (XLA or a Pallas kernel).  A "row" of the Q-network is
one candidate state: a 2048-bit Morgan fingerprint and a steps-left
feature, 2049 inputs, through the hidden widths to one Q value.

All counts are multiply-adds times two.  Bytes are the least a program
must move through HBM: every operand read once and every result written
once, in the dtype the program keeps them in (float32 parameters and
activations, uint8 bit planes for packed fingerprints).
"""

from __future__ import annotations

F32 = 4
FP_BYTES = 256            # 2048 bits, packed 8 to a byte


def qnet_layer_sizes(in_dim: int, hidden: tuple[int, ...]) -> list[tuple[int, int]]:
    sizes = (in_dim,) + tuple(hidden) + (1,)
    return list(zip(sizes[:-1], sizes[1:]))


def qnet_weights(in_dim: int, hidden: tuple[int, ...]) -> int:
    """Matrix entries of the Q-network (biases excluded)."""
    return sum(i * o for i, o in qnet_layer_sizes(in_dim, hidden))


def qnet_params(in_dim: int, hidden: tuple[int, ...]) -> int:
    """Every float of one Q-network: matrices and biases."""
    return sum(i * o + o for i, o in qnet_layer_sizes(in_dim, hidden))


def qnet_forward_flops(rows: int, in_dim: int, hidden: tuple[int, ...]) -> int:
    """Forward pass over ``rows`` candidate states."""
    return 2 * rows * qnet_weights(in_dim, hidden)


def qnet_backward_flops(rows: int, in_dim: int, hidden: tuple[int, ...]) -> int:
    """Gradient with respect to the parameters over ``rows`` states: the
    weight gradient of every layer and the input gradient of every layer
    but the first (the states are not differentiated)."""
    layers = qnet_layer_sizes(in_dim, hidden)
    wgrad = sum(i * o for i, o in layers)
    dgrad = sum(i * o for i, o in layers[1:])
    return 2 * rows * (wgrad + dgrad)


def fleet_q_packed(workers: int, cap: int, in_dim: int,
                   hidden: tuple[int, ...]) -> tuple[int, int]:
    """The training fleet's packed Q dispatch at its operand shapes:
    ``[W, cap, 256]`` u8 planes and ``[W, cap]`` steps-left under each
    worker's own ``[W, ...]`` parameters.  Returns (flops, bytes)."""
    flops = qnet_forward_flops(workers * cap, in_dim, hidden)
    nbytes = (workers * qnet_params(in_dim, hidden) * F32      # params
              + workers * cap * (FP_BYTES + F32)               # bits, frac
              + workers * cap * F32)                           # q out
    return flops, nbytes


def learner_update(workers: int, batch: int, next_rows: int, in_dim: int,
                   hidden: tuple[int, ...]) -> tuple[int, int]:
    """One double-DQN update of every worker.  ``next_rows`` is the number
    of successor rows evaluated per worker (``batch * C`` at the operand
    shapes, or the useful rows for model FLOPs): the online network on the
    states (forward and backward), the online and the target network on
    the successors (forward only).  Bytes: params, target and both Adam
    moments read, params and moments written, and the packed batch.
    Returns (flops, bytes)."""
    flops = workers * (qnet_forward_flops(batch + 2 * next_rows, in_dim, hidden)
                       + qnet_backward_flops(batch, in_dim, hidden))
    p = qnet_params(in_dim, hidden) * F32
    nbytes = workers * (7 * p + (batch + next_rows) * (FP_BYTES + F32))
    return flops, nbytes


def alfabet_flops(atoms: int, feat: int, hidden: int, rounds: int,
                  bond_orders: int = 3) -> int:
    """The BDE message-passing network over one padded molecule."""
    d = hidden
    per_round = bond_orders * (2 * atoms * d * d + 2 * atoms * atoms * d) \
        + 2 * atoms * d * d
    return (2 * atoms * feat * d + rounds * per_round
            + 2 * atoms * d * (d // 2) + 2 * atoms * (d // 2))


def aimnet_flops(atoms: int, feat: int, conf_feat: int, hidden: int,
                 ensemble: int = 1) -> int:
    """The IP network over one padded molecule."""
    d = hidden
    one = (2 * atoms * (feat + conf_feat) * d + 2 * atoms * d * d
           + 2 * d * (d // 2) + 2 * (d // 2))
    return ensemble * one
