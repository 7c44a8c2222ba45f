"""Plain reference of the candidate fingerprints the Q-network reads.

Morgan (ECFP-style) radius 3 folded to 2048 bits, with the program's
documented hashing scheme written out one molecule at a time:

* an atom's radius-0 invariant is ``mix64(((element * 64 + degree) * 64
  + total bond order) * 64 + free valence)`` with elements C, N, O = 0, 1, 2
  and valences 4, 3, 2;
* radius ``r`` is ``mix64(mix64(h) + sum over bonded j of
  mix64(h_j XOR salt[order_ij]))`` (a commutative neighbour sum);
* every atom's hash at every radius 0..3 sets bit ``hash mod 2048``;

``mix64`` is the splitmix64 finaliser.  Packed rows follow numpy's
``packbits`` order (bit ``8i + k`` is bit ``7 - k`` of byte ``i``).
Imports nothing of the program: a molecule is its element vector and its
symmetric bond-order matrix.
"""

from __future__ import annotations

import numpy as np

RADIUS = 3
N_BITS = 2048
VALENCE = np.array([4, 3, 2], np.int64)          # C, N, O
SALT = np.array([0x0, 0xA24BAED4963EE407, 0x9FB21C651E98DF25,
                 0xD6E8FEB86659FD93], np.uint64)
_C0 = np.uint64(0x9E3779B97F4A7C15)
_C1 = np.uint64(0xBF58476D1CE4E5B9)
_C2 = np.uint64(0x94D049BB133111EB)


def mix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        z = x.astype(np.uint64) + _C0
        z = (z ^ (z >> np.uint64(30))) * _C1
        z = (z ^ (z >> np.uint64(27))) * _C2
        return z ^ (z >> np.uint64(31))


def fingerprint_bits(elements: np.ndarray, bonds: np.ndarray) -> np.ndarray:
    """One molecule -> bool [2048]."""
    el = np.asarray(elements, np.int64)
    bonds = np.asarray(bonds, np.int64)
    n = el.shape[0]
    out = np.zeros(N_BITS, bool)
    if n == 0:
        return out
    tot = bonds.sum(axis=1)
    deg = np.count_nonzero(bonds, axis=1)
    free = VALENCE[el] - tot
    h = mix64((((el * 64 + deg) * 64 + tot) * 64 + free).astype(np.uint64))
    hashes = [h]
    bonded = bonds > 0
    for _ in range(RADIUS):
        with np.errstate(over="ignore"):
            nb = np.where(bonded, mix64(h[None, :] ^ SALT[bonds]),
                          np.uint64(0)).sum(axis=1, dtype=np.uint64)
            h = mix64(mix64(h) + nb)
        hashes.append(h)
    out[(np.concatenate(hashes) % np.uint64(N_BITS)).astype(np.int64)] = True
    return out


def packed_fingerprint(elements: np.ndarray, bonds: np.ndarray) -> np.ndarray:
    """One molecule -> u8 [256]."""
    return np.packbits(fingerprint_bits(elements, bonds))
