"""Plain reference of the predictors' inputs: a molecule's padded graph
arrays and its pseudo-conformer features, written out from the
program's documented scheme.  Imports nothing of the program: a molecule
is its element vector (C, N, O = 0, 1, 2) and its symmetric bond-order
matrix.

Atom features (16): element one-hot (3); degree one-hot, capped at 4
(5); free valence one-hot, capped at 4 (5); total bond order / 4; an
oxygen that carries a hydrogen; the number of rings through the atom,
capped at 3, over 3.  Adjacency: one channel per bond order 1..3.

Rings: for every bond in a cycle, the shortest path between its ends
with that bond left out (breadth first, neighbours in index order),
one ring per distinct atom set.

A conformer exists unless an atom lies in three rings or more, two rings
that share a bond include one of at most four atoms, a ring atom carries
a triple bond, a ring atom of a ring of at most five carries two double
bonds, or an atom of degree four in a three-ring carries a double bond.
Its coordinates are the three lowest non-trivial eigenvectors of the
bond-weighted graph Laplacian (weight ``1 / length``, lengths 1.5, 1.34,
1.2 by order), each over the square root of its eigenvalue, scaled to a
mean bond length of 1.5.  Per atom (8): distance to the centroid; atoms
within 2.2; mean and least distance to the other atoms; mean and
largest distance to bonded atoms; atoms within 3.0; the third
coordinate.
"""

from __future__ import annotations

from collections import deque

import numpy as np

VALENCE = np.array([4, 3, 2], np.int64)          # C, N, O
OXYGEN = 2
ATOM_FEAT, CONF_FEAT, BOND_ORDERS = 16, 8, 3
BOND_LEN = {1: 1.5, 2: 1.34, 3: 1.2}


def _shortest_path(bonds: np.ndarray, src: int, dst: int) -> list[int] | None:
    """Breadth-first path ``src`` -> ``dst`` leaving out the bond between
    them."""
    prev = {src: -1}
    q = deque([src])
    while q:
        u = q.popleft()
        if u == dst:
            path = [dst]
            while prev[path[-1]] >= 0:
                path.append(prev[path[-1]])
            return path[::-1]
        for v in np.nonzero(bonds[u])[0]:
            v = int(v)
            if {u, v} == {src, dst} or v in prev:
                continue
            prev[v] = u
            q.append(v)
    return None


def rings(bonds: np.ndarray) -> list[frozenset]:
    found: dict[frozenset, int] = {}
    n = bonds.shape[0]
    for i in range(n):
        for j in np.nonzero(bonds[i])[0]:
            if j > i:
                path = _shortest_path(bonds, i, int(j))
                if path is not None:
                    found.setdefault(frozenset(path), len(path))
    return list(found)


def free_valence(elements: np.ndarray, bonds: np.ndarray) -> np.ndarray:
    return VALENCE[np.asarray(elements, np.int64)] - np.asarray(bonds, np.int64).sum(axis=1)


def has_oh(elements: np.ndarray, bonds: np.ndarray) -> bool:
    return bool(np.any((np.asarray(elements) == OXYGEN)
                       & (free_valence(elements, bonds) >= 1)))


def graph_arrays(elements: np.ndarray, bonds: np.ndarray, max_atoms: int) -> dict:
    el = np.asarray(elements, np.int64)
    b = np.asarray(bonds, np.int64)
    n = el.shape[0]
    fv = free_valence(el, b)
    deg = np.count_nonzero(b, axis=1)
    member = np.zeros(n, np.int64)
    for r in rings(b):
        member[list(r)] += 1
    feat = np.zeros((max_atoms, ATOM_FEAT), np.float32)
    idx = np.arange(n)
    feat[idx, el] = 1.0
    feat[idx, 3 + np.minimum(deg, 4)] = 1.0
    feat[idx, 8 + np.minimum(fv, 4)] = 1.0
    feat[idx, 13] = b.sum(axis=1) / 4.0
    feat[idx, 14] = ((el == OXYGEN) & (fv >= 1)).astype(np.float32)
    feat[idx, 15] = np.minimum(member, 3) / 3.0
    adj = np.zeros((max_atoms, max_atoms, BOND_ORDERS), np.float32)
    for o in range(1, BOND_ORDERS + 1):
        adj[:n, :n, o - 1] = b == o
    mask = np.zeros(max_atoms, np.float32)
    mask[:n] = 1.0
    return {"atom_feat": feat, "adj": adj, "mask": mask}


def conformer_valid(bonds: np.ndarray) -> bool:
    b = np.asarray(bonds, np.int64)
    n = b.shape[0]
    if n == 0:
        return False
    rs = rings(b)
    member = np.zeros(n, np.int64)
    for r in rs:
        member[list(r)] += 1
    if np.any(member >= 3):
        return False
    for x in range(len(rs)):
        for y in range(x + 1, len(rs)):
            if len(rs[x] & rs[y]) >= 2 and min(len(rs[x]), len(rs[y])) <= 4:
                return False
    for i in range(n):
        if not member[i]:
            continue
        n_double, n_triple = int(np.sum(b[i] == 2)), int(np.sum(b[i] == 3))
        smallest = min(len(r) for r in rs if i in r)
        if n_triple >= 1:
            return False
        if (n_double >= 2) and smallest <= 5:
            return False
        if smallest == 3 and n_double >= 1 and np.count_nonzero(b[i]) >= 4:
            return False
    return True


def coordinates(bonds: np.ndarray) -> np.ndarray:
    b = np.asarray(bonds, np.int64)
    n = b.shape[0]
    if n == 1:
        return np.zeros((1, 3))
    w = np.zeros((n, n))
    for i in range(n):
        for j in np.nonzero(b[i])[0]:
            w[i, j] = 1.0 / BOND_LEN[int(b[i, j])]
    vals, vecs = np.linalg.eigh(np.diag(w.sum(axis=1)) - w)
    keep = [k for k in np.argsort(vals) if vals[k] > 1e-9][:3]
    xyz = np.zeros((n, 3))
    for d, k in enumerate(keep):
        xyz[:, d] = vecs[:, k] / np.sqrt(max(vals[k], 1e-9))
    lengths = [np.linalg.norm(xyz[i] - xyz[j])
               for i in range(n) for j in np.nonzero(b[i])[0] if j > i]
    if lengths and np.mean(lengths) > 1e-12:
        xyz *= 1.5 / np.mean(lengths)
    return xyz


def conformer_features(bonds: np.ndarray, max_atoms: int) -> np.ndarray:
    b = np.asarray(bonds, np.int64)
    n = b.shape[0]
    xyz = coordinates(b)
    out = np.zeros((max_atoms, CONF_FEAT), np.float32)
    pair = np.linalg.norm(xyz[:, None, :] - xyz[None, :, :], axis=-1)
    np.fill_diagonal(pair, np.inf)
    out[:n, 0] = np.linalg.norm(xyz - xyz.mean(axis=0), axis=1)
    for i in range(n):
        others = pair[i][np.isfinite(pair[i])]
        bonded = np.nonzero(b[i])[0]
        out[i, 1] = np.sum(pair[i] < 2.2)
        out[i, 2] = others.mean() if others.size else 0.0
        out[i, 3] = others.min() if others.size else 0.0
        if bonded.size:
            out[i, 4] = pair[i, bonded].mean()
            out[i, 5] = pair[i, bonded].max()
        out[i, 6] = np.sum(pair[i] < 3.0)
        out[i, 7] = xyz[i, 2]
    return out


def features(molecules: list, max_atoms: int) -> dict:
    """Stacked predictor inputs of ``(elements, bonds)`` pairs, with
    ``conf_valid`` (zero conformer features where none exists) and
    ``has_oh``."""
    rows = []
    for el, b in molecules:
        f = graph_arrays(el, b, max_atoms)
        valid = conformer_valid(b)
        f["conf_feat"] = conformer_features(b, max_atoms) if valid \
            else np.zeros((max_atoms, CONF_FEAT), np.float32)
        f["conf_valid"] = np.float32(valid)
        f["has_oh"] = has_oh(el, b)
        rows.append(f)
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}
