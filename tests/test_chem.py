"""Chemistry substrate: invariants, actions, fingerprints, SMILES, oracle."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # declared in pyproject [test]; degrade to a skip
    HAVE_HYPOTHESIS = False

from repro.chem import (
    ALLOWED_RING_SIZES, Molecule, enumerate_actions,
    morgan_fingerprint, IncrementalMorgan, oracle_bde, oracle_ip,
    has_valid_conformer, sa_score, qed_score, penalized_logp, tanimoto,
)
from repro.chem.actions import enumerate_actions_naive, enumerate_actions_ref
from repro.chem.fingerprint import (
    batch_fingerprints_incremental, batch_morgan_fingerprints,
    incremental_fingerprints_grouped, morgan_fingerprint_reference)
from repro.chem.molecule import iso_hash, refine_invariants
from repro.chem.smiles import canonical_smiles, from_smiles, to_smiles
from repro.data.datasets import antioxidant_dataset

PHENOL = "C1=CC=CC=C1O"
BHT_ISH = "CC1=CC(C)=CC(C)=C1O"


@pytest.fixture(scope="module")
def phenol():
    return from_smiles(PHENOL)


@pytest.fixture(scope="module")
def bht():
    return from_smiles(BHT_ISH)


# ------------------------------------------------------------------ #
# molecule basics
# ------------------------------------------------------------------ #
def test_valences_and_oh(phenol):
    phenol.check_valences()
    assert phenol.has_oh_bond()
    assert phenol.num_atoms == 7
    assert len(phenol.ring_info()) == 1
    assert len(phenol.ring_info()[0]) == 6


def test_ring_info_never_writes_bonds():
    """The pipelined rollout enumerates molecules on host threads while the
    property path computes ring_info on the same objects, so ring_info must
    not touch self.bonds even transiently (regression: it used to zero and
    restore each cycle bond, a data race under the overlap)."""
    mol = from_smiles(PHENOL)
    mol.bonds.flags.writeable = False      # any write now raises
    rings = mol.ring_info()
    assert len(rings) == 1 and len(rings[0]) == 6


def test_canonical_key_permutation_invariant(bht):
    rng = np.random.default_rng(0)
    for _ in range(5):
        perm = rng.permutation(bht.num_atoms)
        m2 = Molecule(bht.elements[perm], bht.bonds[np.ix_(perm, perm)])
        assert m2.canonical_key() == bht.canonical_key()
        assert iso_hash(m2) == iso_hash(bht)
        assert canonical_smiles(m2) == canonical_smiles(bht)


def test_iso_hash_distinguishes(phenol, bht):
    assert iso_hash(phenol) != iso_hash(bht)


def test_largest_fragment(phenol):
    # break the C-O bond: O falls off, ring is kept
    i = int(phenol.oh_oxygens()[0])
    j = int(phenol.neighbors(i)[0])
    frag = phenol.with_bond_delta(i, j, -1).largest_fragment()
    assert frag.num_atoms == 6
    assert not frag.has_oh_bond()


# ------------------------------------------------------------------ #
# actions
# ------------------------------------------------------------------ #
def test_actions_match_naive(phenol, bht):
    for mol in (phenol, bht):
        fast = {a.result.canonical_key() for a in enumerate_actions(mol)}
        slow = {a.result.canonical_key() for a in enumerate_actions_naive(mol)}
        assert fast == slow


def test_oh_protection(phenol):
    for a in enumerate_actions(phenol, protect_oh=True):
        assert a.result.has_oh_bond(), a
    unprotected = enumerate_actions(phenol, protect_oh=False)
    assert any(not a.result.has_oh_bond() for a in unprotected)


def test_ring_size_constraint(phenol):
    for a in enumerate_actions(phenol):
        for ring in a.result.ring_info():
            assert len(ring) in ALLOWED_RING_SIZES | {6}


def test_no_op_present(phenol):
    acts = enumerate_actions(phenol)
    assert acts[0].kind == "no_op"
    assert acts[0].result is phenol


def _action_signature(a):
    r = a.result
    return (a.kind, a.detail, r.elements.tobytes(), r.bonds.tobytes())


def test_delta_enumeration_matches_ref(phenol, bht):
    """The delta enumerator must reproduce the reference action list EXACTLY
    — same order, same details, same concrete (labelled) result arrays —
    across every option combination, not just as a canonical-key set."""
    import itertools
    for mol in (phenol, bht, Molecule.empty(), from_smiles("O"),
                from_smiles("OO"), from_smiles("CC(=O)O")):
        for rem, noop, prot in itertools.product([True, False], repeat=3):
            for max_atoms in (38, 8):
                ref = enumerate_actions_ref(
                    mol, allow_removal=rem, allow_no_op=noop,
                    protect_oh=prot, max_atoms=max_atoms)
                new = enumerate_actions(
                    mol, allow_removal=rem, allow_no_op=noop,
                    protect_oh=prot, max_atoms=max_atoms)
                assert [_action_signature(a) for a in new] == \
                       [_action_signature(a) for a in ref]


def _check_against_ref(mol, **kw):
    """Pins ``enumerate_actions`` to the reference with and without O-H
    protection, and every full removal materialised at enumeration."""
    for prot in (True, False):
        new = enumerate_actions(mol, protect_oh=prot, **kw)
        assert all(a.materialized for a in new if a.kind == "bond_delta"
                   and a.detail[2] == -mol.bonds[a.detail[0], a.detail[1]])
        ref = enumerate_actions_ref(mol, protect_oh=prot, **kw)
        assert [_action_signature(a) for a in new] == \
               [_action_signature(a) for a in ref]


# One case per branch of the full-removal path: smiles, the removed bond,
# and the atoms the result keeps (``largest_fragment``'s rule: more atoms,
# then more O, then the side holding the lower index).
_REMOVAL_CASES = {
    "bridge_atom0_falls_away": ("CCCCO", (0, 1), [1, 2, 3, 4]),
    "bridge_atom0_survives": ("OCCCC", (3, 4), [0, 1, 2, 3]),
    "tie_size_and_o_lower_index": ("OCCO", (1, 2), [0, 1]),
    "tie_size_no_o_lower_index": ("CCCC", (1, 2), [0, 1]),
    "tie_size_more_o_far_side": ("CCCO", (1, 2), [2, 3]),
    "tie_size_more_o_atom0_side": ("OCCC", (1, 2), [0, 1]),
    "ring_single": (PHENOL, (0, 1), list(range(7))),
    "ring_fused": ("C1=CC=C2C=CC=CC2=C1O", (3, 8), list(range(11))),
    "ring_bridged_bicycle": ("OC1CC2CCC1C2", (1, 2), list(range(8))),
    "ring_spiro": ("C1CC12CC2O", (0, 1), list(range(6))),
    "oh_lost_with_fragment": ("CCCCCO", (4, 5), [0, 1, 2, 3, 4]),
    "oh_given_back_to_end": ("CCCOC", (3, 4), [0, 1, 2, 3]),
    "parent_in_fragments": ("CCO.CC", (0, 1), [1, 2]),
}


@pytest.mark.parametrize("case", list(_REMOVAL_CASES))
def test_full_removals_match_ref(case):
    """Each branch of the full-removal path (bridge sides, the tie rules,
    ring bonds, the O-H flag) equals the reference, and the case really
    reaches its branch: the named removal keeps the named atoms, and
    survives protection iff an O-H does."""
    smiles, (i, j), kept = _REMOVAL_CASES[case]
    mol = from_smiles(smiles)
    _check_against_ref(mol)
    order = int(mol.bonds[i, j])
    bonds = mol.bonds.copy()
    bonds[i, j] = bonds[j, i] = 0
    want = Molecule(mol.elements[kept], bonds[np.ix_(kept, kept)])
    for prot in (False, True):
        hits = [a for a in enumerate_actions(mol, protect_oh=prot)
                if a.detail == (i, j, -order)]
        if prot and not want.has_oh_bond():
            assert not hits
            continue
        assert len(hits) == 1
        assert _action_signature(hits[0])[2:] == \
               (want.elements.tobytes(), want.bonds.tobytes())


@pytest.mark.parametrize("seed", range(4))
def test_delta_enumeration_matches_ref_dataset_walks(seed):
    """Every molecule of a 10-step walk from the antioxidant dataset, at the
    configuration's 38-atom cap, enumerates as the reference does."""
    for mol in _walk(seed, seed=seed):
        _check_against_ref(mol, max_atoms=38)


def test_delta_enumeration_is_lazy(bht):
    """Only fragment-dropping removals may materialise eagerly; every other
    edit builds its Molecule on first ``result`` access (the engine only
    ever materialises the CHOSEN action)."""
    acts = enumerate_actions(bht)
    lazy = [a for a in acts if not a.materialized]
    assert len(lazy) > len(acts) // 2
    a = lazy[0]
    r1 = a.result                      # materialises now
    assert a.materialized and a.result is r1


def test_molecule_caches_are_read_only(bht):
    fv = bht.free_valences()
    assert bht.free_valences() is fv   # memoised
    sp = bht.all_pairs_shortest_paths()
    assert bht.all_pairs_shortest_paths() is sp
    with pytest.raises(ValueError):
        fv[0] = 99
    with pytest.raises(ValueError):
        sp[0, 0] = 99


def _walk(start: int, seed: int, steps: int = 10) -> list[Molecule]:
    """The molecules a random walk over ``enumerate_actions`` visits from
    molecule ``start`` of ``antioxidant_dataset``, at the configuration's
    38-atom cap."""
    rng = np.random.default_rng(seed)
    mol = antioxidant_dataset(16)[start]
    out = [mol]
    for _ in range(steps - 1):
        acts = enumerate_actions(mol, max_atoms=38)
        mol = acts[int(rng.integers(0, len(acts)))].result
        out.append(mol)
    return out


_APSP_CASES = {
    "phenol": lambda: [from_smiles(PHENOL)],
    "bht": lambda: [from_smiles(BHT_ISH)],
    "two_fragments": lambda: [from_smiles("CC.O"),
                              from_smiles("OC1=CC=CC=C1.CCO")],
    "empty": lambda: [Molecule.empty()],
    "single_atom": lambda: [Molecule.from_element("O")],
    **{f"walk{s}": (lambda s=s: _walk(s, seed=s)) for s in range(4)},
}


@pytest.mark.parametrize("case", list(_APSP_CASES))
def test_all_pairs_shortest_paths_match_bfs(case):
    """The level-at-a-time distance matrix equals the per-pair BFS
    ``shortest_path_length`` everywhere, -1 between fragments included."""
    for mol in _APSP_CASES[case]():
        n = mol.num_atoms
        sp = mol.all_pairs_shortest_paths()
        want = np.array([[mol.shortest_path_length(i, j) for j in range(n)]
                         for i in range(n)], dtype=np.int32).reshape(n, n)
        assert sp.dtype == np.int32
        assert np.array_equal(sp, want)
        if case == "two_fragments":
            assert (sp == -1).any()


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(0, 10**6))
    def test_random_walk_preserves_invariants(seed):
        rng = np.random.default_rng(seed)
        mol = from_smiles(PHENOL)
        for _ in range(4):
            acts = enumerate_actions(mol, max_atoms=14)
            a = acts[int(rng.integers(0, len(acts)))]
            mol = a.result
            mol.check_valences()
            assert mol.has_oh_bond()
            assert mol.num_atoms <= 15

    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10**6),
           st.sampled_from([PHENOL, BHT_ISH, "CC(=O)O", "OO"]))
    def test_delta_enumeration_matches_ref_random_walks(seed, smiles):
        """Random-walk property layer over the exact-pinning test: at every
        visited molecule the delta enumerator equals the reference."""
        rng = np.random.default_rng(seed)
        mol = from_smiles(smiles)
        for _ in range(4):
            ref = enumerate_actions_ref(mol, max_atoms=16)
            new = enumerate_actions(mol, max_atoms=16)
            assert [_action_signature(a) for a in new] == \
                   [_action_signature(a) for a in ref]
            if not new:
                break
            mol = new[int(rng.integers(0, len(new)))].result
else:
    def test_random_walk_preserves_invariants():
        pytest.importorskip("hypothesis")

    def test_delta_enumeration_matches_ref_random_walks():
        pytest.importorskip("hypothesis")


# ------------------------------------------------------------------ #
# fingerprints
# ------------------------------------------------------------------ #
def test_incremental_equals_full(bht):
    inc = IncrementalMorgan(bht)
    assert np.array_equal(inc.fingerprint(counts=True),
                          morgan_fingerprint(bht, counts=True))
    for a in enumerate_actions(bht)[:40]:
        inc2 = inc.after_action(a.result, a.kind, a.detail)
        assert np.array_equal(inc2.fingerprint(counts=True),
                              morgan_fingerprint(a.result, counts=True)), a


def test_batched_incremental_equals_full(phenol, bht):
    """The shared-parent batched pass == full recompute, bit for bit, for
    every candidate (including no-ops and fragment-dropping removals), for
    every routing threshold, binary and counts."""
    for mol in (phenol, bht, from_smiles("OO"), from_smiles("OCC#N")):
        acts = enumerate_actions(mol)
        full = batch_morgan_fingerprints([a.result for a in acts])
        for full_ratio in (0.0, 0.6, 1.1):   # all-full / mixed / all-incremental
            inc = incremental_fingerprints_grouped(
                [mol], [acts], full_ratio=full_ratio)[0]
            assert np.array_equal(full, inc)
        fullc = batch_morgan_fingerprints([a.result for a in acts], counts=True)
        incc = batch_fingerprints_incremental(mol, acts, counts=True)
        assert np.array_equal(fullc, incc)


def test_batched_incremental_grouped_composition_independent(phenol, bht):
    """Cross-slot batching and chunking must not change any bit (the
    pipelined rollout shards slots across threads arbitrarily)."""
    parents = [phenol, bht, from_smiles("CC(=O)O")]
    groups = [enumerate_actions(p) for p in parents]
    ref = [batch_fingerprints_incremental(p, g) for p, g in zip(parents, groups)]
    for chunk in (7, 64, 0):
        got = incremental_fingerprints_grouped(parents, groups, chunk=chunk)
        for r, g in zip(ref, got):
            assert np.array_equal(r, g)


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 10**6),
           st.sampled_from([PHENOL, BHT_ISH, "OO", "OC1CC1"]))
    def test_incremental_fingerprints_random_edit_sequences(seed, smiles):
        """Across random edit sequences, pin BOTH §3.6 incremental paths to
        the full recompute: ``IncrementalMorgan.after_action`` (single-edit
        reference, threaded along the walk) and the batched shared-parent
        pass (all candidates of every visited state).  Removals are included,
        so fragment-dropping edits exercise the re-indexing fallbacks."""
        rng = np.random.default_rng(seed)
        mol = from_smiles(smiles)
        inc = IncrementalMorgan(mol)
        for _ in range(4):
            acts = enumerate_actions(mol, max_atoms=16)
            if not acts:
                break
            batched = batch_fingerprints_incremental(mol, acts)
            full = batch_morgan_fingerprints([a.result for a in acts])
            assert np.array_equal(batched, full)
            a = acts[int(rng.integers(0, len(acts)))]
            inc = inc.after_action(a.result, a.kind, a.detail)
            mol = a.result
            assert np.array_equal(inc.fingerprint(counts=True),
                                  morgan_fingerprint(mol, counts=True))
else:
    def test_incremental_fingerprints_random_edit_sequences():
        pytest.importorskip("hypothesis")


def test_batch_equals_single(phenol, bht):
    mols = [a.result for a in enumerate_actions(bht)[:25]] + [phenol]
    batch = batch_morgan_fingerprints(mols, counts=True)
    for i, m in enumerate(mols):
        assert np.array_equal(batch[i], morgan_fingerprint(m, counts=True))


def test_fingerprint_permutation_invariant(bht):
    rng = np.random.default_rng(1)
    perm = rng.permutation(bht.num_atoms)
    m2 = Molecule(bht.elements[perm], bht.bonds[np.ix_(perm, perm)])
    assert np.array_equal(morgan_fingerprint(m2, counts=True),
                          morgan_fingerprint(bht, counts=True))


def test_reference_fingerprint_runs(bht):
    fp = morgan_fingerprint_reference(bht)
    assert fp.shape == (2048,) and fp.sum() > 0


# ------------------------------------------------------------------ #
# SMILES
# ------------------------------------------------------------------ #
def test_smiles_roundtrip_actions(bht):
    for a in enumerate_actions(bht):
        s = canonical_smiles(a.result)
        m = from_smiles(s)
        assert m.canonical_key() == a.result.canonical_key(), s


def test_smiles_multifragment():
    assert from_smiles("C.O").num_atoms == 2


# ------------------------------------------------------------------ #
# oracle / properties
# ------------------------------------------------------------------ #
def test_oracle_tradeoff_direction(phenol):
    """Adding an ortho amino group must lower BDE *and* lower IP (§2.1)."""
    ring_c = int(phenol.neighbors(phenol.oh_oxygens()[0])[0])
    ortho = [int(v) for v in phenol.neighbors(ring_c) if phenol.symbol(v) == "C"][0]
    sub = phenol.with_added_atom("N", ortho, 1)
    assert oracle_bde(sub) < oracle_bde(phenol)
    assert oracle_ip(sub) < oracle_ip(phenol)


def test_oracle_bde_none_without_oh():
    assert oracle_bde(from_smiles("C1=CC=CC=C1")) is None


def test_conformer_validity_rules(phenol):
    assert has_valid_conformer(phenol)
    # triple bond in a ring is invalid
    bad = from_smiles("C1=CC=CC=C1O")
    bonds = bad.bonds.copy()
    bonds[1, 2] = bonds[2, 1] = 3
    m = Molecule(bad.elements, bonds)
    assert not has_valid_conformer(m)


def test_scores_ranges(bht):
    assert 1.0 <= sa_score(bht) <= 8.0
    assert 0.0 < qed_score(bht) < 0.95
    assert penalized_logp(bht) < 5
    assert tanimoto(bht, bht) == 1.0
    assert 0.0 <= tanimoto(bht, from_smiles(PHENOL)) < 1.0
