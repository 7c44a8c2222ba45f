"""The reference's predictor inputs, written out from the documented
scheme, equal what the program's ``featurize`` builds, bit for bit: over the
antioxidant starts and the molecules one edit away from some of them,
and over strained rings where a conformer fails."""

import numpy as np
import pytest


def _molecules():
    from repro.chem.actions import enumerate_actions
    from repro.data.datasets import antioxidant_dataset

    starts = antioxidant_dataset(700)[::7]
    out = list(starts)
    for m in starts[:20]:
        out += [a.result for a in enumerate_actions(m)[::5]]
    return out


def _strained():
    from repro.chem.smiles import from_smiles

    return [from_smiles(s) for s in ("C1CC1O", "C1=CC1(C)CO", "C12CC1C2O",
                                     "C1CC2CC12O", "OC1C#CCCC1", "OC1=C=CCC1",
                                     "OC12CC(C1)C2", "O")]


@pytest.mark.parametrize("which", ["antioxidants", "strained"])
def test_reference_features_match_the_programs(which):
    from chip.reference import features as rfeat
    from repro.predictors.service import featurize, stack_features

    mols = _molecules() if which == "antioxidants" else _strained()
    prog = stack_features([featurize(m, 40) for m in mols])
    ref = rfeat.features([(m.elements, m.bonds) for m in mols], 40)
    for k in ("atom_feat", "adj", "mask", "conf_feat", "conf_valid"):
        np.testing.assert_array_equal(ref[k], prog[k], err_msg=k)
    assert list(ref["has_oh"]) == [m.has_oh_bond() for m in mols]
    if which == "strained":
        assert 0 < ref["conf_valid"].sum() < len(mols)
