"""The kernels' work functions against counts by hand on the 8-atom
chain, and against the program's own topology."""
import importlib.util

import numpy as np
import pytest

from bench import reference as ref
from bench.tests.conftest import ROOT

SYSTEM8 = {"n_atoms": 8, "excluded_separation": 3}


def metric(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_nonbonded_pairs_by_hand():
    nb = metric("nb_dense_roofline")
    # 8 atoms: pairs at separation 3, 4, 5, 6, 7 -> 5 + 4 + 3 + 2 + 1
    assert nb.interacting_pairs(SYSTEM8) == 15
    flops, nbytes = nb.work(SYSTEM8, replicas=2)
    assert flops == 2 * 15 * nb.FLOPS_PER_PAIR
    # positions in and forces out per replica, 3 parameters per atom
    assert nbytes == 4 * (2 * 2 * 8 * 3 + 3 * 8)


def test_nonbonded_pairs_match_the_program_mask():
    from repro.md.system import chain_molecule
    nb = metric("nb_dense_roofline")
    for n in (8, 64, 2881):
        mask = np.asarray(chain_molecule(n).nb_mask)
        sys_ = {"n_atoms": n, "excluded_separation": 3}
        assert nb.interacting_pairs(sys_) == int(np.triu(mask).sum())


def test_bonded_terms_by_hand():
    b = metric("bonded_roofline")
    flops, nbytes = b.work(SYSTEM8, replicas=3)
    # 7 bonds, 6 angles, 5 dihedrals
    per = 7 * b.FLOPS["bond"] + 6 * b.FLOPS["angle"] + 5 * b.FLOPS["dihedral"]
    assert flops == 3 * per
    assert nbytes == 4 * (2 * 3 * 8 * 3 + 2 * (7 + 6 + 5))


def test_bonded_terms_match_the_program_topology():
    from repro.md.system import chain_molecule
    s = chain_molecule(8)
    assert (len(s.bonds), len(s.angles), len(s.dihedrals)) == (7, 6, 5)


@pytest.mark.parametrize("name", ["nb_dense_roofline", "bonded_roofline"])
def test_work_grows_with_replicas_and_atoms(name):
    m = metric(name)
    f1, b1 = m.work({"n_atoms": 64, "excluded_separation": 3}, 4)
    f2, b2 = m.work({"n_atoms": 64, "excluded_separation": 3}, 8)
    f3, _ = m.work({"n_atoms": 128, "excluded_separation": 3}, 4)
    assert f2 == 2 * f1 and b2 > b1 and f3 > f1


def test_reference_topology_matches_the_program():
    """The reference rebuilds the chain from the configuration file; the
    program builds it in code.  Same bonds, angles, torsions, charges."""
    import json
    from repro.md.system import chain_molecule
    conf = json.loads((ROOT / "bench" / "configs" /
                       "tremd_chain2881.json").read_text())
    system = dict(conf["system"], n_atoms=40)
    top = ref.chain_topology(system)
    prog = chain_molecule(40)
    np.testing.assert_array_equal(top["bonds"], np.asarray(prog.bonds))
    np.testing.assert_array_equal(top["angles"], np.asarray(prog.angles))
    np.testing.assert_array_equal(top["quads"], np.asarray(prog.dihedrals))
    np.testing.assert_allclose(top["dih_n"], np.asarray(prog.dihedral_n))
    np.testing.assert_allclose(top["dih_k"], np.asarray(prog.dihedral_k))
    np.testing.assert_allclose(top["charges"], np.asarray(prog.charges),
                               rtol=1e-6)
    idx = np.arange(40)
    mask = np.abs(idx[:, None] - idx[None, :]) >= top["excluded_separation"]
    np.testing.assert_array_equal(mask, np.asarray(prog.nb_mask) > 0)
