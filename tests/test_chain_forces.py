"""Analytic force kernels vs autodiff-of-energy oracles.

The ``force_path="pallas"`` hot path computes forces in closed form
(kernels/chain_forces bonded pass + kernels/lj_forces nonbonded pass).
This suite pins the hand-derived gradients to ``jax.grad`` of the
``repro.md.energy`` reference energies — per term class, with and
without the umbrella bias, replica-batched, and through the Pallas
kernels in interpret mode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chain_forces import ops as chain_ops
from repro.kernels.chain_forces import ref as chain_ref
from repro.kernels.lj_forces import ops as nb_ops
from repro.kernels.lj_forces import ref as nb_ref
from repro.md import MDEngine
from repro.md import energy as E
from repro.md.system import chain_molecule


def _setup(n_atoms=22, n_rep=4):
    sysm = chain_molecule(n_atoms)
    pos = MDEngine(system=sysm).init_state(jax.random.key(0), n_rep)["pos"]
    return sysm, pos


def _umbrella(n_rep, n_u):
    c = jax.random.uniform(jax.random.key(1), (n_rep, n_u)) * 360.0
    return c, jnp.full((n_rep, n_u), 0.02)


def _force_scale(g):
    return max(float(jnp.max(jnp.abs(g))), 1.0)


@pytest.mark.parametrize("term", ["all", "bonds", "angles", "dihedrals"])
def test_bonded_ref_matches_autodiff_per_term(term):
    """Analytic bonded forces == -grad of the bonded energy, per class
    (isolated by zeroing the other classes' force constants)."""
    sysm, pos = _setup()
    zero = {"bonds": {"angle_k", "dihedral_k"},
            "angles": {"bond_k", "dihedral_k"},
            "dihedrals": {"bond_k", "angle_k"}}.get(term, set())
    sysm = dataclasses.replace(
        sysm, **{k: jnp.zeros_like(getattr(sysm, k)) for k in zero})
    top = chain_ref.chain_topology(sysm)
    f, e = chain_ref.bonded_forces(pos, top)
    g = jax.grad(lambda p: jnp.sum(E.batched_bonded_energy(p, sysm)))(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=2e-3 * _force_scale(g))
    np.testing.assert_allclose(
        np.asarray(e), np.asarray(E.batched_bonded_energy(pos, sysm)),
        rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("n_u", [1, 2])
def test_bonded_ref_bias_matches_autodiff(n_u):
    """Umbrella-bias torque (U=1 and U=2) rides the torsion pass."""
    sysm, pos = _setup()
    top = chain_ref.chain_topology(sysm)
    c, k = _umbrella(pos.shape[0], n_u)

    def u(p):
        e_b, phi, psi = E._batched_bonded_terms(p, sysm)
        return jnp.sum(e_b + E.batched_bias_energy(phi, psi, c, k))

    f, _ = chain_ref.bonded_forces(pos, top, c, k)
    g = jax.grad(u)(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=2e-3 * _force_scale(g))


@pytest.mark.parametrize("n_atoms", [10, 22, 46])
@pytest.mark.parametrize("bias", [False, True])
def test_chain_kernel_interpret_matches_ref(n_atoms, bias):
    """The Pallas bonded kernel (interpret mode) == the jnp analytic
    oracle, across system sizes and with/without the bias."""
    sysm, pos = _setup(n_atoms)
    pack = chain_ops.build_pack(sysm)
    args = _umbrella(pos.shape[0], 2) if bias else (None, None)
    f_r, e_r = chain_ops.bonded_forces(pos, pack, *args, use_kernel=False)
    f_k, e_k = chain_ops.bonded_forces(pos, pack, *args, use_kernel=True,
                                       interpret=True)
    scale = _force_scale(f_r)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_r),
                               atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r),
                               rtol=1e-4, atol=1e-3)


def test_nonbonded_ref_matches_autodiff():
    """Analytic LJ + elec forces == -grad of the pairwise energies, and
    the energy accumulators match the batched energy terms."""
    sysm, pos = _setup()
    f_lj, f_el, e_lj, e_el = nb_ref.nonbonded(
        pos, sysm.lj_sigma, sysm.lj_eps, sysm.charges, sysm.nb_mask)
    g_lj = jax.grad(lambda p: jnp.sum(E.batched_lj_energy(p, sysm)))(pos)
    g_el = jax.grad(lambda p: jnp.sum(E.batched_elec_energy(p, sysm)))(pos)
    np.testing.assert_allclose(np.asarray(f_lj), np.asarray(-g_lj),
                               atol=1e-4 * _force_scale(g_lj))
    np.testing.assert_allclose(np.asarray(f_el), np.asarray(-g_el),
                               atol=1e-4 * _force_scale(g_el))
    np.testing.assert_allclose(np.asarray(e_lj),
                               np.asarray(E.batched_lj_energy(pos, sysm)),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(e_el),
                               np.asarray(E.batched_elec_energy(pos, sysm)),
                               rtol=1e-5, atol=1e-3)


def _oracle64(fn, pos, *args, **kw):
    """``fn`` (an ``nb_ref`` pass) evaluated in float64: the oracle's own
    float32 rounding (its rowsum-minus-GEMM force identity cancels at
    large |x|) stays out of the kernel comparison."""
    with jax.enable_x64(True):
        cast = [None if a is None else jnp.asarray(a, jnp.float64)
                for a in (pos, *args)]
        kw = {k: None if v is None else jnp.asarray(v, jnp.float64)
              for k, v in kw.items()}
        return jax.tree.map(np.asarray, fn(*cast, **kw))


@pytest.mark.parametrize("n_atoms,n_rep,block", [
    (22, 4, 32), (200, 1, 128), (200, 3, 128), (300, 1, 128),
    (300, 3, 128)])
@pytest.mark.parametrize("variant", ["energies", "force", "force_salted"])
def test_nonbonded_kernel_interpret_matches_ref(n_atoms, n_rep, block,
                                                variant):
    """The chain nonbonded Pallas kernel (interpret) == the jnp oracle:
    both forces AND both energy accumulators from the one sweep, or the
    force-only build through the salt-folded propagate entry, with live
    lane padding (N not a multiple of 128) and R = 1 or several."""
    sysm, pos = _setup(n_atoms, n_rep)
    args = (sysm.lj_sigma, sysm.lj_eps, sysm.charges, sysm.nb_mask)
    if variant == "energies":
        k_out = nb_ops.nonbonded_batched(pos, *args, block=block,
                                         interpret=True)
        want = _oracle64(nb_ref.nonbonded, pos, *args)
        names = ("f_lj", "f_el", "e_lj", "e_el")
    else:
        scale = (jnp.linspace(0.6, 1.0, n_rep)
                 if variant == "force_salted" else None)
        k_out = (nb_ops.nonbonded_force(pos, *args, salt_scale=scale,
                                        use_kernel=True, block=block,
                                        interpret=True),)
        want = (_oracle64(nb_ref.nonbonded_force, pos, *args,
                          salt_scale=scale),)
        names = ("f",)
    for name, a, b in zip(names, k_out, want):
        scale = max(float(np.max(np.abs(b))), 1.0)
        np.testing.assert_allclose(np.asarray(a), b, atol=1e-5 * scale,
                                   err_msg=name)


@pytest.mark.parametrize("salted", [False, True])
def test_nonbonded_force_combined(salted):
    """The salt-folded single-pass force == f_lj + scale * f_el."""
    sysm, pos = _setup()
    args = (sysm.lj_sigma, sysm.lj_eps, sysm.charges, sysm.nb_mask)
    scale = (jnp.linspace(0.6, 1.0, pos.shape[0]) if salted else None)
    f = nb_ops.nonbonded_force(pos, *args, salt_scale=scale,
                               use_kernel=False)
    f_lj, f_el, _, _ = nb_ref.nonbonded(pos, *args)
    want = f_lj + (f_el if scale is None else scale[:, None, None] * f_el)
    np.testing.assert_allclose(np.asarray(f), np.asarray(want),
                               rtol=1e-5, atol=1e-4 * _force_scale(want))


def test_generic_topology_contraction():
    """The incidence contraction is not chain-specific: a topology with
    permuted atom numbering still matches autodiff."""
    sysm, _ = _setup(12)
    perm = np.asarray([3, 7, 0, 9, 4, 11, 1, 8, 5, 10, 2, 6])
    relabel = lambda a: jnp.asarray(perm[np.asarray(a)], jnp.int32)
    shuffled = dataclasses.replace(
        sysm, bonds=relabel(sysm.bonds), angles=relabel(sysm.angles),
        dihedrals=relabel(sysm.dihedrals),
        phi_quad=tuple(int(perm[i]) for i in sysm.phi_quad),
        psi_quad=tuple(int(perm[i]) for i in sysm.psi_quad))
    top = chain_ref.chain_topology(shuffled)
    pos = MDEngine(system=shuffled).init_state(jax.random.key(3), 3)["pos"]
    f, _ = chain_ref.bonded_forces(pos, top)
    g = jax.grad(lambda p: jnp.sum(E.batched_bonded_energy(p, shuffled)))(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=2e-3 * _force_scale(g))


@pytest.mark.parametrize("term", ["all", "bonds", "angles", "dihedrals"])
def test_sparse_bonded_matches_autodiff_per_term(term):
    """The slot-table contraction == -grad of the bonded energy, per
    class — the same per-term oracle the dense contraction is pinned
    to, so dense and sparse are pinned to one reference."""
    sysm, pos = _setup()
    zero = {"bonds": {"angle_k", "dihedral_k"},
            "angles": {"bond_k", "dihedral_k"},
            "dihedrals": {"bond_k", "angle_k"}}.get(term, set())
    sysm = dataclasses.replace(
        sysm, **{k: jnp.zeros_like(getattr(sysm, k)) for k in zero})
    top = chain_ref.chain_topology(sysm)
    slots = chain_ref.bonded_slots(top)
    f, e = chain_ref.bonded_forces_sparse(pos, top, slots)
    g = jax.grad(lambda p: jnp.sum(E.batched_bonded_energy(p, sysm)))(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=2e-3 * _force_scale(g))
    np.testing.assert_allclose(
        np.asarray(e), np.asarray(E.batched_bonded_energy(pos, sysm)),
        rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("n_atoms", [10, 46, 256, 1024])
@pytest.mark.parametrize("bias", [False, True])
def test_sparse_bonded_matches_dense(n_atoms, bias):
    """Sparse vs dense contraction of the SAME edge gradients, with and
    without the umbrella bias, up to N=1024: forces to float tolerance
    (the contraction order differs), energies exactly (the energy never
    touches the contraction)."""
    sysm, pos = _setup(n_atoms, n_rep=2)
    top = chain_ref.chain_topology(sysm)
    slots = chain_ref.bonded_slots(top)
    args = _umbrella(pos.shape[0], 2) if bias else (None, None)
    f_d, e_d = chain_ref.bonded_forces(pos, top, *args)
    f_s, e_s = chain_ref.bonded_forces_sparse(pos, top, slots, *args)
    np.testing.assert_allclose(np.asarray(f_s), np.asarray(f_d),
                               atol=1e-5 * _force_scale(f_d))
    np.testing.assert_array_equal(np.asarray(e_s), np.asarray(e_d))
    # the slot tables stay a topology CONSTANT: width independent of N
    assert slots.idx.shape == (n_atoms, slots.n_slots)
    assert slots.n_slots <= 15


def test_sparse_bonded_permuted_topology():
    """The host-side incidence inversion is not chain-specific: a
    permuted atom numbering contracts to the same autodiff gradient."""
    sysm, _ = _setup(12)
    perm = np.asarray([3, 7, 0, 9, 4, 11, 1, 8, 5, 10, 2, 6])
    relabel = lambda a: jnp.asarray(perm[np.asarray(a)], jnp.int32)
    shuffled = dataclasses.replace(
        sysm, bonds=relabel(sysm.bonds), angles=relabel(sysm.angles),
        dihedrals=relabel(sysm.dihedrals),
        phi_quad=tuple(int(perm[i]) for i in sysm.phi_quad),
        psi_quad=tuple(int(perm[i]) for i in sysm.psi_quad))
    top = chain_ref.chain_topology(shuffled)
    slots = chain_ref.bonded_slots(top)
    pos = MDEngine(system=shuffled).init_state(jax.random.key(3), 3)["pos"]
    f, _ = chain_ref.bonded_forces_sparse(pos, top, slots)
    g = jax.grad(lambda p: jnp.sum(E.batched_bonded_energy(p, shuffled)))(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=2e-3 * _force_scale(g))


def test_ops_sparse_dispatch():
    """``chain_ops.bonded_forces(sparse=True)`` routes the jnp path
    through the slot contraction (pack carries the tables) and agrees
    with the dense dispatch."""
    sysm, pos = _setup()
    pack = chain_ops.build_pack(sysm)
    c, k = _umbrella(pos.shape[0], 2)
    f_d, e_d = chain_ops.bonded_forces(pos, pack, c, k, use_kernel=False)
    f_s, e_s = chain_ops.bonded_forces(pos, pack, c, k, use_kernel=False,
                                       sparse=True)
    np.testing.assert_allclose(np.asarray(f_s), np.asarray(f_d),
                               atol=1e-5 * _force_scale(f_d))
    np.testing.assert_array_equal(np.asarray(e_s), np.asarray(e_d))


def test_lj_fluid_analytic_forces_match_autodiff():
    """LJEngine's direct analytic force (the batched propagate path)
    == -grad of the minimum-image LJ energy oracle."""
    pos = jax.random.uniform(jax.random.key(9), (3, 27, 3)) * 10.0
    sigma, eps, box = 3.4, 0.238, 12.0
    f = nb_ref.lj_forces(pos, sigma, eps, box)
    g = jax.grad(lambda p: jnp.sum(nb_ref.lj_energy(p, sigma, eps, box)))(pos)
    np.testing.assert_allclose(np.asarray(f), np.asarray(-g),
                               atol=1e-4 * _force_scale(g))


def test_engine_pallas_kernel_propagate_matches_analytic():
    """MDEngine(force_path="pallas") with kernels forced on (interpret)
    propagates within tolerance of the analytic jnp path."""
    from repro.config import RepExConfig
    from repro.core import build_grid, ctrl_for_assignment
    grid = build_grid(RepExConfig(
        dimensions=(("temperature", 2), ("umbrella", 2))))
    n = grid.n_ctrl
    ctrl = ctrl_for_assignment(grid, jnp.arange(n))
    rngs = jax.random.split(jax.random.key(5), n)
    n_steps = jnp.full(n, 2, jnp.int32)
    eng_j = MDEngine(force_path="pallas", use_force_kernels=False)
    eng_k = MDEngine(force_path="pallas", use_force_kernels=True)
    state = eng_j.init_state(jax.random.key(0), n)
    out_j = eng_j.propagate(state, ctrl, n_steps, rngs, max_steps=2)
    out_k = eng_k.propagate(state, ctrl, n_steps, rngs, max_steps=2)
    for leaf in ("pos", "vel"):
        np.testing.assert_allclose(np.asarray(out_k[leaf]),
                                   np.asarray(out_j[leaf]),
                                   rtol=2e-4, atol=2e-4)


def test_engine_fused_kernel_propagate_matches_analytic():
    """MDEngine(force_path="fused") with kernels forced on runs the
    fused-propagate kernel (interpret), whose nonbonded sweep is the
    shared pair body on one full tile; it propagates within tolerance
    of the fused jnp path."""
    from repro.config import RepExConfig
    from repro.core import build_grid, ctrl_for_assignment
    grid = build_grid(RepExConfig(
        dimensions=(("temperature", 2), ("salt", 2))))
    n = grid.n_ctrl
    ctrl = ctrl_for_assignment(grid, jnp.arange(n))
    rngs = jax.random.split(jax.random.key(6), n)
    n_steps = jnp.full(n, 2, jnp.int32)
    eng_j = MDEngine(force_path="fused", use_force_kernels=False)
    eng_k = MDEngine(force_path="fused", use_force_kernels=True)
    state = eng_j.init_state(jax.random.key(1), n)
    out_j = eng_j.propagate(state, ctrl, n_steps, rngs, max_steps=2)
    out_k = eng_k.propagate(state, ctrl, n_steps, rngs, max_steps=2)
    for leaf in ("pos", "vel"):
        np.testing.assert_allclose(np.asarray(out_k[leaf]),
                                   np.asarray(out_j[leaf]),
                                   rtol=2e-4, atol=2e-4)
