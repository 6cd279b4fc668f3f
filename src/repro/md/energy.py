"""Potential energy terms + the control-decomposed reduced energy.

The decomposition that makes exchange cheap is:

    U(x; ctrl) = U_base(x) + salt(ctrl) * U_elec(x) + U_bias(torsions(x); ctrl)
    u(x; ctrl) = beta(ctrl) * U(x; ctrl)

so the (R x C) cross-energy matrix needed by umbrella/salt exchange is a
*feature outer-product*: per-replica features (U_base, U_elec, phi, psi)
are computed ONCE per exchange (O(R N^2)), and the matrix assembly is a
tiled elementwise kernel (see repro.kernels.exchange_matrix).  This is the
TPU-native answer to the paper's "extra Amber task per replica" for S-REMD
single-point energies.

Two implementations of every term:

  * per-replica scalar functions (``features``, ``bonded_energy``, ...) —
    the reference oracle, composed with ``jax.vmap`` by engines running
    with ``batched=False``;
  * replica-major batched functions (``batched_features``,
    ``batched_bonded_energy``, ...) operating on the full (R, N, 3) stack
    with stacked gathers and one (R, N, N) pairwise pass — the default
    energy/feature hot path (see the "Replica-major batched path"
    section below).

FORCES are no longer derived from this module by default: the propagate
loop's ``force_path="pallas"`` evaluates analytic gradients in
``repro.kernels.chain_forces`` (bonded + umbrella bias) and
``repro.kernels.lj_forces`` (nonbonded), with ``jax.grad`` of the
functions here surviving as the ``force_path="batched"`` tolerance
oracle (tests/test_chain_forces.py pins the analytic forms to these
energies).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import wrap_deg as _wrap_deg
from repro.kernels.lj_forces.ref import COULOMB  # noqa: F401 — canonical
from repro.md.system import MolecularSystem

# every f32 dot runs at full precision: a TPU default pass rounds its
# operands to bf16 (~16 A at x = 4000 A)
_HI = jax.lax.Precision.HIGHEST


def _dihedral_angle(pos, quad) -> jax.Array:
    """Signed dihedral (radians) for one quad of atom indices."""
    p0, p1, p2, p3 = (pos[quad[0]], pos[quad[1]], pos[quad[2]], pos[quad[3]])
    b0, b1, b2 = p1 - p0, p2 - p1, p3 - p2
    n1 = jnp.cross(b0, b1)
    n2 = jnp.cross(b1, b2)
    m1 = jnp.cross(n1, b1 / (jnp.linalg.norm(b1) + 1e-9))
    x = jnp.dot(n1, n2, precision=_HI)
    y = jnp.dot(m1, n2, precision=_HI)
    return jnp.arctan2(y, x)


def dihedral_angles(pos, quads) -> jax.Array:
    return jax.vmap(lambda q: _dihedral_angle(pos, q))(quads)


def bonded_energy(pos, sys: MolecularSystem) -> jax.Array:
    ri = pos[sys.bonds[:, 0]]
    rj = pos[sys.bonds[:, 1]]
    r = jnp.linalg.norm(ri - rj + 1e-12, axis=-1)
    e_bond = jnp.sum(sys.bond_k * (r - sys.bond_r0) ** 2)

    a = pos[sys.angles[:, 0]]
    b = pos[sys.angles[:, 1]]
    c = pos[sys.angles[:, 2]]
    v1 = a - b
    v2 = c - b
    cos = jnp.sum(v1 * v2, -1) / (
        jnp.linalg.norm(v1, axis=-1) * jnp.linalg.norm(v2, axis=-1) + 1e-9)
    theta = jnp.arccos(jnp.clip(cos, -1 + 1e-6, 1 - 1e-6))
    e_angle = jnp.sum(sys.angle_k * (theta - sys.angle_t0) ** 2)

    phi = dihedral_angles(pos, sys.dihedrals)
    e_dih = jnp.sum(sys.dihedral_k
                    * (1 + jnp.cos(sys.dihedral_n * phi
                                   - sys.dihedral_phase)))
    return e_bond + e_angle + e_dih


def lj_energy(pos, sys: MolecularSystem) -> jax.Array:
    disp = pos[:, None, :] - pos[None, :, :]
    r2 = jnp.sum(disp * disp, -1) + jnp.eye(sys.n_atoms)
    sig = 0.5 * (sys.lj_sigma[:, None] + sys.lj_sigma[None, :])
    eps = jnp.sqrt(sys.lj_eps[:, None] * sys.lj_eps[None, :])
    s6 = (sig * sig / r2) ** 3
    e = 4.0 * eps * (s6 * s6 - s6) * sys.nb_mask
    return 0.5 * jnp.sum(e)


def elec_energy(pos, sys: MolecularSystem) -> jax.Array:
    """Bare charge-charge term (scaled by the salt control outside)."""
    disp = pos[:, None, :] - pos[None, :, :]
    r = jnp.sqrt(jnp.sum(disp * disp, -1) + jnp.eye(sys.n_atoms))
    qq = sys.charges[:, None] * sys.charges[None, :]
    e = COULOMB * qq / r * sys.nb_mask
    return 0.5 * jnp.sum(e)


def features(pos, sys: MolecularSystem) -> Dict[str, jax.Array]:
    """Per-configuration features sufficient for ANY ctrl's energy."""
    phi = _dihedral_angle(pos, jnp.asarray(sys.phi_quad))
    psi = _dihedral_angle(pos, jnp.asarray(sys.psi_quad))
    return {
        "u_base": bonded_energy(pos, sys) + lj_energy(pos, sys),
        "u_elec": elec_energy(pos, sys),
        "phi": phi,
        "psi": psi,
    }


def bias_energy(phi, psi, ctrl_center, ctrl_k) -> jax.Array:
    """Umbrella restraints on (phi, psi) in DEGREES (paper's units:
    k = 0.02 kcal/mol/deg^2, centers on [0, 360))."""
    angles = jnp.stack([jnp.rad2deg(phi), jnp.rad2deg(psi)])
    n = ctrl_center.shape[-1]
    d = _wrap_deg(angles[:n] - ctrl_center)
    return jnp.sum(ctrl_k * d * d)


def potential_energy(pos, sys: MolecularSystem, ctrl_row: Dict) -> jax.Array:
    """Full potential for one replica under one ctrl row."""
    f = features(pos, sys)
    salt_scale = 1.0 - 0.5 * ctrl_row.get("salt", 0.0)   # Debye-ish screening
    u = f["u_base"] + salt_scale * f["u_elec"]
    u = u + bias_energy(f["phi"], f["psi"],
                        ctrl_row.get("umbrella_center", jnp.zeros(1)),
                        ctrl_row.get("umbrella_k", jnp.zeros(1)))
    return u


def reduced_energy_from_features(f: Dict, ctrl_row: Dict) -> jax.Array:
    salt_scale = 1.0 - 0.5 * ctrl_row.get("salt", 0.0)
    u = f["u_base"] + salt_scale * f["u_elec"]
    u = u + bias_energy(f["phi"], f["psi"],
                        ctrl_row.get("umbrella_center", jnp.zeros(1)),
                        ctrl_row.get("umbrella_k", jnp.zeros(1)))
    return ctrl_row["beta"] * u


# ---------------------------------------------------------------------------
# Replica-major batched path
# ---------------------------------------------------------------------------
#
# Everything below operates on a (R, N, 3) position STACK and returns
# (R,)-shaped energies / features.  Same math as the per-replica functions
# above (which remain the reference oracle, reachable via
# ``MDEngine(batched=False)``), but expressed as a handful of WIDE ops
# instead of a vmap over R scalar-sized programs:
#
#   * one stacked position gather feeds every bonded term class
#     (bonds + angles + torsions + the phi/psi feature quads), followed by
#     one segment reduction per class;
#   * one (R, N, N) pairwise pass produces BOTH the LJ and the
#     electrostatic sums (the vmap path builds the displacement tensor
#     twice).
#
# On CPU/TPU this is the difference between ~100 XLA thunks per BAOAB
# step and ~a dozen — the replica axis becomes the leading axis of a few
# fused kernels, which is what lets the replica count scale without the
# dispatch count scaling with it.


def batched_dihedral_angles(pos, quads) -> jax.Array:
    """Signed dihedrals for a stack: pos (R, N, 3), quads (D, 4) -> (R, D)."""
    p = jnp.take(pos, quads, axis=1)              # (R, D, 4, 3) one gather
    return _torsion_from_gathered(p)


def _torsion_from_gathered(p) -> jax.Array:
    """Dihedral angles from pre-gathered quad positions (..., 4, 3)."""
    b0 = p[..., 1, :] - p[..., 0, :]
    b1 = p[..., 2, :] - p[..., 1, :]
    b2 = p[..., 3, :] - p[..., 2, :]
    n1 = jnp.cross(b0, b1)
    n2 = jnp.cross(b1, b2)
    b1n = b1 / (jnp.linalg.norm(b1, axis=-1, keepdims=True) + 1e-9)
    m1 = jnp.cross(n1, b1n)
    x = jnp.sum(n1 * n2, -1)
    y = jnp.sum(m1 * n2, -1)
    return jnp.arctan2(y, x)


def _batched_bonded_terms(pos, sys: MolecularSystem
                          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Bonded energy + the (phi, psi) feature torsions from ONE gather.

    pos: (R, N, 3).  Returns (e_bonded (R,), phi (R,), psi (R,)).
    The phi/psi quads ride along in the torsion gather so the feature
    pass costs no extra gather/dihedral program.
    """
    quads = jnp.concatenate(
        [sys.dihedrals,
         jnp.asarray([sys.phi_quad, sys.psi_quad], jnp.int32)], axis=0)
    nb, na, nd = sys.bonds.shape[0], sys.angles.shape[0], quads.shape[0]
    idx = jnp.concatenate([sys.bonds.reshape(-1), sys.angles.reshape(-1),
                           quads.reshape(-1)])
    g = jnp.take(pos, idx, axis=1)                # (R, 2B + 3A + 4D', 3)
    r_cnt = pos.shape[0]
    gb = g[:, : 2 * nb].reshape(r_cnt, nb, 2, 3)
    ga = g[:, 2 * nb: 2 * nb + 3 * na].reshape(r_cnt, na, 3, 3)
    gq = g[:, 2 * nb + 3 * na:].reshape(r_cnt, nd, 4, 3)

    r = jnp.linalg.norm(gb[:, :, 0] - gb[:, :, 1] + 1e-12, axis=-1)
    e_bond = jnp.sum(sys.bond_k * (r - sys.bond_r0) ** 2, axis=-1)

    v1 = ga[:, :, 0] - ga[:, :, 1]
    v2 = ga[:, :, 2] - ga[:, :, 1]
    cos = jnp.sum(v1 * v2, -1) / (
        jnp.linalg.norm(v1, axis=-1) * jnp.linalg.norm(v2, axis=-1) + 1e-9)
    theta = jnp.arccos(jnp.clip(cos, -1 + 1e-6, 1 - 1e-6))
    e_angle = jnp.sum(sys.angle_k * (theta - sys.angle_t0) ** 2, axis=-1)

    ang = _torsion_from_gathered(gq)              # (R, D + 2)
    n_dih = sys.dihedrals.shape[0]
    e_dih = jnp.sum(sys.dihedral_k
                    * (1 + jnp.cos(sys.dihedral_n * ang[:, :n_dih]
                                   - sys.dihedral_phase)), axis=-1)
    return e_bond + e_angle + e_dih, ang[:, n_dih], ang[:, n_dih + 1]


def _pair_blocks(pos, lj_sigma, lj_eps):
    """Component-split pairwise blocks: computing r2 as dx^2 + dy^2 +
    dz^2 on (R, N, N) planes (instead of a trailing-axis reduce over a
    rank-4 displacement stack) keeps the whole coefficient pass one
    element-wise XLA fusion — the (R, N, N, 3) tensor is never formed."""
    x, y, z = pos[..., 0], pos[..., 1], pos[..., 2]
    dx = x[..., :, None] - x[..., None, :]
    dy = y[..., :, None] - y[..., None, :]
    dz = z[..., :, None] - z[..., None, :]
    r2 = dx * dx + dy * dy + dz * dz + jnp.eye(pos.shape[1])
    sig = 0.5 * (lj_sigma[:, None] + lj_sigma[None, :])
    eps = jnp.sqrt(lj_eps[:, None] * lj_eps[None, :])
    s6 = (sig * sig / r2) ** 3
    return r2, eps, s6


@jax.custom_vjp
def _pair_energies(pos, lj_sigma, lj_eps, charges, nb_mask):
    """POSITIONS-ONLY differentiation boundary: the analytic backward
    below returns the exact gradient w.r.t. ``pos`` and ZERO cotangents
    for the force-field parameters (sigma/eps/charges/mask) — the MD hot
    loop treats them as constants.  Do not differentiate this helper
    w.r.t. parameters (e.g. for force-field fitting); use the autodiff
    oracle path (``lj_energy``/``elec_energy`` under vmap) instead."""
    r2, eps, s6 = _pair_blocks(pos, lj_sigma, lj_eps)
    e_lj = 0.5 * jnp.sum(4.0 * eps * (s6 * s6 - s6) * nb_mask,
                         axis=(-2, -1))
    qq = charges[:, None] * charges[None, :]
    e_el = 0.5 * jnp.sum(COULOMB * qq / jnp.sqrt(r2) * nb_mask,
                         axis=(-2, -1))
    return e_lj, e_el


def _pair_energies_fwd(pos, lj_sigma, lj_eps, charges, nb_mask):
    args = (pos, lj_sigma, lj_eps, charges, nb_mask)
    return _pair_energies(*args), args


def _pair_energies_bwd(res, g):
    """Analytic pairwise gradient — the MD hot loop's backward pass.

    Autodiff through the (R, N, N) pass re-materializes every
    intermediate as its own kernel; the closed-form gradient (the same
    structure the validated ``lj_forces`` kernel backward uses, plus the
    Coulomb term) is a handful of wide ops:

        d(e_lj)/dx_i = -sum_j 24 eps (2 s6^2 - s6) / r2 * disp_ij
        d(e_el)/dx_i = -sum_j C q_i q_j / r^3 * disp_ij

    The coefficient-times-displacement sum is evaluated as

        sum_j coef_ij (x_i - x_j) = rowsum(coef) * x - coef @ x

    — one (R, N, N) x (R, N, 3) batched GEMM, never materializing the
    (R, N, N, 3) displacement stack (same identity the analytic
    nonbonded force pass in ``kernels/lj_forces`` uses).
    """
    pos, lj_sigma, lj_eps, charges, nb_mask = res
    g_lj, g_el = g
    r2, eps, s6 = _pair_blocks(pos, lj_sigma, lj_eps)
    qq = charges[:, None] * charges[None, :]
    coef = (g_lj[:, None, None] * 24.0 * eps * (2.0 * s6 * s6 - s6) / r2
            + g_el[:, None, None] * COULOMB * qq
            / (r2 * jnp.sqrt(r2))) * nb_mask
    d_pos = -(jnp.sum(coef, axis=-1)[..., None] * pos
              - jnp.einsum("...ij,...jc->...ic", coef, pos,
                           precision=_HI))
    zeros = jax.tree.map(jnp.zeros_like, (lj_sigma, lj_eps, charges,
                                          nb_mask))
    return (d_pos,) + zeros


_pair_energies.defvjp(_pair_energies_fwd, _pair_energies_bwd)


def _batched_pair_terms(pos, sys: MolecularSystem
                        ) -> Tuple[jax.Array, jax.Array]:
    """(LJ, elec) energies from ONE (R, N, N) pairwise pass: each (R,)."""
    return _pair_energies(pos, sys.lj_sigma, sys.lj_eps, sys.charges,
                          sys.nb_mask)


def batched_bonded_energy(pos, sys: MolecularSystem) -> jax.Array:
    """(R, N, 3) -> (R,) bond + angle + torsion energy."""
    e_bonded, _, _ = _batched_bonded_terms(pos, sys)
    return e_bonded


def batched_lj_energy(pos, sys: MolecularSystem) -> jax.Array:
    """(R, N, 3) -> (R,) Lennard-Jones energy."""
    return _batched_pair_terms(pos, sys)[0]


def batched_elec_energy(pos, sys: MolecularSystem) -> jax.Array:
    """(R, N, 3) -> (R,) bare charge-charge term (salt-scaled outside)."""
    return _batched_pair_terms(pos, sys)[1]


def batched_features(pos, sys: MolecularSystem) -> Dict[str, jax.Array]:
    """Per-replica features for the whole stack: each entry (R,)."""
    e_bonded, phi, psi = _batched_bonded_terms(pos, sys)
    e_lj, e_elec = _batched_pair_terms(pos, sys)
    return {
        "u_base": e_bonded + e_lj,
        "u_elec": e_elec,
        "phi": phi,
        "psi": psi,
    }


def sparse_pair_energies(pos, sys: MolecularSystem, idx, valid,
                         cutoff: float, use_kernel: bool = False,
                         pair=None, interpret=None
                         ) -> Tuple[jax.Array, jax.Array]:
    """(LJ, elec) energies from the O(N * K) neighbor-list sweep.

    The sparse analogue of :func:`_batched_pair_terms` — the TRUNCATED
    potential (pairs beyond ``cutoff`` contribute zero), which is the
    potential the sparse propagate path actually simulates, so exchange
    energies and MD forces describe the same physics.  ``pair`` passes
    the optional build-time parameter planes (neighbor-list ``pair``
    leaf) through to the sweep."""
    from repro.kernels.lj_forces import ops as nb_ops
    _, _, e_lj, e_el = nb_ops.nonbonded_sparse(
        pos, sys.lj_sigma, sys.lj_eps, sys.charges, idx, valid, cutoff,
        use_kernel=use_kernel, pair=pair, interpret=interpret)
    return e_lj, e_el


def sparse_features(pos, sys: MolecularSystem, idx, valid, cutoff: float,
                    use_kernel: bool = False, pair=None, interpret=None
                    ) -> Dict[str, jax.Array]:
    """Per-replica features under the neighbor-list truncated potential:
    same keys/shapes as :func:`batched_features`, with the pairwise sums
    evaluated on the (R, N, K) list instead of all (R, N, N) pairs."""
    e_bonded, phi, psi = _batched_bonded_terms(pos, sys)
    e_lj, e_elec = sparse_pair_energies(pos, sys, idx, valid, cutoff,
                                        use_kernel=use_kernel, pair=pair,
                                        interpret=interpret)
    return {
        "u_base": e_bonded + e_lj,
        "u_elec": e_elec,
        "phi": phi,
        "psi": psi,
    }


def batched_bias_energy(phi, psi, ctrl_center, ctrl_k) -> jax.Array:
    """Umbrella restraints for the stack: phi/psi (R,), centers (R, U)."""
    angles = jnp.stack([jnp.rad2deg(phi), jnp.rad2deg(psi)], axis=-1)
    n = ctrl_center.shape[-1]
    d = _wrap_deg(angles[..., :n] - ctrl_center)
    return jnp.sum(ctrl_k * d * d, axis=-1)


def _batched_ctrl_reduction(f: Dict, ctrl: Dict) -> jax.Array:
    n_rep = f["phi"].shape[0]
    salt_scale = 1.0 - 0.5 * ctrl.get("salt", 0.0)
    u = f["u_base"] + salt_scale * f["u_elec"]
    return u + batched_bias_energy(
        f["phi"], f["psi"],
        ctrl.get("umbrella_center", jnp.zeros((n_rep, 1))),
        ctrl.get("umbrella_k", jnp.zeros((n_rep, 1))))


def batched_potential_energy(pos, sys: MolecularSystem, ctrl: Dict
                             ) -> jax.Array:
    """Full potential for the stack: pos (R, N, 3), ctrl rows (R, ...)."""
    return _batched_ctrl_reduction(batched_features(pos, sys), ctrl)


def batched_reduced_energy_from_features(f: Dict, ctrl: Dict) -> jax.Array:
    """u(x; ctrl) for the stack from precomputed (R,) features."""
    return ctrl["beta"] * _batched_ctrl_reduction(f, ctrl)
