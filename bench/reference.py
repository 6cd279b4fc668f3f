"""Plain float32 reference of one synchronous T-REMD cycle on the chain
molecule, written from a configuration file alone.

It imports nothing of the program under test and takes nothing it made:
the topology, the force field, the BAOAB Langevin integrator and the
neighbor (DEO) exchange are rebuilt here from the configuration's
``system`` and ``integrator`` sections and the ladder's rungs.  Only the random
streams follow the seeded run's documented recipe (``jax.random``
threefry keys), because the noise and the Metropolis uniforms are part
of what a seeded run means:

  cycle keys       k_md, k_ex, k_next = split(ensemble_key, 3)
  replica r noise  normal(fold_in(split(k_md, R)[r], t), (N, 3)), step t
  uniforms         uniform(k_ex, (W,)), one per pair slot of the sweep

Forces are ``-grad`` of the energy, by autodiff, one replica at a time.
``dtype`` lowers the force-field arithmetic (the control): displacements
are formed in float32 from float32 state, everything after them runs in
``dtype``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

AKMA = 418.4            # kcal/mol/A/amu -> A/ps^2
KB = 0.0019872041       # kcal/mol/K
COULOMB = 332.0637      # kcal mol^-1 A e^-2


def chain_topology(system: dict) -> dict:
    """Bonds, angles, dihedrals and per-atom parameters of the linear
    chain the configuration describes (host numpy)."""
    n = int(system["n_atoms"])
    i = np.arange(n)
    bonds = np.stack([i[:-1], i[1:]], 1)
    angles = np.stack([i[:-2], i[1:-1], i[2:]], 1)
    quads = np.stack([i[:-3], i[1:-2], i[2:-1], i[3:]], 1)
    dih = system["dihedral"]
    dn = np.full(len(quads), float(dih["n"]))
    dk = np.full(len(quads), float(dih["k"]))
    special = system["phi_psi"]
    for q in special["quads"]:
        row = int(np.flatnonzero((quads == np.asarray(q)).all(1))[0])
        dn[row], dk[row] = float(special["n"]), float(special["k"])
    q = float(system["charge"])
    charges = np.where(i % 2 == 0, q, -q)
    charges = charges - charges.mean()
    f32 = lambda x: np.asarray(x, np.float32)           # noqa: E731
    return {
        "n_atoms": n,
        "bonds": bonds, "bond_r0": float(system["bond"]["r0"]),
        "bond_k": float(system["bond"]["k"]),
        "angles": angles,
        "angle_t0": float(np.deg2rad(system["angle"]["theta0_deg"])),
        "angle_k": float(system["angle"]["k"]),
        "quads": quads, "dih_n": f32(dn), "dih_k": f32(dk),
        "dih_phase": float(dih["phase"]),
        "charges": f32(charges),
        "sigma": float(system["lj_sigma"]), "eps": float(system["lj_eps"]),
        "mass": float(system["mass_amu"]),
        # pairs closer along the chain than this interact only bonded
        "excluded_separation": int(system["excluded_separation"]),
    }


def energy_terms(pos, top: dict, dtype=jnp.float32):
    """The potential energy of one replica (pos (N, 3) float32) in parts,
    float32 (kcal/mol): one entry per bond, angle and dihedral, then one
    per atom, its row of the nonbonded pair sum.  Adding them in float64
    keeps the reference's own rounding far below the program's."""
    def term_vecs(idx_a, idx_b):
        return (pos[idx_b] - pos[idx_a]).astype(dtype)

    b, a, q = top["bonds"], top["angles"], top["quads"]
    d = term_vecs(b[:, 0], b[:, 1])
    r = jnp.sqrt(jnp.sum(d * d, -1))
    e_bond = top["bond_k"] * (r - top["bond_r0"]) ** 2

    v1 = term_vecs(a[:, 1], a[:, 0])
    v2 = term_vecs(a[:, 1], a[:, 2])
    cos = jnp.sum(v1 * v2, -1) / jnp.sqrt(
        jnp.sum(v1 * v1, -1) * jnp.sum(v2 * v2, -1))
    theta = jnp.arccos(jnp.clip(cos, -1 + 1e-6, 1 - 1e-6))
    e_angle = top["angle_k"] * (theta - top["angle_t0"]) ** 2

    b0 = term_vecs(q[:, 0], q[:, 1])
    b1 = term_vecs(q[:, 1], q[:, 2])
    b2 = term_vecs(q[:, 2], q[:, 3])
    n1 = jnp.cross(b0, b1)
    n2 = jnp.cross(b1, b2)
    m1 = jnp.cross(n1, b1 / jnp.sqrt(jnp.sum(b1 * b1, -1, keepdims=True)))
    phi = jnp.arctan2(jnp.sum(m1 * n2, -1), jnp.sum(n1 * n2, -1))
    e_dih = top["dih_k"].astype(dtype) * (
        1 + jnp.cos(top["dih_n"].astype(dtype) * phi - top["dih_phase"]))

    n = top["n_atoms"]
    idx = jnp.arange(n)
    pair = jnp.abs(idx[:, None] - idx[None, :]) >= top["excluded_separation"]
    disp = (pos[:, None, :] - pos[None, :, :]).astype(dtype)
    r2 = jnp.where(pair, jnp.sum(disp * disp, -1), 1.0)
    s6 = (top["sigma"] ** 2 / r2) ** 3
    e_lj = 4.0 * top["eps"] * (s6 * s6 - s6)
    qc = jnp.asarray(top["charges"]).astype(dtype)
    e_el = COULOMB * qc[:, None] * qc[None, :] / jnp.sqrt(r2)
    e_nb = 0.5 * jnp.sum(jnp.where(pair, e_lj + e_el, 0.0), axis=1)
    return jnp.concatenate([e_bond, e_angle, e_dih, e_nb]).astype(
        jnp.float32)


def energy(pos, top: dict, dtype=jnp.float32):
    """Potential energy (kcal/mol) of one replica, pos (N, 3) float32."""
    return jnp.sum(energy_terms(pos, top, dtype))


def forces(pos, top: dict, dtype=jnp.float32):
    return -jax.grad(energy)(pos, top, dtype)


def baoab(pos, vel, temperature, key, n_steps: int, top: dict,
          integrator: dict, dtype=jnp.float32):
    """``n_steps`` BAOAB Langevin steps of one replica; step t draws
    ``normal(fold_in(key, t), (N, 3))``."""
    dt = float(integrator["dt_ps"])
    c1 = np.exp(-float(integrator["gamma_per_ps"]) * dt)
    m = top["mass"]
    sigma = jnp.sqrt(AKMA * KB * temperature / m)
    kick = 0.5 * dt * AKMA / m

    def step(t, carry):
        pos, vel, f = carry
        vel = vel + kick * f
        pos = pos + 0.5 * dt * vel
        noise = jax.random.normal(jax.random.fold_in(key, t), pos.shape)
        vel = c1 * vel + np.sqrt(1 - c1 * c1) * sigma * noise
        pos = pos + 0.5 * dt * vel
        f = forces(pos, top, dtype)
        return pos, vel + kick * f, f

    pos, vel, _ = jax.lax.fori_loop(
        0, n_steps, step, (pos, vel, forces(pos, top, dtype)))
    return pos, vel


def cycle_keys(ensemble_key):
    """(k_md, k_ex, k_next) of one cycle."""
    k_md, k_ex, k_next = jax.random.split(ensemble_key, 3)
    return k_md, k_ex, k_next


def sweep_pairs(n_rungs: int, parity: int):
    """Rung pairs (c, c + 1) of one DEO sweep, and the padded width W
    the uniforms are drawn at (the longer of the two sweeps)."""
    left = np.arange(parity, n_rungs - 1, 2)
    return left, left + 1, n_rungs // 2


def exchange(assignment, energies, temperatures, k_ex, parity: int):
    """One neighbor sweep of Metropolis swaps of rungs between replicas.

    ``assignment[r]`` is the rung replica r holds, ``energies[r]`` its
    potential energy.  Returns (new assignment, accepted per pair,
    margin per pair): the margin is |log u + delta| in kT, how far the
    uniform draw lay from the acceptance boundary."""
    assignment = np.asarray(assignment)
    beta = 1.0 / (KB * np.asarray(temperatures, np.float64))
    left, right, width = sweep_pairs(len(temperatures), parity)
    inv = np.argsort(assignment)
    ri, rj = inv[left], inv[right]
    u_i = np.asarray(energies, np.float64)[ri]
    u_j = np.asarray(energies, np.float64)[rj]
    delta = (beta[right] - beta[left]) * (u_i - u_j)
    uni = np.asarray(jax.random.uniform(k_ex, (width,)),
                     np.float64)[:len(left)]
    accept = uni < np.exp(np.minimum(-delta, 0.0))
    margin = np.abs(np.log(uni) + np.maximum(delta, 0.0))
    new = assignment.copy()
    new[ri] = np.where(accept, right, left)
    new[rj] = np.where(accept, left, right)
    return new, accept, margin
