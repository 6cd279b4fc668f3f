"""The fused BAOAB Pallas kernel: force + integrator update, one launch.

One program per replica (grid ``(R,)``), packed (8, Np) layout shared
with the force kernels.  Each launch performs ONE fused iteration:

    for each term block k:
      g  = C @ P_k                   bonded gather      (MXU)
      s  = bonded_scatter_rows(g)    bonded gradients   (VPU)
      fb += s @ P_k^T                bonded scatter     (MXU)
    nb = nonbonded_pair_rows(C, C^T) LJ + elec sweep    (VPU)
    f  = fb + nb_lj + salt * nb_el
    B-A-O-A-B masked update on coordinate/velocity rows 0..2

The gradient bodies are the SAME functions the standalone kernels run
(``chain_forces.kernel.bonded_scatter_rows``,
``lj_forces.kernel.nonbonded_pair_rows``) — the fusion changes launch
structure, never math.  The nonbonded sweep runs on the full (Np, Np)
tile, which is what lets force + update share one program — and what
bounds the kernel to ``MAX_ATOMS`` atoms.

Per-replica step scalars ride an (R, 1, 8) input ``step_par``:
lane 0 = trail mask (this iteration applies step i-1's trailing half-B),
lane 1 = lead mask (it applies step i's leading half-B + A-O-A),
lane 2 = salt scale.  The pre-SCALED noise block (noise_scale * xi, the
O-step increment) streams in packed rows 0..2 — drawing stays outside
so the kernel is RNG-agnostic.  ``mass_rows`` rows 0..2 carry the
masses (padding lanes 1.0, so padded-atom divides stay finite).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chain_forces.kernel import (_DN, _DNT, N_ROLES,
                                               VMEM_CAP_BYTES,
                                               bonded_scatter_rows,
                                               one_hot_dot)
from repro.kernels.lj_forces.kernel import (atom_columns, atom_rows,
                                           nonbonded_pair_rows, pair_rows)

# Largest system the kernel holds in VMEM: it keeps the whole one-hot
# matrix, the (Np, Np) exclusion mask and the (Np, Np) pair tile of one
# replica at once (refused for VMEM at 1,536 atoms on v5e; pinned by
# tests/test_tpu_compile.py).
MAX_ATOMS = 1280


def _fused_baoab_kernel(c_ref, v_ref, nz_ref, st_ref, bias_ref, p_ref,
                        bnd_ref, ang_ref, qud_ref, m_ref, mass_ref,
                        nc_ref, nv_ref, *, tb, n_k, bias, coulomb,
                        c1, half_kick, half_dt):
    c = c_ref[0]                                   # (8, Np) coords+params
    v = v_ref[0]                                   # (8, Np) velocities

    # -- force: bonded, term block by term block (two MXU matmuls
    #    around the VPU gradient body each) ---------------------------
    fb = None
    for k in range(n_k):
        p = p_ref[:, k * N_ROLES * tb:(k + 1) * N_ROLES * tb]
        g = one_hot_dot(c, p, _DN)
        s, _e = bonded_scatter_rows(
            g, bnd_ref[:, k * tb:(k + 1) * tb],
            ang_ref[:, k * tb:(k + 1) * tb], qud_ref[:, k * tb:(k + 1) * tb],
            bias_ref[0], tb=tb, rb=1, bias=bias)
        fk = one_hot_dot(s, p, _DNT)
        fb = fk if fb is None else fb + fk

    # -- force: nonbonded, full (Np, Np) tile ---------------------------
    nb, _ = nonbonded_pair_rows(atom_rows(c), atom_columns(c), m_ref[...],
                                coulomb=coulomb, energies=False)
    rows = pair_rows(nb)

    st = st_ref[0]                                 # (1, 8) step scalars
    trail, lead, salt = st[0, 0], st[0, 1], st[0, 2]
    f = fb[0:3] + rows[0:3] + salt * rows[3:6]     # (3, Np)

    # -- masked force-sharing B-A-O-A-B on rows 0..2 --------------------
    kick = half_kick * f / mass_ref[0:3, :]
    pos, vel = c[0:3], v[0:3]
    vel = jnp.where(trail > 0.5, vel + kick, vel)  # trailing B of i-1
    nvel = vel + kick                              # leading B of step i
    npos = pos + half_dt * nvel                    # A
    nvel = c1 * nvel + nz_ref[0, 0:3]              # O (pre-scaled noise)
    npos = npos + half_dt * nvel                   # A
    alive = lead > 0.5
    nc_ref[...] = jnp.concatenate(
        [jnp.where(alive, npos, pos), c[3:8]], axis=0)[None]
    nv_ref[...] = jnp.concatenate(
        [jnp.where(alive, nvel, vel), v[3:8]], axis=0)[None]


def fused_baoab_kernel_batched(coords, vels, noise, step_par, bias_par,
                               gmat, bond_par, ang_par, quad_par, nb_mask,
                               mass_rows, *, tb: int, bias: bool,
                               coulomb: float, c1: float,
                               half_kick: float, half_dt: float,
                               interpret: bool = False):
    """One fused BAOAB iteration over the replica stack, one launch.

    coords/vels/noise (R, 8, Np) packed; step_par/bias_par (R, 1, 8);
    gmat (Np, K·9·TB); bond/ang/quad (8, K·TB); nb_mask (Np, Np);
    mass_rows (8, Np).  Returns (new coords, new vels), both (R, 8, Np)
    with rows 3..7 passed through unchanged.
    """
    r, _, n_pad = coords.shape
    tp = gmat.shape[1]
    width = bond_par.shape[1]
    kern = functools.partial(_fused_baoab_kernel, tb=tb,
                             n_k=tp // (N_ROLES * tb), bias=bias,
                             coulomb=coulomb, c1=c1, half_kick=half_kick,
                             half_dt=half_dt)
    return pl.pallas_call(
        kern,
        grid=(r,),
        in_specs=[
            pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
            pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
            pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
            pl.BlockSpec((1, 1, 8), lambda q: (q, 0, 0)),
            pl.BlockSpec((1, 1, 8), lambda q: (q, 0, 0)),
            pl.BlockSpec((n_pad, tp), lambda q: (0, 0)),
            pl.BlockSpec((8, width), lambda q: (0, 0)),
            pl.BlockSpec((8, width), lambda q: (0, 0)),
            pl.BlockSpec((8, width), lambda q: (0, 0)),
            pl.BlockSpec((n_pad, n_pad), lambda q: (0, 0)),
            pl.BlockSpec((8, n_pad), lambda q: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
            pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, 8, n_pad), jnp.float32),
            jax.ShapeDtypeStruct((r, 8, n_pad), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_CAP_BYTES),
        name="fused_propagate",
        interpret=interpret,
    )(coords, vels, noise, step_par, bias_par, gmat, bond_par, ang_par,
      quad_par, nb_mask, mass_rows)
