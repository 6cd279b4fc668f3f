"""All-pairs LJ energy + forces as Pallas TPU kernels.

Layout: coordinates packed as an (8, N) f32 array — rows 0..2 = x,y,z,
row 3 = validity mask (padding atoms are masked out), rows 4..7 zero.
The 8-row major dim matches the f32 sublane tile; N is padded to the
lane width so (8, BN) blocks are native VMEM tiles.

All kernels are replica-batched with a leading REPLICA grid dimension:
coords are (R, 8, N) and the grid is (R, nI, nJ) with the replica index
outermost, j innermost — the (1, 8, BI) force tile for an (r, i) block
stays resident while j-tiles stream (same revisiting pattern as flash
attention).  One launch propagates the whole ensemble, the
replica-major execution the RepEx scalability claim needs from its
engines; single-configuration callers go through the same kernels with
R = 1 (the ops layer adds/strips the replica axis).

``nonbonded_kernel_batched`` is the chain-molecule variant: per-atom
LJ parameters and charges, an exclusion-mask input, and LJ + elec
forces plus both per-replica energy accumulators from ONE sweep — the
single-launch replacement for the MD engine's autodiff force subgraph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chain_forces.kernel import (_DN, VMEM_CAP_BYTES,
                                               one_hot_dot)


def _pair_blocks(ci, cj, sigma, box, bi, bj, ii, jj):
    """Returns (r2, s6, mask, disp) for one (BI, BJ) tile."""
    xi, yi, zi, vi = ci[0], ci[1], ci[2], ci[3]
    xj, yj, zj, vj = cj[0], cj[1], cj[2], cj[3]
    dx = xi[:, None] - xj[None, :]
    dy = yi[:, None] - yj[None, :]
    dz = zi[:, None] - zj[None, :]
    if box > 0:
        dx = dx - box * jnp.round(dx / box)
        dy = dy - box * jnp.round(dy / box)
        dz = dz - box * jnp.round(dz / box)
    gi = ii * bi + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
    gj = jj * bj + jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    same = gi == gj
    mask = (vi[:, None] * vj[None, :]) * (1.0 - same.astype(jnp.float32))
    # guard excluded pairs (diagonal, padding atoms at the origin) so the
    # r^-12 term never sees r2 == 0: masked pairs contribute exactly 0.
    r2 = dx * dx + dy * dy + dz * dz + (1.0 - mask)
    s6 = (sigma * sigma / r2) ** 3
    return r2, s6, mask, (dx, dy, dz)


# -- replica-batched kernels (leading replica grid dimension) --------------
# (single-configuration callers index replica 0 of an R = 1 launch; the
# former thin wrappers are gone so every call site shares one kernel body)


def _energy_kernel_batched(ci_ref, cj_ref, o_ref, *, sigma, eps, box,
                           bi, bj):
    ii = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when((ii == 0) & (jj == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r2, s6, mask, _ = _pair_blocks(ci_ref[0], cj_ref[0], sigma, box,
                                   bi, bj, ii, jj)
    e = 4.0 * eps * (s6 * s6 - s6) * mask
    o_ref[0, 0, 0] += 0.5 * jnp.sum(e)


def _forces_kernel_batched(ci_ref, cj_ref, o_ref, *, sigma, eps, box,
                           bi, bj):
    ii = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    r2, s6, mask, (dx, dy, dz) = _pair_blocks(ci_ref[0], cj_ref[0], sigma,
                                              box, bi, bj, ii, jj)
    coef = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * mask
    fx = jnp.sum(coef * dx, axis=1)
    fy = jnp.sum(coef * dy, axis=1)
    fz = jnp.sum(coef * dz, axis=1)
    zero = jnp.zeros_like(fx)
    o_ref[...] += jnp.stack([fx, fy, fz, zero, zero, zero, zero,
                             zero])[None]


def lj_energy_kernel_batched(coords, *, sigma: float, eps: float,
                             box: float, block: int = 128,
                             interpret: bool = False) -> jax.Array:
    """coords: (R, 8, N) packed; returns (R,) energies, one launch."""
    r, _, n = coords.shape
    block = min(block, n)
    assert n % block == 0
    nb = n // block
    kern = functools.partial(_energy_kernel_batched, sigma=sigma, eps=eps,
                             box=box, bi=block, bj=block)
    out = pl.pallas_call(
        kern,
        grid=(r, nb, nb),
        in_specs=[pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, i)),
                  pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, j))],
        out_specs=pl.BlockSpec((1, 1, 1), lambda q, i, j: (q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((r, 1, 1), jnp.float32),
        name="lj_energy",
        interpret=interpret,
    )(coords, coords)
    return out[:, 0, 0]


def lj_forces_kernel_batched(coords, *, sigma: float, eps: float,
                             box: float, block: int = 128,
                             interpret: bool = False) -> jax.Array:
    """coords: (R, 8, N) packed; returns (R, 8, N), rows 0..2 = forces."""
    r, _, n = coords.shape
    block = min(block, n)
    assert n % block == 0
    nb = n // block
    kern = functools.partial(_forces_kernel_batched, sigma=sigma, eps=eps,
                             box=box, bi=block, bj=block)
    return pl.pallas_call(
        kern,
        grid=(r, nb, nb),
        in_specs=[pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, i)),
                  pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, j))],
        out_specs=pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, i)),
        out_shape=jax.ShapeDtypeStruct((r, 8, n), jnp.float32),
        name="lj_forces",
        interpret=interpret,
    )(coords, coords)


# -- chain nonbonded: LJ + electrostatics, forces + energies, one sweep ----
#
# Same tiled revisiting pattern as the fluid kernels, extended for the
# chain engine: per-atom sigma / sqrt(eps) / charge ride in coordinate
# rows 4..6, the exclusion mask (diagonal + 1-2/1-3 + padding) streams as
# its own (BI, BJ) tile, and every (r, i, j) tile emits the LJ force, the
# UNscaled electrostatic force (rows 3..5 — the salt ctrl applies
# outside the kernel, keeping it ctrl-independent) and both per-replica
# energy accumulators.  One launch replaces the separate
# energy-forward + force-backward passes of the autodiff path.


def nonbonded_pair_rows(ci, cj, mask, *, coulomb):
    """The chain nonbonded tile body on packed (8, ·) coordinate blocks:
    one (BI, BJ) sweep -> ((8, BI) force rows [0..2 LJ, 3..5 elec],
    e_lj, e_el).  Shared between ``_nonbonded_kernel_batched`` (tiled
    standalone pass) and the fused-propagate kernel
    (``kernels.fused_propagate``), which runs it on the full (Np, Np)
    tile — ONE pair-math body for both launch shapes."""
    xi, yi, zi = ci[0], ci[1], ci[2]
    xj, yj, zj = cj[0], cj[1], cj[2]
    dx = xi[:, None] - xj[None, :]
    dy = yi[:, None] - yj[None, :]
    dz = zi[:, None] - zj[None, :]
    # masked pairs (diagonal, exclusions, padding) never see r2 -> 0
    r2 = dx * dx + dy * dy + dz * dz + (1.0 - mask)
    sig = 0.5 * (ci[4][:, None] + cj[4][None, :])
    eps = ci[5][:, None] * cj[5][None, :]          # rows carry sqrt(eps)
    qq = ci[6][:, None] * cj[6][None, :]
    s6 = (sig * sig / r2) ** 3
    r = jnp.sqrt(r2)
    e_lj = 0.5 * jnp.sum(4.0 * eps * (s6 * s6 - s6) * mask)
    e_el = 0.5 * jnp.sum(coulomb * qq / r * mask)
    c_lj = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * mask
    c_el = coulomb * qq / (r2 * r) * mask
    zero = jnp.zeros_like(xi)
    rows = jnp.stack(
        [jnp.sum(c_lj * dx, axis=1), jnp.sum(c_lj * dy, axis=1),
         jnp.sum(c_lj * dz, axis=1), jnp.sum(c_el * dx, axis=1),
         jnp.sum(c_el * dy, axis=1), jnp.sum(c_el * dz, axis=1),
         zero, zero])
    return rows, e_lj, e_el


def _nonbonded_kernel_batched(ci_ref, cj_ref, m_ref, f_ref, elj_ref,
                              eel_ref, *, coulomb):
    ii = pl.program_id(1)
    jj = pl.program_id(2)

    @pl.when(jj == 0)
    def _init_f():
        f_ref[...] = jnp.zeros_like(f_ref)

    @pl.when((ii == 0) & (jj == 0))
    def _init_e():
        elj_ref[...] = jnp.zeros_like(elj_ref)
        eel_ref[...] = jnp.zeros_like(eel_ref)

    rows, e_lj, e_el = nonbonded_pair_rows(ci_ref[0], cj_ref[0], m_ref[...],
                                           coulomb=coulomb)
    elj_ref[...] += jnp.reshape(e_lj, (1, 1, 1))
    eel_ref[...] += jnp.reshape(e_el, (1, 1, 1))
    f_ref[...] += rows[None]


# Largest system the sparse kernel holds in VMEM: each program builds
# (Np, Np) iota and one-hot planes for its neighbor-slot gathers, and
# its VMEM need grows with the neighbor capacity too.  At this size it
# compiles for v5e at any capacity up to N; larger systems compile
# only with few slots (6,144 atoms at 128, not at 512).  Pinned by
# tests/test_tpu_compile.py.
SPARSE_MAX_ATOMS = 1024


def _nonbonded_sparse_kernel_batched(c_ref, idx_ref, val_ref, f_ref,
                                     elj_ref, eel_ref, *, coulomb,
                                     cutoff, k_pad):
    """One program per replica: K one-hot gather matmuls + VPU rows.

    Neighbor slot k of every atom is gathered in ONE (8, Np) @ (Np, Np)
    matmul — ``oh[n, i] = (idx[k, i] == n)`` — the same dense-one-hot
    trick the chain_forces kernel uses for its topology gathers (MXU
    work instead of dynamic indexing).  Slot validity and the true
    cutoff mask every contribution, so padded K-rows, padded atoms and
    sentinel indices are all inert.
    """
    c = c_ref[0]                                   # (8, Np)
    n_pad = c.shape[1]
    xi, yi, zi = c[0:1], c[1:2], c[2:3]
    sig_i, se_i, q_i = c[4:5], c[5:6], c[6:7]
    iota = jax.lax.broadcasted_iota(jnp.int32, (n_pad, n_pad), 0)

    def body(k, carry):
        facc, elj, eel = carry
        idx_row = idx_ref[0, pl.ds(k, 1), :]       # (1, Np)
        val_row = val_ref[0, pl.ds(k, 1), :]
        oh = (iota == idx_row).astype(jnp.bfloat16)
        g = one_hot_dot(c, oh, _DN)
        dx, dy, dz = xi - g[0:1], yi - g[1:2], zi - g[2:3]
        r2 = dx * dx + dy * dy + dz * dz
        mask = val_row * (r2 <= cutoff * cutoff).astype(jnp.float32)
        r2 = r2 + (1.0 - mask)
        sig = 0.5 * (sig_i + g[4:5])
        eps = se_i * g[5:6]                        # rows carry sqrt(eps)
        qq = q_i * g[6:7]
        s6 = (sig * sig / r2) ** 3
        r = jnp.sqrt(r2)
        c_lj = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * mask
        c_el = coulomb * qq / (r2 * r) * mask
        elj = elj + 0.5 * jnp.sum(4.0 * eps * (s6 * s6 - s6) * mask)
        eel = eel + 0.5 * jnp.sum(coulomb * qq / r * mask)
        zero = jnp.zeros_like(xi)
        facc = facc + jnp.concatenate(
            [c_lj * dx, c_lj * dy, c_lj * dz,
             c_el * dx, c_el * dy, c_el * dz, zero, zero], axis=0)
        return facc, elj, eel

    facc = jnp.zeros_like(c)
    facc, elj, eel = jax.lax.fori_loop(
        0, k_pad, body, (facc, jnp.zeros(()), jnp.zeros(())))
    f_ref[...] = facc[None]
    elj_ref[...] = jnp.reshape(elj, (1, 1, 1))
    eel_ref[...] = jnp.reshape(eel, (1, 1, 1))


def nonbonded_sparse_kernel_batched(coords, idx, valid, *, coulomb: float,
                                    cutoff: float,
                                    interpret: bool = False):
    """coords (R, 8, Np) packed (rows as ``nonbonded_kernel_batched``);
    idx/valid (R, Kp, Np) SLOT-MAJOR transposed neighbor tables.
    Returns (forces (R, 8, Np): rows 0..2 = LJ, 3..5 = elec;
    e_lj (R, 1, 1); e_el (R, 1, 1)) from one launch."""
    r, _, n_pad = coords.shape
    k_pad = idx.shape[1]
    kern = functools.partial(_nonbonded_sparse_kernel_batched,
                             coulomb=coulomb, cutoff=cutoff, k_pad=k_pad)
    return pl.pallas_call(
        kern,
        grid=(r,),
        in_specs=[pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
                  pl.BlockSpec((1, k_pad, n_pad), lambda q: (q, 0, 0)),
                  pl.BlockSpec((1, k_pad, n_pad), lambda q: (q, 0, 0))],
        out_specs=[pl.BlockSpec((1, 8, n_pad), lambda q: (q, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda q: (q, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda q: (q, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, 8, n_pad), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_CAP_BYTES),
        name="lj_nonbonded_sparse",
        interpret=interpret,
    )(coords, idx, valid)


def nonbonded_kernel_batched(coords, nb_mask, *, coulomb: float,
                             block: int = 128, interpret: bool = False):
    """coords (R, 8, N) packed (rows 0..2 xyz, 3 validity, 4 sigma,
    5 sqrt(eps), 6 charge); nb_mask (N, N).  Returns
    (forces (R, 8, N): rows 0..2 = LJ, 3..5 = elec;
     e_lj (R, 1, 1); e_el (R, 1, 1)) from one launch."""
    r, _, n = coords.shape
    block = min(block, n)
    assert n % block == 0
    nb = n // block
    kern = functools.partial(_nonbonded_kernel_batched, coulomb=coulomb)
    return pl.pallas_call(
        kern,
        grid=(r, nb, nb),
        in_specs=[pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, i)),
                  pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, j)),
                  pl.BlockSpec((block, block), lambda q, i, j: (i, j))],
        out_specs=[pl.BlockSpec((1, 8, block), lambda q, i, j: (q, 0, i)),
                   pl.BlockSpec((1, 1, 1), lambda q, i, j: (q, 0, 0)),
                   pl.BlockSpec((1, 1, 1), lambda q, i, j: (q, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, 8, n), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.float32),
                   jax.ShapeDtypeStruct((r, 1, 1), jnp.float32)],
        name="lj_nonbonded_dense",
        interpret=interpret,
    )(coords, coords, nb_mask)
