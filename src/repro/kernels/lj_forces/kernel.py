"""All-pairs LJ energy + forces as Pallas TPU kernels.

Layout: coordinates packed as an (8, N) f32 array — rows 0..2 = x,y,z,
row 3 = validity mask (padding atoms are masked out), rows 4..7 zero.
The 8-row major dim matches the f32 sublane tile; N is padded to the
lane width so (8, BN) blocks are native VMEM tiles.

All kernels are replica-batched: coords are (R, 8, N), one launch
propagates the whole ensemble (the replica-major execution the RepEx
scalability claim needs from its engines), and single-configuration
callers go through the same kernels with R = 1 (the ops layer
adds/strips the replica axis).  The fluid kernels (``lj_energy``,
``lj_forces``) tile the pairs on a grid (R, nI, nJ), replica outermost
and j innermost: the (1, 8, BI) force tile of an (r, i) block stays
resident while j-tiles stream (the flash-attention revisiting pattern).

``nonbonded_kernel_batched`` is the chain-molecule variant: per-atom
LJ parameters and charges, an exclusion-mask input, and LJ + elec
forces (plus, when asked for, both per-replica energies) from ONE
sweep — the single-launch replacement for the MD engine's autodiff
force subgraph.  Its grid is (R, nI): each step owns one lane-dense
128-atom i-block of one replica and sweeps every j atom itself in
sublane slabs (see the comment block above it).
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


# -- chain nonbonded: LJ + electrostatics, one sweep -----------------------
#
# Per-atom sigma / sqrt(eps) / charge ride in coordinate rows 4..6 and
# the exclusion mask (diagonal + 1-2/1-3 + padding) is its own (Np, Np)
# operand.  Pairs are laid out j on sublanes, i on lanes: every sum over
# j is an element-wise vreg add onto 8 sublanes, finished by one 8-to-1
# sublane sum per force row, and the rows come out in the packed lane
# layout — no cross-lane reduction, no column-to-row relayout.  The i
# atoms' quantities are lane rows of the packed block (a sublane
# broadcast away from a pair tile); the j atoms' are lane-broadcast
# planes formed in VMEM once per replica, 128 atoms at a time, each by
# one 128 x 128 transpose of a sublane-broadcast row (a lane broadcast
# per column costs the chip several times more, and rebuilding the
# planes at every i-block nearly doubles the call).  The electrostatic
# force leaves UNscaled (rows 3..5): the salt ctrl applies outside,
# keeping the kernel ctrl-independent.

# The packed rows the pair body reads: x, y, z, sigma, sqrt(eps), charge.
PAIR_ROWS = (0, 1, 2, 4, 5, 6)
# Sublanes of j one pair tile of the dense kernel's sweep holds: a tile's
# pair quantities are then single vregs, kept in registers.
J_SLAB = 8


def atom_rows(c):
    """Packed (8, B) block -> the pair body's six (1, B) lane rows."""
    return tuple(c[k:k + 1] for k in PAIR_ROWS)


def atom_columns(c):
    """Packed (8, B) block -> the pair body's six (B, 1) columns."""
    ct = c.T
    return tuple(ct[:, k:k + 1] for k in PAIR_ROWS)


def atom_planes(c):
    """Packed (8, B) block -> six (B, B) planes, [j, l] = atom j's value
    for every lane l: a sublane broadcast, then one transpose each."""
    b = c.shape[1]
    return tuple(jnp.broadcast_to(c[k:k + 1], (b, b)).T for k in PAIR_ROWS)


def _fold8(x):
    """(BJ, BI) -> (8, BI): the sum over j folded onto 8 sublanes by
    element-wise vreg adds (BJ a multiple of 8)."""
    bj = x.shape[0]
    if bj == 8:
        return x
    return jnp.sum(x.reshape(bj // 8, 8, x.shape[1]), axis=0)


def nonbonded_pair_rows(pi, pj, mask, *, coulomb, energies):
    """The chain nonbonded pair body on one (BJ, BI) tile, j on sublanes.

    pi, pj: the i and j atoms' (x, y, z, sigma, sqrt(eps), charge), each
    broadcastable to (BJ, BI) — i as lane rows (``atom_rows``) or tiles,
    j as columns (``atom_columns``) or planes (``atom_planes``); mask
    (BJ, BI) indexed [j, i] (the exclusion mask is symmetric).  Returns
    ``(forces, energies)``: forces the six sums over j (LJ x, y, z, then
    elec x, y, z), each folded onto 8 sublanes, (8, BI) — ``pair_rows``
    finishes them into packed force rows; energies ``(e_lj, e_el)``
    folded the same way (pair energies, not yet halved), or None when not
    asked for.  Shared by ``_nonbonded_kernel_batched`` (which sweeps j
    in slabs) and the fused-propagate kernel (``kernels.fused_propagate``,
    one full (Np, Np) tile) — ONE pair-math body for both launch
    shapes."""
    xi, yi, zi, sig_i, se_i, q_i = pi
    xj, yj, zj, sig_j, se_j, q_j = pj
    dx = xi - xj
    dy = yi - yj
    dz = zi - zj
    # masked pairs (diagonal, exclusions, padding) never see r2 -> 0; a
    # factor of mask (0 or 1) is exact wherever it enters a product
    r2 = dx * dx + dy * dy + dz * dz + (1.0 - mask)
    inv_r = jax.lax.rsqrt(r2)                     # full precision
    inv_r2 = inv_r * inv_r
    sig = 0.5 * (sig_i + sig_j)
    eps = se_i * se_j * mask                       # rows carry sqrt(eps)
    qq = q_i * q_j * mask
    s2 = sig * sig * inv_r2
    s6 = s2 * s2 * s2
    c_lj = 24.0 * eps * (2.0 * s6 * s6 - s6) * inv_r2
    u_el = coulomb * qq * inv_r
    c_el = u_el * inv_r2
    forces = tuple(_fold8(c * d) for c in (c_lj, c_el)
                   for d in (dx, dy, dz))
    if not energies:
        return forces, None
    return forces, (_fold8(4.0 * eps * (s6 * s6 - s6)), _fold8(u_el))


def pair_rows(forces):
    """``nonbonded_pair_rows``' six folded force sums -> the packed
    (8, BI) force rows: 0..2 LJ, 3..5 elec, 6..7 zero."""
    rows = [jnp.sum(f, axis=0, keepdims=True) for f in forces]
    zero = jnp.zeros_like(rows[0])
    return jnp.concatenate(rows + [zero, zero], axis=0)


def _nonbonded_kernel_batched(c_ref, m_ref, f_ref, *refs, coulomb, bi):
    """One (replica, i-block) grid step: the BI i atoms on lanes against
    every j atom, swept in sublane slabs.  The replica's j planes are
    built into VMEM scratch at its first i-block and read by the rest;
    energies, when asked for, accumulate into outputs resident across
    the whole grid."""
    *e_refs, p_ref = refs
    q = pl.program_id(0)
    i = pl.program_id(1)
    n_j = c_ref.shape[2] // bi

    @pl.when(i == 0)
    def _planes():
        def build(k, carry):
            j0 = pl.multiple_of(k * bi, bi)
            for t, p in enumerate(atom_planes(c_ref[0, :, pl.ds(j0, bi)])):
                p_ref[t, pl.ds(j0, bi), :] = p
            return carry
        jax.lax.fori_loop(0, n_j, build, 0)

    if e_refs:
        @pl.when((q == 0) & (i == 0))
        def _init_e():
            for e_ref in e_refs:
                e_ref[...] = jnp.zeros_like(e_ref)

    ci = c_ref[0, :, pl.ds(pl.multiple_of(i * bi, bi), bi)]     # (8, BI)
    pi = tuple(jnp.broadcast_to(v, (J_SLAB, bi)) for v in atom_rows(ci))

    def sweep(k, acc):
        for s in range(0, bi, J_SLAB):
            j0 = pl.multiple_of(k * bi + s, J_SLAB)
            f, e = nonbonded_pair_rows(
                pi, tuple(p_ref[t, pl.ds(j0, J_SLAB), :]
                          for t in range(len(PAIR_ROWS))),
                m_ref[pl.ds(j0, J_SLAB), :], coulomb=coulomb,
                energies=bool(e_refs))
            acc = tuple(a + b for a, b in zip(acc, f + (e or ())))
        return acc

    acc = jax.lax.fori_loop(
        0, n_j, sweep, tuple(jnp.zeros((8, bi), jnp.float32)
                             for _ in range(8 if e_refs else 6)))
    f_ref[...] = pair_rows(acc[:6])[None]
    for e_ref, e in zip(e_refs, acc[6:]):
        e_ref[pl.ds(q, 1)] += jnp.reshape(0.5 * jnp.sum(e), (1, 1, 1))


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
                             energies: bool = True,
                             interpret: bool = False):
    """coords (R, 8, Np) packed (rows 0..2 xyz, 3 validity, 4 sigma,
    5 sqrt(eps), 6 charge); nb_mask (Np, Np), symmetric.  Returns the
    forces (R, 8, Np) — rows 0..2 LJ, 3..5 elec — from one launch, with
    e_lj (R, 1, 1) and e_el (R, 1, 1) when ``energies`` (static: the
    force-only callers compile no energy accumulators).

    Grid (R, nI): each step owns one 128-atom i-block of one replica and
    sweeps all of j itself.  The replica's (8, Np) block is fetched once
    per replica and its j planes built once, into a (6, Np, BI) VMEM
    scratch (~3 KB per atom); the mask's (Np, BI) slab is fetched per
    step, behind the step's compute."""
    r, _, n_pad = coords.shape
    assert n_pad % J_SLAB == 0, n_pad
    # 128 lanes where the padding allows, else the whole (small) axis
    bi = 128 if n_pad % 128 == 0 else n_pad
    kern = functools.partial(_nonbonded_kernel_batched, coulomb=coulomb,
                             bi=bi)
    f_spec = pl.BlockSpec((1, 8, bi), lambda q, i: (q, 0, i))
    f_shape = jax.ShapeDtypeStruct((r, 8, n_pad), jnp.float32)
    e_spec = pl.BlockSpec((r, 1, 1), lambda q, i: (0, 0, 0))
    e_shape = jax.ShapeDtypeStruct((r, 1, 1), jnp.float32)
    return pl.pallas_call(
        kern,
        grid=(r, n_pad // bi),
        in_specs=[pl.BlockSpec((1, 8, n_pad), lambda q, i: (q, 0, 0)),
                  pl.BlockSpec((n_pad, bi), lambda q, i: (0, i))],
        out_specs=[f_spec, e_spec, e_spec] if energies else f_spec,
        out_shape=[f_shape, e_shape, e_shape] if energies else f_shape,
        scratch_shapes=[pltpu.VMEM((len(PAIR_ROWS), n_pad, bi),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_CAP_BYTES),
        name="lj_nonbonded_dense",
        interpret=interpret,
    )(coords, nb_mask)
