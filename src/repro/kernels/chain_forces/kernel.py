"""Replica-batched bonded-force Pallas kernel.

Bonded topology is a DENSE one-hot gather matrix so both the gather and
the scatter-add are MXU matmuls — TPU-native, no dynamic indexing:

    G = C @ P_k        (3·RB, Np) @ (Np, 9·TB) -> (3·RB, 9·TB)   gather
    F^T += P_k @ S^T   (Np, 9·TB) @ (9·TB, 3·RB) -> (Np, 3·RB)   scatter-add

(the scatter keeps the large one-hot block as the untransposed operand,
so Mosaic transposes only the small gradient block).

The grid is ``(R / RB, K)``: a block of RB replicas by K term blocks.
Term block ``k`` is one TB-wide slice of edges with EVERY role of that
slice side by side — ``[bond_i | bond_j | ang_a | ang_b | ang_c |
quad_0..quad_3]``, each TB lanes — so one (Np, 9·TB) block of ``P``
holds everything one slice of terms needs, and forces and energies
accumulate across the K axis.  VMEM therefore holds ``Np x 9·TB``
of the one-hot matrix, linear in N, never the whole (Np, Tp) matrix.

Coordinates are component-major within a replica block: rows
``[x_0..x_{RB-1} | y_0.. | z_0..]``, so every geometric quantity is an
(RB, TB) array and the whole body is VPU element-wise work between the
two matmuls.  Padded slots carry k = 0 and gather zeros; every
denominator is guarded so their (zero-weighted) geometry stays finite.

Mosaic lowers neither ``acos`` nor ``atan2``: the angle and dihedral are
evaluated with :func:`atan2`, an f32 polynomial on ``sqrt`` and
division.  The one-hot matrix is bf16 and every matmul against it is
exact to f32 (:func:`one_hot_dot`): a gather must not round
coordinates, and a default-precision pass would round them to bf16.

Outputs: transposed forces (R / RB, Np, rows) (columns 0..3·RB-1) and
the bonded energy (R / RB, RB, 1).  The gradient math is the hand-derived set
documented in ``ref.py`` — the kernel and the jnp oracle are the same
formulas in two layouts.

Dense-vs-sparse dispatch contract: this kernel keeps the dense one-hot
MXU contraction even when the engine selects ``bonded="sparse"``; the
sparse O(N·S) contraction (``ref.bonded_forces_sparse``) is the jnp
large-N path, and the tests pin exchange decisions across both.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chain_forces.ref import DEG, _wrap_deg

_DN = (((1,), (0,)), ((), ()))     # contract last dim of lhs w/ first of rhs
_DNT = (((1,), (1,)), ((), ()))    # contract last dims (rhs transposed)
N_ROLES = 9                        # term slots per edge slice, see above

# v5e holds 128 MiB of VMEM per core: the scoped limit every MD kernel
# asks for, below the default 16-32 MiB the one-hot blocks outgrow
VMEM_CAP_BYTES = 100 * 2**20
# Largest system this kernel holds in VMEM: its scoped need is ~8 KB per
# atom (the double-buffered (Np, 9·TB) one-hot block and its temporaries)
# and was refused above ~16K atoms on v5e; tests/test_tpu_compile.py
# compiles it at this size.
MAX_ATOMS = 12000


def one_hot_dot(a, b, dims):
    """f32 x one-hot matmul, exact to f32: the one-hot operand is bf16
    (0 and 1 are exact there) and the f32 operand is split into three
    bf16 parts whose sum is the f32 value, so three single-pass bf16
    matmuls with f32 accumulation reproduce the f32 product.  A gather
    is exact; a scatter-add is an f32 sum.  (Mosaic's own f32 contract
    precision would also split the one-hot block, at ~5x its size in
    VMEM.)"""
    oh_lhs = a.dtype == jnp.bfloat16
    x = b if oh_lhs else a
    out = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        lhs, rhs = (a, part) if oh_lhs else (part, b)
        # explicit DEFAULT: a process-wide "highest" default would ask
        # Mosaic for an f32 contraction of these bf16 operands
        d = jax.lax.dot_general(lhs, rhs, dims,
                                precision=jax.lax.Precision.DEFAULT,
                                preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def atan2(y, x):
    """Element-wise ``arctan2`` from f32 arithmetic that Mosaic lowers:
    one division and the Cephes ``atanf`` polynomial (~2 ulp).  The ratio
    min/max lies in [0, 1]; above tan(pi/8) it is reduced by the pi/4
    identity, and the octant is restored from the signs and the order
    of |x| and |y|.  ``atan2(0, 0) == 0`` as in numpy."""
    ax, ay = jnp.abs(x), jnp.abs(y)
    mx = jnp.maximum(ax, ay)
    t = jnp.minimum(ax, ay) / jnp.where(mx > 0.0, mx, 1.0)
    big = t > 0.41421356237309503
    t = jnp.where(big, (t - 1.0) / (t + 1.0), t)
    z = t * t
    a = ((((8.05374449538e-2 * z - 1.38776856032e-1) * z
           + 1.99777106478e-1) * z - 3.33329491539e-1) * z * t + t)
    a = jnp.where(big, a + math.pi / 4, a)
    a = jnp.where(ay > ax, math.pi / 2 - a, a)
    a = jnp.where(x < 0.0, math.pi - a, a)
    return jnp.where(y < 0.0, -a, a)


def replica_block(n_replicas: int) -> int:
    """Replicas per program: the largest of 32, 16, ..., 1 dividing R."""
    return next(b for b in (32, 16, 8, 4, 2, 1) if n_replicas % b == 0)


def row_pad(rb: int) -> int:
    """Rows of a component-major block: 3·RB padded to the f32 sublane."""
    return -(-3 * rb // 8) * 8


def _xyz(g, seg, tb, rb):
    blk = g[:, seg * tb:(seg + 1) * tb]
    return blk[0:rb], blk[rb:2 * rb], blk[2 * rb:3 * rb]


def _cross(ax, ay, az, bx, by, bz):
    return (ay * bz - az * by, az * bx - ax * bz, ax * by - ay * bx)


def _dot3(ax, ay, az, bx, by, bz):
    return ax * bx + ay * by + az * bz


def _rowsum(a):
    return jnp.sum(a, axis=1, keepdims=True)


def bonded_scatter_rows(g, bnd, ang, qud, bias_par, *, tb, rb, bias):
    """The bonded gradient body on one gathered term block:
    (≥3·RB, 9·TB) component-major coordinates -> ((row_pad(RB), 9·TB)
    scatter rows, (RB, 1) bonded energies).

    Shared between ``_chain_forces_kernel`` (standalone bonded pass) and
    the fused-propagate kernel (``kernels.fused_propagate``, RB = 1), so
    the hand-derived gradient math exists in exactly one kernel-layout
    form.  ``bnd``/``ang``/``qud`` are this slice's (8, TB) parameter
    blocks; ``bias_par`` is the block's (RB, 8) umbrella rows.
    """
    pad = row_pad(rb) - 3 * rb

    def rows3(fx, fy, fz):
        parts = [fx, fy, fz]
        if pad:
            parts.append(jnp.zeros((pad, tb), jnp.float32))
        return jnp.concatenate(parts, axis=0)

    # -- bonds ------------------------------------------------------------
    xi, yi, zi = _xyz(g, 0, tb, rb)
    xj, yj, zj = _xyz(g, 1, tb, rb)
    dx, dy, dz = xi - xj + 1e-12, yi - yj + 1e-12, zi - zj + 1e-12
    r = jnp.sqrt(dx * dx + dy * dy + dz * dz)
    r0, kb = bnd[0:1, :], bnd[1:2, :]
    e_bond = _rowsum(kb * (r - r0) ** 2)
    cb = 2.0 * kb * (r - r0) / r                   # dE/dd coefficient
    s_bi = rows3(-cb * dx, -cb * dy, -cb * dz)     # force = -grad
    s_bj = rows3(cb * dx, cb * dy, cb * dz)

    # -- angles -----------------------------------------------------------
    ax_, ay_, az_ = _xyz(g, 2, tb, rb)
    bx_, by_, bz_ = _xyz(g, 3, tb, rb)
    cx_, cy_, cz_ = _xyz(g, 4, tb, rb)
    v1x, v1y, v1z = ax_ - bx_, ay_ - by_, az_ - bz_
    v2x, v2y, v2z = cx_ - bx_, cy_ - by_, cz_ - bz_
    n1 = jnp.sqrt(_dot3(v1x, v1y, v1z, v1x, v1y, v1z))
    n2 = jnp.sqrt(_dot3(v2x, v2y, v2z, v2x, v2y, v2z))
    den = n1 * n2 + 1e-9
    dot = _dot3(v1x, v1y, v1z, v2x, v2y, v2z)
    cosv = dot / den
    cc = jnp.clip(cosv, -1 + 1e-6, 1 - 1e-6)
    sinv = jnp.sqrt((1.0 - cc) * (1.0 + cc))
    theta = atan2(sinv, cc)                        # arccos(cc)
    t0, ka = ang[0:1, :], ang[1:2, :]
    e_angle = _rowsum(ka * (theta - t0) ** 2)
    interior = ((cosv > -1 + 1e-6) & (cosv < 1 - 1e-6)).astype(cosv.dtype)
    g_c = 2.0 * ka * (theta - t0) * (-1.0 / sinv) * interior
    w1 = dot * n2 / (den * den * (n1 + 1e-12))
    w2 = dot * n1 / (den * den * (n2 + 1e-12))
    gax = g_c * (v2x / den - w1 * v1x)
    gay = g_c * (v2y / den - w1 * v1y)
    gaz = g_c * (v2z / den - w1 * v1z)
    gcx = g_c * (v1x / den - w2 * v2x)
    gcy = g_c * (v1y / den - w2 * v2y)
    gcz = g_c * (v1z / den - w2 * v2z)
    s_aa = rows3(-gax, -gay, -gaz)
    s_ab = rows3(gax + gcx, gay + gcy, gaz + gcz)
    s_ac = rows3(-gcx, -gcy, -gcz)

    # -- torsions + umbrella bias ----------------------------------------
    p0 = _xyz(g, 5, tb, rb)
    p1 = _xyz(g, 6, tb, rb)
    p2 = _xyz(g, 7, tb, rb)
    p3 = _xyz(g, 8, tb, rb)
    b0x, b0y, b0z = p1[0] - p0[0], p1[1] - p0[1], p1[2] - p0[2]
    b1x, b1y, b1z = p2[0] - p1[0], p2[1] - p1[1], p2[2] - p1[2]
    b2x, b2y, b2z = p3[0] - p2[0], p3[1] - p2[1], p3[2] - p2[2]
    n1x, n1y, n1z = _cross(b0x, b0y, b0z, b1x, b1y, b1z)
    n2x, n2y, n2z = _cross(b1x, b1y, b1z, b2x, b2y, b2z)
    nb1 = jnp.sqrt(_dot3(b1x, b1y, b1z, b1x, b1y, b1z))
    ib = 1.0 / (nb1 + 1e-9)
    m1x, m1y, m1z = _cross(n1x, n1y, n1z, b1x * ib, b1y * ib, b1z * ib)
    x = _dot3(n1x, n1y, n1z, n2x, n2y, n2z)
    y = _dot3(m1x, m1y, m1z, n2x, n2y, n2z)
    dihed = atan2(y, x)
    nq, kq = qud[0:1, :], qud[1:2, :]
    ph = qud[2:3, :]
    e_dih = _rowsum(kq * (1.0 + jnp.cos(nq * dihed - ph)))
    torque = -kq * nq * jnp.sin(nq * dihed - ph)
    if bias:
        isphi, ispsi = qud[3:4, :], qud[4:5, :]
        deg = dihed * DEG
        torque += isphi * (2.0 * bias_par[:, 2:3]
                           * _wrap_deg(deg - bias_par[:, 0:1]) * DEG)
        torque += ispsi * (2.0 * bias_par[:, 3:4]
                           * _wrap_deg(deg - bias_par[:, 1:2]) * DEG)
    inv1 = 1.0 / (_dot3(n1x, n1y, n1z, n1x, n1y, n1z) + 1e-12)
    inv2 = 1.0 / (_dot3(n2x, n2y, n2z, n2x, n2y, n2z) + 1e-12)
    invb = 1.0 / (nb1 + 1e-12)
    c0 = -nb1 * inv1                               # db0 = c0 * n1
    c2 = -nb1 * inv2                               # db2 = c2 * n2
    d1a = _dot3(b0x, b0y, b0z, b1x, b1y, b1z) * invb * inv1
    d1b = _dot3(b2x, b2y, b2z, b1x, b1y, b1z) * invb * inv2
    # force on quad atom a = -torque * dphi_a; dphi chain through b0,b1,b2
    tq = -torque
    f0x, f0y, f0z = tq * -c0 * n1x, tq * -c0 * n1y, tq * -c0 * n1z
    t1x = tq * (c0 * n1x - (d1a * n1x + d1b * n2x))
    t1y = tq * (c0 * n1y - (d1a * n1y + d1b * n2y))
    t1z = tq * (c0 * n1z - (d1a * n1z + d1b * n2z))
    t2x = tq * ((d1a * n1x + d1b * n2x) - c2 * n2x)
    t2y = tq * ((d1a * n1y + d1b * n2y) - c2 * n2y)
    t2z = tq * ((d1a * n1z + d1b * n2z) - c2 * n2z)
    f3x, f3y, f3z = tq * c2 * n2x, tq * c2 * n2y, tq * c2 * n2z
    s_q0 = rows3(f0x, f0y, f0z)
    s_q1 = rows3(t1x, t1y, t1z)
    s_q2 = rows3(t2x, t2y, t2z)
    s_q3 = rows3(f3x, f3y, f3z)

    s = jnp.concatenate([s_bi, s_bj, s_aa, s_ab, s_ac,
                         s_q0, s_q1, s_q2, s_q3], axis=1)   # (rows, 9·TB)
    return s, e_bond + e_angle + e_dih


def _chain_forces_kernel(c_ref, p_ref, bnd_ref, ang_ref, qud_ref, bias_ref,
                         f_ref, e_ref, *, tb, rb, bias):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        f_ref[...] = jnp.zeros_like(f_ref)
        e_ref[...] = jnp.zeros_like(e_ref)

    p = p_ref[...]                                 # (Np, 9·TB)
    g = one_hot_dot(c_ref[0], p, _DN)              # (rows, 9·TB)
    s, e = bonded_scatter_rows(g, bnd_ref[...], ang_ref[...], qud_ref[...],
                               bias_ref[0], tb=tb, rb=rb, bias=bias)
    f_ref[...] += one_hot_dot(p, s, _DNT)[None]
    e_ref[...] += e[None]


def chain_forces_kernel_batched(coords, gmat, bond_par, ang_par, quad_par,
                                bias_par, *, tb: int, bias: bool,
                                interpret: bool = False):
    """coords (R/RB, rows, Np) component-major; gmat (Np, K·9·TB)
    one-hot; bond/ang/quad (8, K·TB); bias_par (R/RB, RB, 8).  Returns
    (forces^T (R/RB, Np, rows), e_bonded (R/RB, RB, 1)) from one
    launch."""
    nrb, rows, n_pad = coords.shape
    rb = bias_par.shape[1]
    n_k = gmat.shape[1] // (N_ROLES * tb)
    kern = functools.partial(_chain_forces_kernel, tb=tb, rb=rb, bias=bias)
    return pl.pallas_call(
        kern,
        grid=(nrb, n_k),
        in_specs=[
            pl.BlockSpec((1, rows, n_pad), lambda q, k: (q, 0, 0)),
            pl.BlockSpec((n_pad, N_ROLES * tb), lambda q, k: (0, k)),
            pl.BlockSpec((8, tb), lambda q, k: (0, k)),
            pl.BlockSpec((8, tb), lambda q, k: (0, k)),
            pl.BlockSpec((8, tb), lambda q, k: (0, k)),
            pl.BlockSpec((1, rb, 8), lambda q, k: (q, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, n_pad, rows), lambda q, k: (q, 0, 0)),
            pl.BlockSpec((1, rb, 1), lambda q, k: (q, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nrb, n_pad, rows), jnp.float32),
            jax.ShapeDtypeStruct((nrb, rb, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_CAP_BYTES),
        name="chain_bonded",
        interpret=interpret,
    )(coords, gmat, bond_par, ang_par, quad_par, bias_par)
