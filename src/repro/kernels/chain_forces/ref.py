"""Pure-jnp analytic bonded forces: bonds + angles + torsions + umbrella
bias, with hand-derived gradients — no autodiff graph.

This is both the reference oracle the kernel tests pin against AND the
fast CPU path (`ops.bonded_forces` dispatches here off-TPU; interpret
mode is a correctness harness, not a fast path).  The math mirrors
``repro.md.energy`` term for term — same guard epsilons, same clip
bounds — so the closed-form gradients agree with ``jax.grad`` of the
reference energies to float rounding.

Derivative conventions (verified against autodiff in
tests/test_chain_forces.py):

  bonds      E = k (r - r0)^2,  r = |d|,  d = r_i - r_j + 1e-12
             dE/dr_i = 2 k (r - r0) d / r
  angles     c = v1.v2 / (|v1||v2| + 1e-9), theta = arccos(clip(c))
             dc/dv1 = v2/den - (v1.v2) n2 v1 / (den^2 n1)
             (gradient gated to the interior of the clip interval)
  torsions   phi = atan2(m1.n2, n1.n2) with n1 = b0 x b1, n2 = b1 x b2:
             dphi/db0 = -|b1| n1 / |n1|^2
             dphi/db1 = (b0.b1) n1 / (|b1||n1|^2)
                        + (b2.b1) n2 / (|b1||n2|^2)
             dphi/db2 = -|b1| n2 / |n2|^2
             (per-atom gradients by the chain rule through
             b0 = p1 - p0, b1 = p2 - p1, b2 = p3 - p2)
  bias       E = sum_u k_u wrap(deg(phi_u) - c_u)^2
             dE/dphi_u = 2 k_u wrap(...) * 180/pi

All functions take a replica stack ``pos`` of shape (..., N, 3) and
return forces of the same shape plus (...,)-shaped energies.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import wrap_deg as _wrap_deg

DEG = 180.0 / jnp.pi


class ChainTopology(NamedTuple):
    """Bonded topology + parameters as plain arrays.

    ``quads`` carries the force-field dihedrals with the phi/psi feature
    quads APPENDED (cosine weight ``quad_k`` zero for the appended two)
    so the umbrella bias rides the same gather/gradient pass as the
    torsion terms — bias torque applies to the last two slots.

    ``inc_stack`` is the signed per-edge scatter operator: six (W, N)
    signed incidence matrices — one per gradient-edge role [bond d |
    angle v1 arm | angle v2 arm | quad b0 | quad b1 | quad b2], each row
    holding +1 at the edge's head atom and -1 at its tail, lane-padded
    to the common width ``edge_width`` — stacked into (6, W, N).
    Scatter-add of per-edge gradient vectors onto atoms is then ONE
    batched contraction — XLA-CPU lowers ``.at[].add`` scatters to a
    serial while loop, and a cross-role concatenate feeding a single
    flat GEMM hits XLA-CPU's per-element fused-concatenate emitter;
    the role-batched dot avoids both (and is MXU-native on TPU).
    """
    bonds: jax.Array        # (B, 2) int32
    bond_r0: jax.Array      # (B,)
    bond_k: jax.Array       # (B,)
    angles: jax.Array       # (A, 3) int32
    angle_t0: jax.Array     # (A,)
    angle_k: jax.Array      # (A,)
    quads: jax.Array        # (Q, 4) int32 — dihedrals + [phi_quad, psi_quad]
    quad_n: jax.Array       # (Q,)
    quad_k: jax.Array       # (Q,) — 0 for the two appended feature quads
    quad_phase: jax.Array   # (Q,)
    inc_stack: jax.Array    # (6, W, N) f32 signed edge scatter per role
    edge_width: int         # W = max(B, A, Q)


def chain_topology(system) -> ChainTopology:
    """Build a ChainTopology from any object with MolecularSystem's
    bonded attributes (duck-typed so this package never imports md)."""
    import numpy as np
    quads = np.concatenate(
        [np.asarray(system.dihedrals),
         np.asarray([system.phi_quad, system.psi_quad], np.int32)], axis=0)
    bonds = np.asarray(system.bonds)
    angles = np.asarray(system.angles)
    n = int(system.n_atoms)
    width = max(len(bonds), len(angles), len(quads))

    def inc_mat(edges):
        """Signed incidence rows from (head, tail) pairs, width-padded."""
        m = np.zeros((width, n), np.float32)
        rows = np.arange(len(edges))
        m[rows, [e[0] for e in edges]] += 1.0
        m[rows, [e[1] for e in edges]] -= 1.0
        return m

    inc_stack = jnp.asarray(np.stack([
        inc_mat([(i, j) for i, j in bonds]),               # d = r_i - r_j
        inc_mat([(a, b) for a, b, _ in angles]),           # v1 arm
        inc_mat([(c, b) for _, b, c in angles]),           # v2 arm
        inc_mat([(p1, p0) for p0, p1, _, _ in quads]),     # b0 = p1 - p0
        inc_mat([(p2, p1) for _, p1, p2, _ in quads]),     # b1 = p2 - p1
        inc_mat([(p3, p2) for _, _, p2, p3 in quads]),     # b2 = p3 - p2
    ]))
    zeros2 = jnp.zeros(2, jnp.float32)
    return ChainTopology(
        bonds=jnp.asarray(bonds), bond_r0=system.bond_r0,
        bond_k=system.bond_k,
        angles=jnp.asarray(angles), angle_t0=system.angle_t0,
        angle_k=system.angle_k,
        quads=jnp.asarray(quads, jnp.int32),
        quad_n=jnp.concatenate([system.dihedral_n, zeros2 + 1.0]),
        quad_k=jnp.concatenate([system.dihedral_k, zeros2]),
        quad_phase=jnp.concatenate([system.dihedral_phase, zeros2]),
        inc_stack=inc_stack, edge_width=width,
    )




def _edge_grads(pos, top: ChainTopology,
                umbrella_center: Optional[jax.Array] = None,
                umbrella_k: Optional[jax.Array] = None):
    """Per-EDGE gradient tensors + bonded energy — the O(W) half both
    contraction paths share.

    Returns (edges (..., 6, 3, W), e_bonded (...,)): one lane-padded
    gradient-vector row per role [bond d | angle v1 | angle v2 | quad b0
    | quad b1 | quad b2].  The dense path contracts ``edges`` against
    the signed incidence stack (O(N * W) GEMM); the sparse path gathers
    the slots each atom touches (O(N * S)).

    Layout notes (XLA-CPU measured, each worth >20% on the propagate hot
    path — see ROADMAP §Performance):

      * geometry runs on (..., 3, W) tensors (components as a REAL axis
        right after the gather transpose), so cross products are single
        ``jnp.cross`` ops, vector norms/dots are mid-axis reduces, and —
        crucially — the per-edge gradient tensors come out shaped
        (..., 3, W) NATURALLY, with no per-component stack/concatenate
        feeding the scatter (XLA-CPU's fused-concatenate emitter walks a
        per-element operand branch chain that re-computes producer
        chains — measured ~5x slower than this form).
    """
    nb, na, nq = top.bonds.shape[0], top.angles.shape[0], top.quads.shape[0]
    # role-major index layout: [bond_i | bond_j | ang_a | ang_b | ang_c
    # | quad_0..quad_3] so each role is a static slice of the gather
    idx = jnp.concatenate([top.bonds[:, 0], top.bonds[:, 1],
                           top.angles[:, 0], top.angles[:, 1],
                           top.angles[:, 2],
                           top.quads[:, 0], top.quads[:, 1],
                           top.quads[:, 2], top.quads[:, 3]])
    g = jnp.swapaxes(jnp.take(pos, idx, axis=-2), -1, -2)  # (..., 3, T)

    def seg(off, w):
        return g[..., :, off:off + w]

    def ex(s):                       # (..., W) scalar row -> (..., 1, W)
        return s[..., None, :]

    # bonds: dE/dr_i = 2k(r - r0) d/r
    d = seg(0, nb) - seg(nb, nb) + 1e-12
    r = jnp.sqrt(jnp.sum(d * d, -2))
    e_bond = jnp.sum(top.bond_k * (r - top.bond_r0) ** 2, axis=-1)
    cb = 2.0 * top.bond_k * (r - top.bond_r0) / r

    # angles
    o = 2 * nb
    v1 = seg(o, na) - seg(o + na, na)
    v2 = seg(o + 2 * na, na) - seg(o + na, na)
    n1 = jnp.sqrt(jnp.sum(v1 * v1, -2))
    n2 = jnp.sqrt(jnp.sum(v2 * v2, -2))
    den = n1 * n2 + 1e-9
    dot = jnp.sum(v1 * v2, -2)
    cosv = dot / den
    cc = jnp.clip(cosv, -1 + 1e-6, 1 - 1e-6)
    theta = jnp.arccos(cc)
    e_angle = jnp.sum(top.angle_k * (theta - top.angle_t0) ** 2, axis=-1)
    interior = (cosv > -1 + 1e-6) & (cosv < 1 - 1e-6)
    g_c = (2.0 * top.angle_k * (theta - top.angle_t0)
           * (-1.0 / jnp.sqrt(1.0 - cc * cc)) * interior)
    # the + 1e-12 guards keep degenerate (zero-length, zero-k) terms
    # finite — the padded slots of the kernel layout hit them
    w1 = dot * n2 / (den * den * (n1 + 1e-12))
    w2 = dot * n1 / (den * den * (n2 + 1e-12))
    e_a1 = ex(g_c) * (v2 / ex(den) - ex(w1) * v1)
    e_a2 = ex(g_c) * (v1 / ex(den) - ex(w2) * v2)

    # torsions (+ umbrella bias on the two appended feature quads)
    o = 2 * nb + 3 * na
    p0, p1 = seg(o, nq), seg(o + nq, nq)
    p2, p3 = seg(o + 2 * nq, nq), seg(o + 3 * nq, nq)
    b0, b1, b2 = p1 - p0, p2 - p1, p3 - p2
    n1v = jnp.cross(b0, b1, axis=-2)
    n2v = jnp.cross(b1, b2, axis=-2)
    nb1 = jnp.sqrt(jnp.sum(b1 * b1, -2))
    m1 = jnp.cross(n1v, b1 / ex(nb1 + 1e-9), axis=-2)
    phi = jnp.arctan2(jnp.sum(m1 * n2v, -2), jnp.sum(n1v * n2v, -2))
    e_dih = jnp.sum(top.quad_k
                    * (1.0 + jnp.cos(top.quad_n * phi - top.quad_phase)),
                    axis=-1)
    torque = -top.quad_k * top.quad_n * jnp.sin(top.quad_n * phi
                                                - top.quad_phase)
    if umbrella_center is not None:
        n_u = umbrella_center.shape[-1]                   # U in {1, 2}
        dev = _wrap_deg(phi[..., nq - 2: nq - 2 + n_u] * DEG
                        - umbrella_center)
        tq = 2.0 * umbrella_k * dev * DEG
        torque = torque.at[..., nq - 2: nq - 2 + n_u].add(tq)
    inv1 = 1.0 / (jnp.sum(n1v * n1v, -2) + 1e-12)
    inv2 = 1.0 / (jnp.sum(n2v * n2v, -2) + 1e-12)
    invb = 1.0 / (nb1 + 1e-12)
    c0 = torque * -nb1 * inv1                  # torque-folded db0 = c0 n1
    c2 = torque * -nb1 * inv2                  # torque-folded db2 = c2 n2
    d1a = torque * jnp.sum(b0 * b1, -2) * invb * inv1
    d1b = torque * jnp.sum(b2 * b1, -2) * invb * inv2

    # per-EDGE gradient tensors (..., 3, W), one role-batched contraction
    w = top.edge_width

    def pad_w(a):
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, w - a.shape[-1])])

    edges = jnp.stack([pad_w(ex(cb) * d),
                       pad_w(e_a1), pad_w(e_a2),
                       pad_w(ex(c0) * n1v),
                       pad_w(ex(d1a) * n1v + ex(d1b) * n2v),
                       pad_w(ex(c2) * n2v)], axis=-3)      # (..., 6, 3, W)
    return edges, e_bond + e_angle + e_dih


def bonded_forces(pos, top: ChainTopology,
                  umbrella_center: Optional[jax.Array] = None,
                  umbrella_k: Optional[jax.Array] = None):
    """Analytic bonded + bias force field for a replica stack — the
    DENSE incidence contraction (the oracle; ``MDEngine(bonded="dense")``).

    pos: (..., N, 3); umbrella_center/umbrella_k: (..., U) per-replica
    (U in {1, 2}; None disables the bias and constant-folds it away).
    Returns (force (..., N, 3), e_bonded (...,)) with e_bonded the
    ctrl-independent bond+angle+torsion energy (bias excluded — it is
    not part of the u_base feature).

    The scatter-add onto atoms is ONE role-batched dense contraction
    against ``top.inc_stack`` (``.at[].add`` would lower to a serial
    while loop on CPU; six separate per-role GEMMs pay five extra Eigen
    dispatches).  The contraction is O(N * W) per role — effectively
    quadratic for chains, which is why :func:`bonded_forces_sparse`
    exists for large N.
    """
    edges, e = _edge_grads(pos, top, umbrella_center, umbrella_k)
    out = jax.lax.dot_general(
        edges, top.inc_stack,
        (((edges.ndim - 1,), (1,)), ((edges.ndim - 3,), (0,))),
        precision=jax.lax.Precision.HIGHEST)
    force = -jnp.swapaxes(jnp.sum(out, axis=0), -1, -2)    # (..., N, 3)
    return force, e


class BondedSlots(NamedTuple):
    """Static per-atom gather tables for the sparse bonded contraction.

    The signed incidence stack (6, W, N) is column-sparse: each atom is
    touched by a BOUNDED number of (role, edge) slots — for a linear
    chain at most 2 bonds + 4 angle arms + 6 torsion edges, independent
    of N.  Inverting it host-side gives, per atom, the flattened slot
    index ``role * W + w`` and its sign; the scatter-add then becomes a
    gather + S-axis sum (the neighbor-list ``_slot_force`` pattern):
    O(N * S) instead of the dense contraction's O(N * W) — linear in N
    with no ``.at[].add`` scatter (serial on XLA-CPU) anywhere.
    """
    idx: jax.Array    # (N, S) int32 — flattened (role * W + w) slots
    sign: jax.Array   # (N, S) f32 — +1 head / -1 tail / 0 padding
    n_slots: int      # S = max per-atom incidence count


def bonded_slots(top: ChainTopology) -> BondedSlots:
    """Invert the signed incidence stack into per-atom gather tables
    (host-side, once — engines build this next to the topology)."""
    import numpy as np
    inc = np.asarray(top.inc_stack)                        # (6, W, N)
    n, w = inc.shape[2], inc.shape[1]
    role, edge, atom = np.nonzero(inc)
    order = np.argsort(atom, kind="stable")
    atom, flat = atom[order], (role * w + edge)[order]
    sign = inc[role[order], edge[order], atom]
    first = np.searchsorted(atom, atom, side="left")
    rank = np.arange(len(atom)) - first
    s = max(int(rank.max(initial=0)) + 1, 1) if len(atom) else 1
    idx = np.zeros((n, s), np.int32)
    sgn = np.zeros((n, s), np.float32)
    idx[atom, rank] = flat
    sgn[atom, rank] = sign
    return BondedSlots(idx=jnp.asarray(idx), sign=jnp.asarray(sgn),
                       n_slots=s)


def bonded_forces_sparse(pos, top: ChainTopology, slots: BondedSlots,
                         umbrella_center: Optional[jax.Array] = None,
                         umbrella_k: Optional[jax.Array] = None):
    """Analytic bonded + bias forces via the SPARSE slot-gather
    contraction (``MDEngine(bonded="sparse")``) — same per-edge gradient
    math as :func:`bonded_forces` (shared ``_edge_grads``), but the
    scatter-add onto atoms is a static gather + S-axis sum over the
    per-atom slot tables instead of the (6, W) x (W, N) incidence GEMMs:
    O(N * S) total with S a topology constant, so the whole bonded pass
    is linear in N.

    XLA-CPU lessons respected: no ``.at[].add`` (the accumulation is a
    plain masked sum over a gathered axis), component-split gathers
    (x/y/z planes gathered separately from the flattened (..., 3, 6W)
    edge buffer — one rank-3 gather per component, no rank-4 tensor),
    and the per-term-class gradient geometry is untouched.

    Matches the dense contraction to float reduction-order rounding
    (the slot sum and the GEMM accumulate the same signed terms in
    different orders); pinned in tests/test_chain_forces.py.
    """
    edges, e = _edge_grads(pos, top, umbrella_center, umbrella_k)
    # (..., 6, 3, W) -> (..., 3, 6*W): one materialized flat edge buffer
    # (the gather forces materialization anyway; role-major flat index
    # matches BondedSlots.idx = role * W + w)
    flat = jnp.swapaxes(edges, -3, -2).reshape(
        edges.shape[:-3] + (3, 6 * top.edge_width))
    force = -jnp.stack(
        [jnp.sum(slots.sign * jnp.take(flat[..., c, :], slots.idx,
                                       axis=-1), axis=-1)
         for c in range(3)], axis=-1)                      # (..., N, 3)
    return force, e
