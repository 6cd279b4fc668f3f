"""Host-side packing + dispatch for the bonded-force kernel.

``build_pack(system)`` converts a molecular system's bonded topology
into the kernel's dense layout ONCE (one-hot gather matrix, lane-padded
parameter rows) — engines build it at construction time and close over
it, so the hot loop carries only array inputs.

``bonded_forces`` is the MD-facing entry point: the jnp analytic oracle
(`ref.bonded_forces`) by default — on CPU the oracle IS the fast path,
interpret mode is a correctness harness — and the replica-grid Pallas
kernel when ``use_kernel`` is set (or on TPU backends via
``default_use_kernel``).

``sparse=True`` selects the sparse bonded contraction
(`ref.bonded_forces_sparse`): the per-edge gradients are routed to
atoms through precomputed (N, S) slot tables instead of the dense
(6, W, N) incidence GEMM, turning the contraction O(N·W) -> O(N·S)
with S a small topology constant.  The Pallas kernel keeps the dense
one-hot MXU contraction regardless — on the systolic array the dense
matmul is effectively free at these widths and the gather layout is
hostile — so ``sparse`` only redirects the jnp (CPU) path; both paths
are pinned bitwise-equal on exchange decisions in the tests.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import (default_interpret, default_use_kernel,
                           pad_to_block)
from repro.kernels.chain_forces import kernel as K
from repro.kernels.chain_forces import ref


class ChainForcePack(NamedTuple):
    """Kernel-ready bonded topology (static ints + device arrays)."""
    n_atoms: int
    n_pad: int
    tb: int                   # edges per term block (one lane tile)
    n_blocks: int             # K term blocks
    gmat: jax.Array           # (Np, K * 9 * tb) bf16 one-hot gather/scatter
    bond_par: jax.Array       # (8, K * tb): rows 0 = r0, 1 = k
    ang_par: jax.Array        # (8, K * tb): rows 0 = t0, 1 = k
    quad_par: jax.Array       # (8, K * tb): rows 0 = n, 1 = k, 2 = phase,
                              #              3 = is_phi, 4 = is_psi
    top: ref.ChainTopology    # plain-array topology for the jnp path
    slots: ref.BondedSlots    # (N, S) inverted incidence for sparse path


def build_pack(system, lane: int = 128) -> ChainForcePack:
    """Pack a system's bonded topology for the kernel (host-side, once).

    ``system`` is duck-typed (any object with MolecularSystem's bonded
    attributes).  Edge ``e`` of every term class sits in term block
    ``e // lane`` at lane ``e % lane``; within a block the nine roles
    follow each other (``K.N_ROLES``).  Padded slots gather atom columns
    of ZEROS (the one-hot matrix simply has no entry) and carry k = 0
    parameters, so they contribute exactly nothing.
    """
    top = ref.chain_topology(system)
    bonds = np.asarray(top.bonds)
    angles = np.asarray(top.angles)
    quads = np.asarray(top.quads)
    nb, na, nq = len(bonds), len(angles), len(quads)
    n_blocks = -(-max(nb, na, nq) // lane)
    width = n_blocks * lane
    n_pad = pad_to_block(int(system.n_atoms), lane)

    gmat = np.zeros((n_pad, n_blocks * K.N_ROLES * lane), np.float32)
    roles = list(bonds.T) + list(angles.T) + list(quads.T)
    for rho, atoms in enumerate(roles):
        e = np.arange(len(atoms))
        gmat[atoms, (e // lane) * K.N_ROLES * lane + rho * lane
             + e % lane] = 1.0

    def par(rows):
        out = np.zeros((8, width), np.float32)
        for i, row in enumerate(rows):
            out[i, : len(row)] = np.asarray(row)
        return out

    is_phi = np.zeros(nq, np.float32)
    is_psi = np.zeros(nq, np.float32)
    is_phi[nq - 2] = 1.0
    is_psi[nq - 1] = 1.0
    return ChainForcePack(
        n_atoms=int(system.n_atoms), n_pad=n_pad, tb=lane,
        n_blocks=n_blocks, gmat=jnp.asarray(gmat, jnp.bfloat16),
        bond_par=jnp.asarray(par((top.bond_r0, top.bond_k))),
        ang_par=jnp.asarray(par((top.angle_t0, top.angle_k))),
        quad_par=jnp.asarray(par((top.quad_n, top.quad_k, top.quad_phase,
                                  is_phi, is_psi))),
        top=top,
        slots=ref.bonded_slots(top),
    )


def _pack_bias(umbrella_center, umbrella_k, n_replicas: int):
    b = jnp.zeros((n_replicas, 8), jnp.float32)
    if umbrella_center is None:
        return b
    n_u = umbrella_center.shape[-1]
    b = b.at[:, 0:n_u].set(umbrella_center)
    b = b.at[:, 2:2 + n_u].set(umbrella_k)
    return b


def bonded_forces(pos, pack: ChainForcePack,
                  umbrella_center: Optional[jax.Array] = None,
                  umbrella_k: Optional[jax.Array] = None,
                  use_kernel: Optional[bool] = None,
                  interpret: Optional[bool] = None,
                  sparse: bool = False):
    """(R, N, 3) stack -> (forces (R, N, 3), e_bonded (R,)).

    Analytic bonds + angles + torsions + umbrella bias; jnp oracle by
    default, Pallas kernel on TPU / when ``use_kernel`` is set.
    ``sparse`` selects the slot-table contraction on the jnp path
    (linear in N); the kernel path stays dense-MXU either way."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        if sparse:
            return ref.bonded_forces_sparse(pos, pack.top, pack.slots,
                                            umbrella_center, umbrella_k)
        return ref.bonded_forces(pos, pack.top, umbrella_center, umbrella_k)
    interp = default_interpret() if interpret is None else interpret
    r, n = pos.shape[0], pack.n_atoms
    rb = K.replica_block(r)
    # (R, N, 3) -> component-major replica blocks (R/RB, rows, Np)
    blk = jnp.transpose(pos.astype(jnp.float32).reshape(r // rb, rb, n, 3),
                        (0, 3, 1, 2)).reshape(r // rb, 3 * rb, n)
    coords = jnp.zeros((r // rb, K.row_pad(rb), pack.n_pad), jnp.float32)
    coords = coords.at[:, :3 * rb, :n].set(blk)
    bias_par = _pack_bias(umbrella_center, umbrella_k, r)
    out, e = K.chain_forces_kernel_batched(
        coords, pack.gmat, pack.bond_par, pack.ang_par, pack.quad_par,
        bias_par.reshape(r // rb, rb, 8), tb=pack.tb,
        bias=umbrella_center is not None, interpret=interp)
    forces = jnp.transpose(out[:, :n, :3 * rb].reshape(r // rb, n, 3, rb),
                           (0, 3, 1, 2)).reshape(r, n, 3)
    return forces.astype(pos.dtype), e.reshape(r)
