"""jit'd wrappers: pack (N,3) positions into the (8, N') kernel layout,
pad to lane multiples, dispatch to the Pallas kernels (interpret on CPU),
and expose energy with an analytic custom_vjp whose backward IS the forces
kernel — the gradient of the MD hot loop never falls back to autodiff
through the kernel.

``lj_energy_batched`` / ``lj_forces_batched`` are the replica-major
variants: (R, N, 3) stacks packed to (R, 8, N') and dispatched through
the replica-grid kernels, energy again carrying a custom_vjp whose
backward is the batched forces kernel.

``nonbonded`` is the chain-molecule pass (per-atom LJ params, charges,
exclusion mask): LJ + electrostatic forces AND both energy accumulators
from one sweep, dispatching between the jnp analytic oracle (default
off-TPU — it is the fast CPU path) and the Pallas kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import (default_interpret, default_use_kernel,
                           pack_coords, pad_to_block)
from repro.kernels.lj_forces import kernel as K
from repro.kernels.lj_forces import ref


def _pack(pos, block: int):
    n = pos.shape[0]
    c = pack_coords(pos[None], pad_to_block(n, block))[0]
    return c, n


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lj_energy(pos, sigma: float, eps: float, box: float, block: int = 128,
              interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    c, n = _pack(pos, block)
    return K.lj_energy_kernel_batched(c[None], sigma=sigma, eps=eps,
                                      box=box, block=block,
                                      interpret=interp)[0]


def _fwd(pos, sigma, eps, box, block, interpret):
    return lj_energy(pos, sigma, eps, box, block, interpret), pos


def _bwd(sigma, eps, box, block, interpret, pos, g):
    f = lj_forces(pos, sigma, eps, box, block, interpret)
    return (-g * f,)    # dU/dx = -F


lj_energy.defvjp(_fwd, _bwd)


def lj_forces(pos, sigma: float, eps: float, box: float, block: int = 128,
              interpret: Optional[bool] = None):
    interp = default_interpret() if interpret is None else interpret
    c, n = _pack(pos, block)
    out = K.lj_forces_kernel_batched(c[None], sigma=sigma, eps=eps, box=box,
                                     block=block, interpret=interp)[0]
    return out[0:3, :n].T


# -- replica-batched wrappers (leading replica axis, one kernel launch) ----


def _pack_batched(pos, block: int):
    n = pos.shape[1]
    return pack_coords(pos, pad_to_block(n, block)), n


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def lj_energy_batched(pos, sigma: float, eps: float, box: float,
                      block: int = 128, interpret: Optional[bool] = None):
    """(R, N, 3) -> (R,) energies through the replica-grid kernel."""
    interp = default_interpret() if interpret is None else interpret
    c, n = _pack_batched(pos, block)
    return K.lj_energy_kernel_batched(c, sigma=sigma, eps=eps, box=box,
                                      block=block, interpret=interp)


def _fwd_batched(pos, sigma, eps, box, block, interpret):
    return lj_energy_batched(pos, sigma, eps, box, block, interpret), pos


def _bwd_batched(sigma, eps, box, block, interpret, pos, g):
    f = lj_forces_batched(pos, sigma, eps, box, block, interpret)
    return (-g[:, None, None] * f,)    # dU/dx = -F, per replica


lj_energy_batched.defvjp(_fwd_batched, _bwd_batched)


def lj_forces_batched(pos, sigma: float, eps: float, box: float,
                      block: int = 128, interpret: Optional[bool] = None):
    """(R, N, 3) -> (R, N, 3) forces through the replica-grid kernel."""
    interp = default_interpret() if interpret is None else interpret
    c, n = _pack_batched(pos, block)
    out = K.lj_forces_kernel_batched(c, sigma=sigma, eps=eps, box=box,
                                     block=block, interpret=interp)
    return jnp.swapaxes(out[:, 0:3, :n], 1, 2)


# -- chain nonbonded (per-atom params + exclusion mask, LJ + elec) ---------


def _pack_nonbonded(pos, lj_sigma, lj_eps, charges, block: int):
    n = pos.shape[1]
    n_pad = pad_to_block(n, block)
    c = pack_coords(pos, n_pad)
    c = c.at[:, 4, :n].set(lj_sigma)
    c = c.at[:, 5, :n].set(jnp.sqrt(lj_eps))
    c = c.at[:, 6, :n].set(charges)
    return c, n, n_pad


def _nonbonded_launch(pos, lj_sigma, lj_eps, charges, nb_mask, block: int,
                      interpret: Optional[bool], energies: bool):
    """Pack, launch the chain nonbonded kernel once, unpack: (f_lj,
    f_el (R, N, 3)) plus (e_lj, e_el (R,)) when ``energies``."""
    interp = default_interpret() if interpret is None else interpret
    c, n, n_pad = _pack_nonbonded(pos, lj_sigma, lj_eps, charges, block)
    mask = jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(nb_mask)
    out = K.nonbonded_kernel_batched(c, mask, coulomb=ref.COULOMB,
                                     energies=energies, interpret=interp)
    f = out[0] if energies else out
    f_lj = jnp.swapaxes(f[:, 0:3, :n], 1, 2).astype(pos.dtype)
    f_el = jnp.swapaxes(f[:, 3:6, :n], 1, 2).astype(pos.dtype)
    if not energies:
        return f_lj, f_el
    return f_lj, f_el, out[1][:, 0, 0], out[2][:, 0, 0]


def nonbonded_batched(pos, lj_sigma, lj_eps, charges, nb_mask,
                      block: int = 128, interpret: Optional[bool] = None):
    """(R, N, 3) stack through the chain nonbonded kernel: one launch ->
    (f_lj (R, N, 3), f_el (R, N, 3), e_lj (R,), e_el (R,))."""
    return _nonbonded_launch(pos, lj_sigma, lj_eps, charges, nb_mask,
                             block, interpret, energies=True)


def nonbonded(pos, lj_sigma, lj_eps, charges, nb_mask,
              use_kernel: Optional[bool] = None, block: int = 128,
              interpret: Optional[bool] = None):
    """Dispatching entry point for the chain nonbonded pass: the jnp
    analytic oracle by default (the fast CPU path — interpret mode is a
    correctness harness), the Pallas kernel on TPU / on request."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        return ref.nonbonded(pos, lj_sigma, lj_eps, charges, nb_mask)
    return nonbonded_batched(pos, lj_sigma, lj_eps, charges, nb_mask,
                             block=block, interpret=interpret)


# -- sparse (neighbor-list) chain nonbonded --------------------------------


def _pack_sparse(pos, lj_sigma, lj_eps, charges, idx, valid, block: int):
    """Pack positions + per-atom params and transpose the (R, N, K)
    neighbor tables to the kernel's slot-major (R, Kp, Np) layout
    (K padded to the f32 sublane multiple, N to the lane block)."""
    c, n, n_pad = _pack_nonbonded(pos, lj_sigma, lj_eps, charges, block)
    r, _, k = idx.shape
    k_pad = ((k + 7) // 8) * 8
    idx_t = jnp.full((r, k_pad, n_pad), n_pad, jnp.int32)
    idx_t = idx_t.at[:, :k, :n].set(jnp.swapaxes(idx, 1, 2))
    val_t = jnp.zeros((r, k_pad, n_pad), jnp.float32)
    val_t = val_t.at[:, :k, :n].set(jnp.swapaxes(valid, 1, 2))
    return c, idx_t, val_t, n


def nonbonded_sparse_batched(pos, lj_sigma, lj_eps, charges, idx, valid,
                             cutoff: float, block: int = 128,
                             interpret: Optional[bool] = None):
    """(R, N, 3) stack through the sparse neighbor-list kernel: one
    launch -> (f_lj, f_el, e_lj (R,), e_el (R,))."""
    interp = default_interpret() if interpret is None else interpret
    c, idx_t, val_t, n = _pack_sparse(pos, lj_sigma, lj_eps, charges,
                                      idx, valid, block)
    out, e_lj, e_el = K.nonbonded_sparse_kernel_batched(
        c, idx_t, val_t, coulomb=ref.COULOMB, cutoff=cutoff,
        interpret=interp)
    f_lj = jnp.swapaxes(out[:, 0:3, :n], 1, 2).astype(pos.dtype)
    f_el = jnp.swapaxes(out[:, 3:6, :n], 1, 2).astype(pos.dtype)
    return f_lj, f_el, e_lj[:, 0, 0], e_el[:, 0, 0]


def nonbonded_sparse(pos, lj_sigma, lj_eps, charges, idx, valid,
                     cutoff: float, use_kernel: Optional[bool] = None,
                     block: int = 128, interpret: Optional[bool] = None,
                     pair=None):
    """Dispatching entry point for the sparse nonbonded pass (mirror of
    :func:`nonbonded`): jnp oracle off-TPU, Pallas kernel on TPU.

    ``pair`` (optional (..., 3, N, K) build-time parameter planes) is a
    jnp-path feature: the kernel gathers params from its packed (8, N)
    rows natively (slot-major planes would triple its VMEM inputs), so
    the kernel path ignores it — numerics are pinned identical anyway."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        return ref.nonbonded_sparse(pos, lj_sigma, lj_eps, charges, idx,
                                    valid, cutoff, pair)
    return nonbonded_sparse_batched(pos, lj_sigma, lj_eps, charges, idx,
                                    valid, cutoff, block=block,
                                    interpret=interpret)


def nonbonded_force_sparse(pos, lj_sigma, lj_eps, charges, idx, valid,
                           cutoff: float, salt_scale=None,
                           use_kernel: Optional[bool] = None,
                           block: int = 128,
                           interpret: Optional[bool] = None,
                           pair=None):
    """Combined (salt-folded) sparse nonbonded force for the propagate
    loop: (R, N, 3) -> (R, N, 3).  ``pair`` as in
    :func:`nonbonded_sparse` (jnp path only)."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        return ref.nonbonded_force_sparse(pos, lj_sigma, lj_eps, charges,
                                          idx, valid, cutoff, salt_scale,
                                          pair)
    f_lj, f_el, _, _ = nonbonded_sparse_batched(
        pos, lj_sigma, lj_eps, charges, idx, valid, cutoff, block=block,
        interpret=interpret)
    if salt_scale is not None:
        f_el = salt_scale[..., None, None] * f_el
    return f_lj + f_el


def nonbonded_force(pos, lj_sigma, lj_eps, charges, nb_mask,
                    salt_scale=None, use_kernel: Optional[bool] = None,
                    block: int = 128, interpret: Optional[bool] = None):
    """Combined (salt-folded) nonbonded force for the propagate loop:
    (R, N, 3) -> (R, N, 3).  The kernel path combines the sweep's split
    outputs; the jnp path folds the scaling into one coefficient pass."""
    if use_kernel is None:
        use_kernel = default_use_kernel()
    if not use_kernel:
        return ref.nonbonded_force(pos, lj_sigma, lj_eps, charges, nb_mask,
                                   salt_scale)
    f_lj, f_el = _nonbonded_launch(pos, lj_sigma, lj_eps, charges, nb_mask,
                                   block, interpret, energies=False)
    if salt_scale is not None:
        f_el = salt_scale[..., None, None] * f_el
    return f_lj + f_el
