"""Host-side driver for the fused BAOAB kernel path.

Packs the replica stack ONCE (coordinates + per-atom LJ/charge rows,
velocities, masses, exclusion mask, topology pack), then runs
``max_steps + 1`` fused kernel launches inside one ``fori_loop`` —
per-iteration work is exactly: draw the noise block
(``integrators.step_noise``), build the (R, 1, 8) step-scalar rows,
launch.  Unpacking happens once at the end; positions never leave the
packed layout between iterations, which is the point — the per-pass
path pays pack/unpack + two kernel dispatches per force evaluation.

Same iteration count, noise stream and masking as
``integrators.propagate_replica_major_fused`` (the jnp fused body);
the conformance matrix pins exchange decisions across both and the
per-pass paths bitwise.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret, pack_coords
from repro.kernels.chain_forces import ops as chain_ops
from repro.kernels.fused_propagate import kernel as K
from repro.kernels.lj_forces import ops as nb_ops
from repro.kernels.lj_forces import ref as nb_ref
from repro.md import integrators as I


def kernel_supported(nonbonded: str) -> bool:
    """Dispatch rule: the fused KERNEL covers the dense all-pairs
    nonbonded sweep only.  ``nonbonded="sparse"`` runs use the fused
    jnp loop with the per-pass (kernel or jnp) force passes inside it,
    keeping the neighbor-list aux carry and ``nb_pair_planes`` intact —
    the same precedent as the planes (the kernel path gathers pair
    parameters from its packed coordinate rows natively)."""
    return nonbonded == "dense"


def fused_propagate(state, pack, system, ctrl, n_steps, rngs,
                    max_steps: int, dt: float, gamma: float, *,
                    block: int = 128,
                    interpret: Optional[bool] = None):
    """Propagate the replica stack through ``max_steps + 1`` fused
    kernel iterations.  ``pack``: the engine's ``ChainForcePack``;
    ``ctrl`` rows as the engine consumes them.  Returns {"pos", "vel"}.
    """
    interp = default_interpret() if interpret is None else interpret
    pos, vel = state["pos"], state["vel"]
    r, n = pos.shape[0], pos.shape[1]
    c, _, n_pad = nb_ops._pack_nonbonded(pos, system.lj_sigma,
                                         system.lj_eps, system.charges,
                                         block)
    assert n_pad == pack.n_pad, (n_pad, pack.n_pad)
    v = pack_coords(vel, n_pad)
    mask = jnp.zeros((n_pad, n_pad), jnp.float32).at[:n, :n].set(
        system.nb_mask)
    u_c = ctrl.get("umbrella_center")
    u_k = ctrl.get("umbrella_k")
    bias_par = chain_ops._pack_bias(u_c, u_k, r)[:, None]
    salt = ctrl.get("salt")
    salt_col = (jnp.ones((r,), jnp.float32) if salt is None
                else (1.0 - 0.5 * salt).astype(jnp.float32))
    mass_rows = jnp.ones((8, n_pad), jnp.float32).at[0:3, :n].set(
        jnp.broadcast_to(system.masses, (3, n)))
    _, noise_scale = I.baoab_scales(system.masses, ctrl["temperature"],
                                    dt, gamma)
    launch = functools.partial(
        K.fused_baoab_kernel_batched, tb=pack.tb, bias=u_c is not None,
        coulomb=nb_ref.COULOMB, c1=float(jnp.exp(jnp.float32(-gamma * dt))),
        half_kick=0.5 * dt * I.AKMA, half_dt=0.5 * dt, interpret=interp)

    def body(i, carry):
        cc, vv = carry
        noise_i = I.step_noise(rngs, i, (n, 3))
        nz = pack_coords(noise_scale * noise_i, n_pad)
        trail = ((i >= 1) & (i <= n_steps)).astype(jnp.float32)
        lead = ((i < n_steps) & (i < max_steps)).astype(jnp.float32)
        st = (jnp.zeros((r, 8), jnp.float32)
              .at[:, 0].set(trail).at[:, 1].set(lead)
              .at[:, 2].set(salt_col))
        return launch(cc, vv, nz, st[:, None], bias_par, pack.gmat,
                      pack.bond_par, pack.ang_par, pack.quad_par, mask,
                      mass_rows)

    cc, vv = jax.lax.fori_loop(0, max_steps + 1, body, (c, v))
    return {"pos": jnp.swapaxes(cc[:, 0:3, :n], 1, 2).astype(pos.dtype),
            "vel": jnp.swapaxes(vv[:, 0:3, :n], 1, 2).astype(vel.dtype)}
