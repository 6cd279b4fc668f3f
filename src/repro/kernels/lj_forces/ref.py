"""Pure-jnp oracle: all-pairs Lennard-Jones energy/forces, minimum image —
plus the chain-molecule ``nonbonded`` pass (per-atom LJ parameters,
charges, exclusion mask; LJ AND electrostatic forces with both energy
accumulators from one pairwise sweep).

Batch-agnostic: ``pos`` may be a single configuration (N, 3) or a replica
stack (..., N, 3); energies reduce over the trailing pair axes only, so
the replica-major engines call the SAME oracle the kernel tests use.
The analytic force expressions here are also the fast CPU path of the
``force_path="pallas"`` engines (no autodiff graph; the ops layer
dispatches to the Pallas kernels only on TPU / on request).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

COULOMB = 332.0637   # kcal mol^-1 Angstrom e^-2


def _pair_terms(pos, sigma: float, box: float):
    disp = pos[..., :, None, :] - pos[..., None, :, :]
    disp = disp - box * jnp.round(disp / box)
    n = pos.shape[-2]
    r2 = jnp.sum(disp * disp, -1) + jnp.eye(n)      # guard the diagonal
    s6 = (sigma * sigma / r2) ** 3
    mask = 1.0 - jnp.eye(n)
    return disp, r2, s6, mask


def lj_energy(pos, sigma: float, eps: float, box: float) -> jax.Array:
    """(..., N, 3) -> (...) total LJ energy per configuration."""
    _, _, s6, mask = _pair_terms(pos, sigma, box)
    e = 4.0 * eps * (s6 * s6 - s6) * mask
    return 0.5 * jnp.sum(e, axis=(-2, -1))


def lj_forces(pos, sigma: float, eps: float, box: float) -> jax.Array:
    """F = -dU/dx, analytic: (..., N, 3) -> (..., N, 3)."""
    disp, r2, s6, mask = _pair_terms(pos, sigma, box)
    coef = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * mask
    return jnp.sum(coef[..., None] * disp, axis=-2)


def _coef_force(coef, pos):
    """F_i = sum_j coef_ij (x_i - x_j) WITHOUT materializing the
    (..., N, N, 3) displacement stack:

        F = rowsum(coef) * x - coef @ x

    one (..., N, N) x (..., N, 3) batched GEMM + elementwise — the
    identity that keeps the pairwise force a rank-3 computation."""
    return (jnp.sum(coef, axis=-1)[..., None] * pos
            - jnp.einsum("...ij,...jc->...ic", coef, pos,
                         precision=jax.lax.Precision.HIGHEST))


def _nonbonded_coefs(pos, lj_sigma, lj_eps, charges, nb_mask,
                     cutoff=None):
    # component-split r2 (dx^2 + dy^2 + dz^2 on (..., N, N) planes): a
    # sum over a trailing 3-axis would materialize the rank-4
    # displacement stack and end the fusion at a reduce; this form keeps
    # the whole coefficient pass one element-wise graph.  ``cutoff``
    # folds a radial truncation into the pair mask (the matched-cutoff
    # oracle of the sparse path shares THIS pair math verbatim).
    n = pos.shape[-2]
    x, y, z = pos[..., 0], pos[..., 1], pos[..., 2]
    dx = x[..., :, None] - x[..., None, :]
    dy = y[..., :, None] - y[..., None, :]
    dz = z[..., :, None] - z[..., None, :]
    r2 = dx * dx + dy * dy + dz * dz + jnp.eye(n)   # guard the diagonal
    if cutoff is not None:
        nb_mask = nb_mask * (r2 <= cutoff * cutoff)
    sig = 0.5 * (lj_sigma[:, None] + lj_sigma[None, :])
    eps = jnp.sqrt(lj_eps[:, None] * lj_eps[None, :])
    s6 = (sig * sig / r2) ** 3
    r = jnp.sqrt(r2)
    qq = charges[:, None] * charges[None, :]
    c_lj = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * nb_mask
    c_el = COULOMB * qq / (r2 * r) * nb_mask
    e_lj = 0.5 * jnp.sum(4.0 * eps * (s6 * s6 - s6) * nb_mask,
                         axis=(-2, -1))
    e_el = 0.5 * jnp.sum(COULOMB * qq / r * nb_mask, axis=(-2, -1))
    return c_lj, c_el, e_lj, e_el


def nonbonded(pos, lj_sigma, lj_eps, charges, nb_mask):
    """Chain-molecule nonbonded pass: LJ + bare electrostatics in ONE
    pairwise sweep, forces AND energies.

    pos (..., N, 3); lj_sigma/lj_eps/charges (N,) per-atom
    (Lorentz-Berthelot mixing); nb_mask (N, N) with 0 on the diagonal
    and excluded (1-2/1-3) pairs.  Returns
    ``(f_lj (..., N, 3), f_el (..., N, 3), e_lj (...,), e_el (...,))``
    with the electrostatic pieces UNscaled — the salt ctrl applies
    outside.  Same math as ``repro.md.energy``'s pairwise term and its
    analytic custom_vjp backward, computed directly (no energy-graph
    forward pass to re-materialize).
    """
    c_lj, c_el, e_lj, e_el = _nonbonded_coefs(pos, lj_sigma, lj_eps,
                                              charges, nb_mask)
    return _coef_force(c_lj, pos), _coef_force(c_el, pos), e_lj, e_el


def nonbonded_force(pos, lj_sigma, lj_eps, charges, nb_mask,
                    salt_scale=None):
    """The propagate-loop variant: ONE combined nonbonded force.

    Folds the per-replica salt scaling (``salt_scale`` (...,) or None)
    into the pair coefficients so LJ + elec cost a single coefficient
    pass and a single GEMM — the energies are never formed."""
    c_lj, c_el, _, _ = _nonbonded_coefs(pos, lj_sigma, lj_eps, charges,
                                        nb_mask)
    if salt_scale is not None:
        c_el = salt_scale[..., None, None] * c_el
    return _coef_force(c_lj + c_el, pos)


# -- sparse (neighbor-list) nonbonded pass ---------------------------------
#
# Same physics as the dense sweep, evaluated only on each atom's padded
# neighbor slots (R, N, K) instead of all (R, N, N) pairs: one position
# gather, element-wise pair terms on (R, N, K) planes, a K-axis
# reduction.  Lists are TWO-SIDED (j in list(i) iff i in list(j)), so
# the per-atom force is a plain K-sum (no scatter) and the energy sums
# halve.  Exclusions are pruned at BUILD time (repro.md.neighbors), so
# the pass needs no dense mask; the true ``cutoff`` (< the list radius
# ``cutoff + skin``) is re-applied per evaluation — the standard Verlet
# contract, which keeps energies/forces independent of list staleness
# within the skin.


def _sparse_pair_coefs(pos, lj_sigma, lj_eps, charges, idx, valid,
                       cutoff: float, pair=None):
    """Per-slot coefficients/energies: pos (..., N, 3), idx/valid
    (..., N, K) -> (c_lj, c_el, e_lj, e_el, (dx, dy, dz)).

    Component-split throughout: x/y/z are gathered as separate
    (..., N, K) planes — same reason as the dense ``_nonbonded_coefs``:
    a (..., N, K, 3) displacement stack plus a trailing 3-axis reduce
    ends the XLA-CPU fusion; the split keeps the whole sweep one
    element-wise graph over rank-3 planes.

    ``pair`` (optional, (..., 3, N, K)) carries the build-time parameter
    planes [sig^2, eps, COULOMB*qq] (``repro.md.neighbors.pair_planes``,
    slot-aligned with ``idx``): with them the per-step parameter gathers
    vanish and the coefficient math is BITWISE identical — each plane
    precomputes exactly the sub-expression the gather path forms first
    (``sig*sig``, ``eps``, ``COULOMB*qq``), so the remaining float-op
    order is unchanged."""
    n = pos.shape[-2]
    j = jnp.clip(idx, 0, n - 1)                 # padding gathers atom n-1,
    flat = j.reshape(j.shape[:-2] + (-1,))      # masked to zero below

    def take(comp):
        return jnp.take_along_axis(comp, flat, axis=-1).reshape(j.shape)

    x, y, z = pos[..., 0], pos[..., 1], pos[..., 2]
    dx = x[..., :, None] - take(x)
    dy = y[..., :, None] - take(y)
    dz = z[..., :, None] - take(z)
    r2 = dx * dx + dy * dy + dz * dz
    mask = valid * (r2 <= cutoff * cutoff)
    r2 = r2 + (1.0 - mask)                      # guard padded / self slots
    if pair is None:
        sig = 0.5 * (lj_sigma[..., :, None] + lj_sigma[j])
        sig2 = sig * sig
        eps = jnp.sqrt(lj_eps[..., :, None] * lj_eps[j])
        cqq = COULOMB * (charges[..., :, None] * charges[j])
    else:
        sig2 = pair[..., 0, :, :]
        eps = pair[..., 1, :, :]
        cqq = pair[..., 2, :, :]
    s6 = (sig2 / r2) ** 3
    r = jnp.sqrt(r2)
    c_lj = 24.0 * eps * (2.0 * s6 * s6 - s6) / r2 * mask
    c_el = cqq / (r2 * r) * mask
    e_lj = 0.5 * jnp.sum(4.0 * eps * (s6 * s6 - s6) * mask, axis=(-2, -1))
    e_el = 0.5 * jnp.sum(cqq / r * mask, axis=(-2, -1))
    return c_lj, c_el, e_lj, e_el, (dx, dy, dz)


def _slot_force(coef, comps):
    """F_i = sum_k coef_ik * disp_ik on component planes: K-axis sums
    per component, stacked back to (..., N, 3)."""
    return jnp.stack([jnp.sum(coef * c, axis=-1) for c in comps], axis=-1)


def nonbonded_sparse(pos, lj_sigma, lj_eps, charges, idx, valid,
                     cutoff: float, pair=None):
    """Sparse analogue of :func:`nonbonded`: LJ + electrostatic forces
    AND both energy accumulators from one O(N * K) neighbor sweep.

    Returns ``(f_lj, f_el, e_lj, e_el)`` with the electrostatic pieces
    UNscaled, exactly like the dense pass.  ``pair`` passes the optional
    build-time parameter planes (see :func:`_sparse_pair_coefs`).
    """
    c_lj, c_el, e_lj, e_el, comps = _sparse_pair_coefs(
        pos, lj_sigma, lj_eps, charges, idx, valid, cutoff, pair)
    return (_slot_force(c_lj, comps), _slot_force(c_el, comps),
            e_lj, e_el)


def nonbonded_force_sparse(pos, lj_sigma, lj_eps, charges, idx, valid,
                           cutoff: float, salt_scale=None, pair=None):
    """Propagate-loop variant: one combined sparse nonbonded force."""
    c_lj, c_el, _, _, comps = _sparse_pair_coefs(
        pos, lj_sigma, lj_eps, charges, idx, valid, cutoff, pair)
    if salt_scale is not None:
        c_el = salt_scale[..., None, None] * c_el
    return _slot_force(c_lj + c_el, comps)


def nonbonded_cutoff(pos, lj_sigma, lj_eps, charges, nb_mask,
                     cutoff: float):
    """DENSE pass with a radial cutoff — the matched-cutoff oracle the
    sparse path is pinned against (tests/test_neighbor_list.py): the
    SAME pair math as :func:`nonbonded` (one shared coefficient
    helper), truncated, summed over all (N, N) pairs."""
    c_lj, c_el, e_lj, e_el = _nonbonded_coefs(pos, lj_sigma, lj_eps,
                                              charges, nb_mask, cutoff)
    return (_coef_force(c_lj, pos), _coef_force(c_el, pos), e_lj, e_el)
