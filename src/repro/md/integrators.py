"""BAOAB Langevin integrator (Leimkuhler-Matthews) in AKMA-ish units.

positions Angstrom, velocities Angstrom/ps, masses amu, energies kcal/mol.
acceleration = F / m * AKMA  (AKMA = 418.4 converts kcal/mol/A/amu to A/ps^2).

``force_fn`` is any (R, N, 3) -> (R, N, 3) stacked force field — the
engines thread autodiff gradients (oracle paths) or the analytic
chain/nonbonded force passes (``force_path="pallas"``, the default)
through the same loop, so ``run_fused`` scans over whichever force
implementation the engine selected with identical masking/noise
semantics.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

AKMA = 418.4
KB = 0.0019872041  # kcal/mol/K


def maxwell_boltzmann(rng, masses, temperature, shape3):
    sigma = jnp.sqrt(AKMA * KB * temperature / masses)[..., None]
    return sigma * jax.random.normal(rng, shape3)


def baoab_step(pos, vel, rng, force_fn: Callable, masses, temperature,
               dt: float = 5e-4, gamma: float = 5.0):
    """One BAOAB step at a (traced) per-replica temperature."""
    m = masses[..., None]
    f = force_fn(pos)
    vel = vel + 0.5 * dt * AKMA * f / m                      # B
    pos = pos + 0.5 * dt * vel                               # A
    c1 = jnp.exp(-gamma * dt)
    sigma = jnp.sqrt(AKMA * KB * temperature / masses)[..., None]
    noise = jax.random.normal(rng, pos.shape)
    vel = c1 * vel + jnp.sqrt(1 - c1 * c1) * sigma * noise   # O
    pos = pos + 0.5 * dt * vel                               # A
    f = force_fn(pos)
    vel = vel + 0.5 * dt * AKMA * f / m                      # B
    return pos, vel


def baoab_scales(masses, temperature, dt: float, gamma: float):
    """The loop-invariant BAOAB coefficients: the O-step decay ``c1 =
    exp(-gamma dt)`` and the (R, N, 1) thermal noise scale
    ``sqrt(1 - c1^2) * sigma(T, m)``.  Computed with the exact
    expressions (and association) the historical in-loop form used, so
    hoisting them out of a propagate loop body — the fused path — leaves
    every downstream float bit unchanged."""
    c1 = jnp.exp(-gamma * dt)
    sigma = jnp.sqrt(AKMA * KB * temperature[:, None]
                     / masses[None, :])[..., None]              # (R, N, 1)
    return c1, jnp.sqrt(1 - c1 * c1) * sigma


def baoab_fused_iteration(i, pos, vel, f, noise_i, c1, noise_scale, masses,
                          n_steps, max_steps: int, dt: float, box: float):
    """The fused-iteration contract: ONE masked force-sharing BAOAB
    update given this iteration's force, noise block and pre-hoisted
    scales — the exact update graph every propagate path shares.

    ``_baoab_apply`` (the pallas/batched loop body) delegates here after
    computing the scales in-body; the fused path hoists them via
    :func:`baoab_scales` and the TPU fused kernel re-emits these same
    formulas in its packed row layout.  Keeping the arithmetic in one
    function is what lets the conformance matrix pin single-step bitwise
    equality across paths.  Returns (pos, vel).
    """
    m = masses[None, :, None]
    kick = 0.5 * dt * AKMA * f / m
    # trailing half-B of step i-1: existed and was active iff i-1 < n
    trail = ((i >= 1) & (i <= n_steps))[:, None, None]
    vel = jnp.where(trail, vel + kick, vel)
    # step i: leading half-B, A, O, A (its trailing B is the NEXT
    # iteration's force)
    lead = ((i < n_steps) & (i < max_steps))[:, None, None]
    nvel = vel + kick                                        # B
    npos = pos + 0.5 * dt * nvel                             # A
    nvel = c1 * nvel + noise_scale * noise_i                 # O
    npos = npos + 0.5 * dt * nvel                            # A
    if box > 0:
        npos = jnp.mod(npos, box)
    return jnp.where(lead, npos, pos), jnp.where(lead, nvel, vel)


def _baoab_apply(i, pos, vel, f, noise_i, masses, temperature, n_steps,
                 max_steps: int, dt: float, gamma: float, box: float):
    """One force-sharing BAOAB update over the whole replica stack,
    given this iteration's (already evaluated) force.

    The BAOAB sequence per step is B A O A B, and the force of a step's
    trailing half-B equals the force of the NEXT step's leading half-B
    (positions do not move between them).  Shifting the loop boundary to
    sit between those two half-kicks lets every iteration evaluate the
    force ONCE and spend it twice:

        iteration i:  f = F(pos_i)            (evaluated by the caller)
                      trailing half-B of step i-1   (masked for i == 0)
                      leading  half-B + A O A of step i  (masked for
                                                          i == max_steps)

    Engines run ``max_steps + 1`` iterations — ``max_steps + 1`` force
    evaluations total instead of ``2 * max_steps`` — with every force
    evaluation INSIDE the loop body, which keeps XLA's compiled rounding
    identical across enclosing scan lengths (the fused driver's
    bitwise-across-chunk-sizes guarantee).  The force evaluation is the
    caller's job so plain and aux-carrying force fields (the sparse
    path's neighbor list) share this exact update graph.

    pos/vel: (R, N, 3); temperature/n_steps: (R,) traced per-replica;
    ``noise_i``: this iteration's pre-drawn N(0,1) array (R, N, 3) (see
    :func:`stacked_step_noise`).  Per-replica masking: a lane advances
    through step ``t`` iff ``t < n_steps[lane]``; exhausted lanes stay
    bitwise frozen.  ``box > 0`` wraps positions periodically after the
    step (the minimum-image force is wrap-invariant up to fp rounding).
    Returns (pos, vel).
    """
    c1, noise_scale = baoab_scales(masses, temperature, dt, gamma)
    return baoab_fused_iteration(i, pos, vel, f, noise_i, c1, noise_scale,
                                 masses, n_steps, max_steps, dt, box)


def propagate_replica_major(state, force_fn: Callable, masses, temperature,
                            n_steps, rngs, max_steps: int,
                            dt: float = 5e-4, gamma: float = 5.0,
                            box: float = 0.0):
    """The shared replica-major propagate loop: pre-drawn noise +
    ``max_steps + 1`` force-sharing BAOAB iterations.

    This helper owns the subtle parts of the batched-propagate contract
    (iteration count, noise indexing, per-lane masking) so every engine
    shares one implementation; engines supply only the stacked
    ``force_fn`` and the optional periodic ``box``.  It is the aux-free
    specialization of :func:`propagate_replica_major_aux` — ONE loop
    body for every engine, dense or sparse.
    ``state``: {"pos", "vel"} with leading replica axis.
    """
    out, _ = propagate_replica_major_aux(
        state, lambda pos, aux: (force_fn(pos), aux), (), masses,
        temperature, n_steps, rngs, max_steps, dt, gamma, box=box)
    return out


def propagate_replica_major_aux(state, force_aux_fn, aux, masses,
                                temperature, n_steps, rngs, max_steps: int,
                                dt: float = 5e-4, gamma: float = 5.0,
                                box: float = 0.0):
    """:func:`propagate_replica_major` for force fields that carry
    auxiliary state through the step loop (the sparse nonbonded path's
    neighbor list: ``force_aux_fn(pos, aux) -> (force, aux)`` runs the
    skin check / conditional rebuild before every evaluation).

    Same iteration count, same noise indexing, same masked BAOAB update
    (:func:`_baoab_apply`) — the aux carry is the only difference, so an
    aux-free ``force_aux_fn`` reproduces :func:`propagate_replica_major`
    exactly.  Returns ({"pos", "vel"}, aux).
    """
    noise = stacked_step_noise(rngs, max_steps + 1, state["pos"].shape[1:])

    def body(i, carry):
        pos, vel, aux = carry
        f, aux = force_aux_fn(pos, aux)
        pos, vel = _baoab_apply(i, pos, vel, f, noise[i], masses,
                                temperature, n_steps, max_steps, dt,
                                gamma, box)
        return pos, vel, aux

    pos, vel, aux = jax.lax.fori_loop(
        0, max_steps + 1, body, (state["pos"], state["vel"], aux))
    return {"pos": pos, "vel": vel}, aux


def propagate_replica_major_fused(state, force_aux_fn, aux, masses,
                                  temperature, n_steps, rngs,
                                  max_steps: int, dt: float = 5e-4,
                                  gamma: float = 5.0, box: float = 0.0):
    """The fused-path jnp propagate loop: same iteration count, same
    noise stream, same masked BAOAB update as
    :func:`propagate_replica_major_aux`, restructured so one iteration
    is one lean fused pass:

      * the loop-invariant O-step scales are hoisted
        (:func:`baoab_scales` — value-identical to the in-body form);
      * the noise block is drawn INSIDE the body (:func:`step_noise`,
        the same ``fold_in(key_r, t)`` stream as the pre-drawn stack),
        so live memory is O(R * N) instead of O(S * R * N);
      * force eval + update share one body via
        :func:`baoab_fused_iteration`.

    Every force evaluation stays INSIDE the loop body (``max_steps + 1``
    iterations), so compiled rounding is scan-length-invariant and the
    driver's bitwise-across-chunk-sizes guarantee carries over
    unchanged.  Returns ({"pos", "vel"}, aux).
    """
    c1, noise_scale = baoab_scales(masses, temperature, dt, gamma)
    shape = state["pos"].shape[1:]

    def body(i, carry):
        pos, vel, aux = carry
        f, aux = force_aux_fn(pos, aux)
        noise_i = step_noise(rngs, i, shape)
        pos, vel = baoab_fused_iteration(i, pos, vel, f, noise_i, c1,
                                         noise_scale, masses, n_steps,
                                         max_steps, dt, box)
        return pos, vel, aux

    pos, vel, aux = jax.lax.fori_loop(
        0, max_steps + 1, body, (state["pos"], state["vel"], aux))
    return {"pos": pos, "vel": vel}, aux


def step_noise(rngs, t, shape) -> jax.Array:
    """One iteration's noise block (R, *shape): replica r draws
    ``normal(fold_in(key_r, t), shape)`` — the stream every propagate
    path consumes, so exchange decisions agree bit for bit across
    paths.  ``t`` may be traced (a loop index)."""
    return jax.vmap(lambda k: jax.random.normal(jax.random.fold_in(k, t),
                                                shape))(rngs)


def stacked_step_noise(rngs, max_steps: int, shape) -> jax.Array:
    """Pre-draw every step's noise: (S, R) key folds -> (S, R, *shape).

    Same ``fold_in(key_r, t)`` stream the per-replica reference path
    consumes step by step, drawn as ONE wide op so the step loop carries
    no RNG thunks.  Deliberate trade: device memory is O(S * R * N)
    instead of the in-loop draw's O(R * N) — cheap for RE workloads,
    whose whole premise is short cycles (``md_steps_per_cycle`` tens to
    hundreds), but worth revisiting if propagate is ever driven with
    very large ``max_steps`` on large systems."""
    return jax.vmap(lambda t: step_noise(rngs, t, shape))(
        jnp.arange(max_steps))


def kinetic_temperature(vel, masses):
    ke = 0.5 * jnp.sum(masses[..., None] * vel * vel, axis=(-2, -1)) / AKMA
    dof = 3 * masses.shape[-1]
    return 2.0 * ke / (dof * KB)
