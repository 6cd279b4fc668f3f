"""Exchange phase: Metropolis acceptance over neighbor pairs (DEO) or the
full-matrix Gibbs scheme.

Like all modern RE implementations we swap *control parameters* (scalars),
never configurations.  The ensemble keeps ``assignment[r] = ctrl index held
by replica r``; an accepted exchange swaps two entries of ``assignment``.

Acceptance for a proposed swap of ctrls (a, b) held by replicas (i, j):

    delta = [u_b(x_i) + u_a(x_j)] - [u_a(x_i) + u_b(x_j)]
    P(accept) = min(1, exp(-delta))

For pure temperature exchange this reduces to (beta_a - beta_b)(E_j - E_i)
and is computable from the per-replica potential energies alone — the
paper's *cheap* exchange.  Umbrella/salt dimensions need the cross energies
u_b(x_i) — the paper's *expensive* 'single-point energy' exchange (S-REMD),
which we batch into one fused evaluation (see kernels/exchange_matrix).

Synchronization contract: exchange is the ONE per-ensemble phase of a
cycle — it reads every replica's reduced energies and failure flags and
permutes the shared ``assignment`` vector.  Under replica sharding
(``run_sharded``) there are two wire protocols:

  * halo (default, ``exchange_comm="halo"``): the shard-LOCAL entry
    points :func:`neighbor_exchange_sharded` /
    :func:`matrix_exchange_sharded`.  Each shard reduces its own replica
    block's features to the per-replica exchange scalars (u_self/u_swap
    rows, or its (B, C) tile of the cross-energy matrix) and only those
    scalars — plus the (B,) failure flags — hop along the ladder ring
    via ``lax.ppermute`` halos (``repro.sharding.ring_all_gather``).
    The expensive feature reduction is O(B) per shard instead of O(R)
    replicated, the matrix build is a (B, C) tile instead of the
    replicated (R, C), and the compiled program contains ONLY
    collective-permutes at exchange time (HLO census,
    tests/test_sharded.py).

  * gather (legacy, ``exchange_comm="gather"``): the PR-5 protocol —
    both legacy entry points accept the cross-device inputs
    pre-gathered: ``features`` (the (R,)-per-field feature rows) and
    ``fail`` (the (R,) failure mask), and every shard recomputes the
    identical full-ensemble reduction.  Kept as the A/B baseline for
    ``benchmarks/run.py exchange_scaling``.

Either way the swap DECISION is evaluated from identical replicated
inputs (the halo ring reassembles the exact per-shard scalars in global
replica order — copies, never reductions), so the discrete trajectory
is bitwise-equal to ``run_fused`` across mesh shapes and wire
protocols; positions never cross devices.  Only the (R,) ``assignment``
row itself stays replicated — the history/checkpoint exception
(docs/SCALING.md).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.controls import ControlGrid, ctrl_for_assignment
from repro.core.modes import shard_rows
from repro.sharding import ring_all_gather


def inverse_permutation(assignment: jax.Array) -> jax.Array:
    """inv[c] = replica holding ctrl c."""
    n = assignment.shape[0]
    return jnp.zeros(n, assignment.dtype).at[assignment].set(jnp.arange(n))


def metropolis(delta: jax.Array, rng: jax.Array) -> jax.Array:
    u = jax.random.uniform(rng, delta.shape)
    return u < jnp.exp(jnp.minimum(-delta, 0.0))


def replica_features(engine, state, gather=None):
    """The engine's feature pass (on the sharded gather wire, followed by
    ``gather`` on every feature row), under the ``features`` scope of a
    device trace (docs/OBSERVABILITY.md)."""
    with jax.named_scope("features"):
        feats = engine.replica_features(state)
        return feats if gather is None else jax.tree.map(gather, feats)


def _split_api(engine, reduce: str) -> bool:
    return (callable(getattr(engine, "replica_features", None))
            and callable(getattr(engine, reduce, None)))


def pair_energies(engine, state, ctrl_self: Dict, ctrl_swap: Dict
                  ) -> Tuple[jax.Array, jax.Array]:
    """Reduced energies under the current and the swapped ctrl assignment.

    Engines exposing the split feature API evaluate both assignments from
    ONE feature pass (the O(N^2) pair sums are ctrl-independent); others
    fall back to two full ``energy`` calls.
    """
    if _split_api(engine, "energy_pair_from_features"):
        return engine.energy_pair_from_features(
            replica_features(engine, state), ctrl_self, ctrl_swap)
    return (engine.energy(state, ctrl_self),
            engine.energy(state, ctrl_swap))


def _sweep_pairs(grid: ControlGrid, assignment: jax.Array, dim_index, parity):
    """Gather one DEO sweep from the stacked :class:`PairTable` and map its
    ctrl pairs to replicas.  Shared by the fused and the halo-sharded
    neighbor exchange — both must draw the sweep identically for the
    bitwise contract to hold."""
    tab = grid.pair_table
    left = jnp.asarray(tab.left)[dim_index, parity]
    right = jnp.asarray(tab.right)[dim_index, parity]
    valid = jnp.asarray(tab.valid)[dim_index, parity]
    inv = inverse_permutation(assignment)
    n = assignment.shape[0]
    # padding pairs scatter to index n: dropped, so they can never race a
    # real pair's write (ctrl 0 appears in both real and padding slots)
    ri = jnp.where(valid, inv[left], n)     # replicas holding the left ctrls
    rj = jnp.where(valid, inv[right], n)
    swapped = (assignment.at[ri].set(right, mode="drop")
               .at[rj].set(left, mode="drop"))
    n_valid = jnp.asarray(tab.count)[dim_index, parity]
    return left, right, valid, ri, rj, swapped, n_valid


def _decide_sweep(assignment, u_self, u_swap, left, right, valid, ri, rj,
                  n_valid, rng, ready, fail):
    """The replicated Metropolis decision on exactly-assembled energy rows.

    Every caller — fused, gather-sharded, halo-sharded — reaches this
    point with bitwise-identical (R,) ``u_self`` / ``u_swap`` rows and the
    same ``rng``, so the accept mask (and hence the discrete trajectory)
    cannot depend on the wire protocol.  The delta keeps the exact fused
    association ``(u_swap[ri] + u_swap[rj]) - (u_self[ri] + u_self[rj])``.
    """
    delta = (u_swap[ri] + u_swap[rj]) - (u_self[ri] + u_self[rj])
    accept = metropolis(delta, rng) & valid
    if ready is not None:
        accept = accept & ready[ri] & ready[rj]
    accept = accept & ~fail[ri] & ~fail[rj]

    new_left = jnp.where(accept, right, left)
    new_right = jnp.where(accept, left, right)
    new_assignment = (assignment.at[ri].set(new_left, mode="drop")
                      .at[rj].set(new_right, mode="drop"))
    stats = {
        "attempted": n_valid,
        "accepted": jnp.sum(accept.astype(jnp.float32)),
        "mean_delta": (jnp.sum(jnp.where(valid, delta, 0.0))
                       / jnp.maximum(n_valid, 1.0)),
        # per-pair-slot telemetry rows (W,): slot w of the stacked
        # PairTable sweep.  ``valid`` and ``accept`` already exist, so
        # carrying them costs nothing here — callers that do not want
        # them pop the keys BEFORE the jit boundary and XLA dead-code
        # eliminates the casts (the telemetry-off HLO-identity contract,
        # tests/test_telemetry.py).
        "_pair_attempt": valid.astype(jnp.float32),
        "_pair_accept": accept.astype(jnp.float32),
    }
    return new_assignment, stats


def neighbor_exchange(
    engine,
    state,
    grid: ControlGrid,
    assignment: jax.Array,
    dim_index,
    parity,
    rng: jax.Array,
    ready: jax.Array = None,
    features=None,
    fail: jax.Array = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One DEO exchange sweep along one grid dimension.

    ``dim_index``/``parity`` may be host ints OR traced scalars (the fused
    multi-cycle path derives them from ``ens.cycle`` on device): the sweep's
    pairs are gathered from the grid's stacked :class:`PairTable`, padded to
    a fixed width so one compiled program serves every sweep.  Padding
    pairs are self-pairs with ``valid == False`` — auto-rejected, and their
    scatter writes are no-ops.

    ``ready`` masks replicas eligible to exchange (asynchronous pattern:
    lagging replicas sit out — their pairs are auto-rejected, which is
    exactly how async RE degrades gracefully instead of barriering).

    ``features`` / ``fail``: pre-computed full-ensemble feature rows and
    failure flags.  The legacy gather-sharded path passes them
    (all-gathered from the per-shard blocks) because ``state`` there holds
    only the local replicas; when omitted they are derived from ``state``
    directly.  Both routes reduce features with the same engine code, so
    decisions are bitwise identical.  Returns (new_assignment, stats).
    """
    left, right, valid, ri, rj, swapped, n_valid = _sweep_pairs(
        grid, assignment, dim_index, parity)

    # current and swapped reduced energies (one feature pass for both)
    ctrl_keys = getattr(engine, "ctrl_keys", None)
    ctrl_self = ctrl_for_assignment(grid, assignment, ctrl_keys)
    ctrl_swap = ctrl_for_assignment(grid, swapped, ctrl_keys)
    if features is not None:
        u_self, u_swap = engine.energy_pair_from_features(
            features, ctrl_self, ctrl_swap)
    else:
        u_self, u_swap = pair_energies(engine, state, ctrl_self, ctrl_swap)

    if fail is None:
        fail = engine.is_failed(state)
    return _decide_sweep(assignment, u_self, u_swap, left, right, valid,
                         ri, rj, n_valid, rng, ready, fail)


def neighbor_exchange_sharded(
    engine,
    state,
    grid: ControlGrid,
    assignment: jax.Array,
    dim_index,
    parity,
    rng: jax.Array,
    *,
    axis_name: str,
    n_shards: int,
    ready: jax.Array = None,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """Halo-sharded DEO sweep: shard-local reductions, ppermute-only wire.

    ``state`` is this shard's replica block (B = R / n_shards rows);
    ``assignment``/``ready``/``rng`` are replicated control-plane inputs.
    Each shard:

      1. issues the (B,) failure-flag halo ring FIRST — the ring's
         ppermute hops carry one bool per local replica and have no data
         dependence on the energy reduction, so XLA overlaps them with
         the expensive feature pass below (the collective–compute
         overlap from the PR-5 open item);
      2. reduces ONLY its local block's features and evaluates
         ``energy_pair_from_features`` on its own ctrl-row slice — O(B)
         work instead of the legacy path's O(R) replicated reduction;
      3. rings the packed (2B,) ``[u_self_loc, u_swap_loc]`` scalars and
         reassembles the exact (R,) rows in global replica order.

    The wire per sweep is therefore O(B) exchange scalars + flags per
    shard boundary per hop — at the paper's R ~ n_devices operating
    point (B = 1) literally one boundary energy row and one flag — and
    the compiled program contains ONLY collective-permutes (census in
    tests/test_sharded.py).  Because ring blocks are copied, never
    reduced, the reassembled rows equal the fused rows bitwise and
    :func:`_decide_sweep` yields the identical trajectory.

    Returns (new_assignment, stats, fail_row): the replicated (R,) fail
    row is handed back so the caller reuses it for failure recovery
    instead of re-gathering (``failures.detect_recover_sharded``).
    """
    n = assignment.shape[0]
    b = n // n_shards
    sl = functools.partial(shard_rows, axis_name=axis_name,
                           n_shards=n_shards)

    # (1) failure halo — issued before the heavy feature pass (overlap)
    fail_row = ring_all_gather(engine.is_failed(state), axis_name,
                               n_shards).reshape(n)

    left, right, valid, ri, rj, swapped, n_valid = _sweep_pairs(
        grid, assignment, dim_index, parity)

    # (2) shard-local energy reduction on the local ctrl-row slices
    ctrl_keys = getattr(engine, "ctrl_keys", None)
    ctrl_self = ctrl_for_assignment(grid, assignment, ctrl_keys)
    ctrl_swap = ctrl_for_assignment(grid, swapped, ctrl_keys)
    feats = replica_features(engine, state)
    u_self_loc, u_swap_loc = engine.energy_pair_from_features(
        feats, jax.tree.map(sl, ctrl_self), jax.tree.map(sl, ctrl_swap))

    # (3) exchange-scalar halo: (2B,) per shard, reassembled in global
    # replica order — copies of exact per-shard values, hence bitwise
    rows = ring_all_gather(
        jnp.concatenate([u_self_loc, u_swap_loc]), axis_name, n_shards)
    u_self = rows[:, :b].reshape(n)
    u_swap = rows[:, b:].reshape(n)

    new_assignment, stats = _decide_sweep(
        assignment, u_self, u_swap, left, right, valid, ri, rj, n_valid,
        rng, ready, fail_row)
    return new_assignment, stats, fail_row


def matrix_exchange(
    engine,
    state,
    grid: ControlGrid,
    assignment: jax.Array,
    rng: jax.Array,
    n_sweeps: int = 1,
    features=None,
    fail: jax.Array = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Gibbs-style exchange from the full cross-energy matrix.

    Uses u[i, c] = reduced energy of replica i's state under ctrl c (the
    all-pairs 'single point energy' matrix — Pallas kernel hot spot).  We
    run ``n_sweeps`` sweeps of independent-pair Metropolis over a random
    pairing of ctrl indices — a standard generalization that mixes faster
    than nearest-neighbor DEO at the same energy-evaluation cost.

    ``features`` / ``fail``: as in :func:`neighbor_exchange` — the
    sharded path supplies the all-gathered feature rows and failure
    flags, and the (R, C) matrix is assembled replicated from them
    (``engine.cross_energy_from_features``).
    """
    n = assignment.shape[0]
    ctrl_grid = {k: v for k, v in grid.values.items()}
    if features is None and _split_api(engine, "cross_energy_from_features"):
        features = replica_features(engine, state)
    if features is not None:
        u = engine.cross_energy_from_features(features, ctrl_grid)
    else:
        u = engine.cross_energy(state, ctrl_grid)
    if fail is None:
        fail = engine.is_failed(state)

    def sweep(carry, key):
        assignment = carry
        perm = jax.random.permutation(key, n)
        a, b = perm[: n // 2 * 2 : 2], perm[1: n // 2 * 2 : 2]
        inv = inverse_permutation(assignment)
        ri, rj = inv[a], inv[b]
        delta = (u[ri, b] + u[rj, a]) - (u[ri, a] + u[rj, b])
        accept = metropolis(delta, jax.random.fold_in(key, 7))
        accept = accept & ~fail[ri] & ~fail[rj]
        new_a = jnp.where(accept, b, a)
        new_b = jnp.where(accept, a, b)
        assignment = assignment.at[ri].set(new_a).at[rj].set(new_b)
        return assignment, jnp.sum(accept.astype(jnp.float32))

    keys = jax.random.split(rng, n_sweeps)
    assignment, accepted = jax.lax.scan(sweep, assignment, keys)
    stats = {
        "attempted": jnp.asarray(n_sweeps * (n // 2), jnp.float32),
        "accepted": jnp.sum(accepted),
        "mean_delta": jnp.zeros(()),
    }
    return assignment, stats


def matrix_exchange_sharded(
    engine,
    state,
    grid: ControlGrid,
    assignment: jax.Array,
    rng: jax.Array,
    n_sweeps: int = 1,
    *,
    axis_name: str,
    n_shards: int,
) -> Tuple[jax.Array, Dict[str, jax.Array], jax.Array]:
    """Blocked, shard-local Gibbs exchange: (B, C) tiles, ppermute wire.

    Each shard builds only ITS (B, C) tile of the cross-energy matrix
    from its local replica block (``engine.cross_energy_from_features``
    on B rows) — O(R²/S) compute and memory per shard instead of the
    legacy replicated (R, C) build.  Per sweep, a shard contributes the
    four energy terms of the fused delta
    ``(u[ri, b] + u[rj, a]) - (u[ri, a] + u[rj, b])`` for the pairs
    whose row replica lives in its block (one-hot-masked: the exact tile
    value where local, 0.0 elsewhere), and the stacked (4·n/2,)
    contribution vector hops the ladder ring.  Summing the ring blocks
    in fixed shard order reassembles each term EXACTLY (x + 0.0 == x;
    the only non-bitwise case, -0.0 vs +0.0, cannot flip a Metropolis
    comparison), so the decision — taken with the fused association and
    the fused rng stream — is bit-identical to :func:`matrix_exchange`.

    As in :func:`neighbor_exchange_sharded` the failure halo is issued
    first to overlap the tile build, and the replicated (R,) fail row is
    returned for reuse by failure recovery.
    """
    n = assignment.shape[0]
    b = n // n_shards
    off = jax.lax.axis_index(axis_name) * b

    fail = ring_all_gather(engine.is_failed(state), axis_name,
                           n_shards).reshape(n)
    feats = replica_features(engine, state)
    tile = engine.cross_energy_from_features(
        feats, {k: v for k, v in grid.values.items()})   # (B, C) local tile

    def pick(rows, cols):
        # this shard's one-hot contribution to u[rows, cols]
        loc = rows - off
        in_block = (loc >= 0) & (loc < b)
        return jnp.where(in_block, tile[jnp.clip(loc, 0, b - 1), cols], 0.0)

    def sweep(carry, key):
        assignment = carry
        perm = jax.random.permutation(key, n)
        a, bb = perm[: n // 2 * 2 : 2], perm[1: n // 2 * 2 : 2]
        inv = inverse_permutation(assignment)
        ri, rj = inv[a], inv[bb]
        contrib = jnp.stack(
            [pick(ri, bb), pick(rj, a), pick(ri, a), pick(rj, bb)])
        terms = ring_all_gather(contrib.reshape(-1), axis_name,
                                n_shards).sum(axis=0).reshape(4, -1)
        delta = (terms[0] + terms[1]) - (terms[2] + terms[3])
        accept = metropolis(delta, jax.random.fold_in(key, 7))
        accept = accept & ~fail[ri] & ~fail[rj]
        new_a = jnp.where(accept, bb, a)
        new_b = jnp.where(accept, a, bb)
        assignment = assignment.at[ri].set(new_a).at[rj].set(new_b)
        return assignment, jnp.sum(accept.astype(jnp.float32))

    keys = jax.random.split(rng, n_sweeps)
    assignment, accepted = jax.lax.scan(sweep, assignment, keys)
    stats = {
        "attempted": jnp.asarray(n_sweeps * (n // 2), jnp.float32),
        "accepted": jnp.sum(accepted),
        "mean_delta": jnp.zeros(()),
    }
    return assignment, stats, fail
