"""Replica-Exchange Patterns: synchronous vs asynchronous cycles.

Synchronous (paper Fig 1a): every replica propagates exactly
``md_steps`` and then a global exchange runs — the collective IS the
barrier.

Asynchronous (paper Fig 1b), TPU-adapted: SPMD has no OS-level asynchrony,
so heterogeneous progress is modelled explicitly.  Replica i advances
``round(window * speed_i)`` steps per real-time window (speed varies across
replicas — the paper's heterogeneous-engines / straggler scenario), banks
progress in ``debt``, and only replicas whose debt crosses ``md_steps`` are
*ready* to exchange; pairs with an un-ready member are auto-rejected and the
un-ready replica keeps simulating.  A straggler therefore delays only its
ladder neighbours, never the ensemble — the paper's async claim, preserved
under SPMD.

``dim_index`` / ``parity`` come in two flavours:

  * legacy per-cycle path (``sync_cycle`` / ``async_cycle``): HOST-static —
    the driver schedules dimensions round-robin (the paper's M-REMD:
    "simulations are performed only in one dimension at any given instant
    of time") and each (dim, parity) pair is its own compiled cycle.
  * fused path (``fused_cycle``): TRACED — derived from ``ens.cycle`` on
    device via a gather into the grid's stacked pair table, so a single
    compiled ``lax.scan`` can run K full cycles with zero host round-trips.

Replica sharding (``fused_cycle(axis_name=...)``, used by
``REMDDriver.run_sharded``): the same cycle body runs inside a
``shard_map`` over a ``("replica",)`` mesh axis.  Synchronization
contract per phase — propagate is PER-REPLICA and fully shard-local
(positions/velocities/neighbor lists never leave their device); the
exchange is the only PER-ENSEMBLE phase, with two wire protocols
selected by ``exchange_comm``:

  * ``"halo"`` (default): shard-LOCAL exchange — each shard reduces only
    its own block's features to the per-replica exchange scalars and
    those scalars (plus the (B,) failure flags) hop the ladder ring via
    ``lax.ppermute`` halos (``exchange.neighbor_exchange_sharded`` /
    ``matrix_exchange_sharded``).  The failure halo is issued BEFORE the
    expensive energy reduction so XLA overlaps the permute hops with
    local compute.  Per-shard wire: O(R/n_shards) scalars per sweep —
    O(1) boundary rows at the paper's R ~ n_devices operating point —
    and the compiled program contains only collective-permutes.
  * ``"gather"`` (legacy PR-5 baseline, kept for the
    ``exchange_scaling`` A/B benchmark): all-gather the (R,)-per-field
    feature rows + (R,) failure mask and recompute the full reduction
    replicated on every shard.

Either way the swap decision is evaluated from bitwise-identical
replicated inputs, which keeps the discrete trajectory bit-equal to the
unsharded ``run_fused`` (docs/SCALING.md §Bitwise-equivalence contract).
Control-plane vectors (``assignment``, ``debt``, ``speed``, ``alive``,
per-replica step counts and RNG keys) are computed replicated at full
(R,) size and sliced to the local block via ``modes.shard_rows`` right
before propagate.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import modes as M
from repro.core.controls import ControlGrid, ctrl_for_assignment
from repro.core.ensemble import Ensemble
from repro.core.exchange import (matrix_exchange, matrix_exchange_sharded,
                                 neighbor_exchange,
                                 neighbor_exchange_sharded, replica_features)


def _propagate(engine, ens: Ensemble, grid: ControlGrid, n_steps, rng,
               execution: Dict[str, Any], max_steps: int, mesh=None):
    ctrl = ctrl_for_assignment(grid, ens.assignment,
                               getattr(engine, "ctrl_keys", None))
    if execution["mode"] == "mode2":
        return M.propagate_mode2(engine, ens.state, ctrl, n_steps, rng,
                                 execution["n_waves"], mesh,
                                 max_steps=max_steps)
    return M.propagate_mode1(engine, ens.state, ctrl, n_steps, rng, mesh,
                             max_steps=max_steps)


def _propagate_sharded(engine, ens: Ensemble, grid: ControlGrid, n_steps,
                       rng, execution: Dict[str, Any], max_steps: int,
                       axis_name: str, n_shards: int):
    """Per-shard propagate: ``ens.state`` holds only this shard's replica
    block; ctrl rows, step counts and per-replica keys are computed
    replicated (they are (R,)-small) and sliced to the block, so every
    replica sees inputs bitwise-equal to the unsharded run.  Mode II's
    ``n_waves`` applies to the LOCAL block — the mesh is the spatial
    resource dimension, waves the temporal one (see ``repro.core.modes``).
    """
    ctrl = ctrl_for_assignment(grid, ens.assignment,
                               getattr(engine, "ctrl_keys", None))
    keys = M.per_replica_keys(rng, ens.assignment.shape[0])
    sl = functools.partial(M.shard_rows, axis_name=axis_name,
                           n_shards=n_shards)
    ctrl = jax.tree.map(sl, ctrl)
    if execution["mode"] == "mode2":
        return M.propagate_mode2(engine, ens.state, ctrl, sl(n_steps),
                                 n_waves=execution["n_waves"],
                                 max_steps=max_steps, keys=sl(keys))
    return M.propagate_mode1(engine, ens.state, ctrl, sl(n_steps),
                             max_steps=max_steps, keys=sl(keys))


def _exchange(engine, state, grid, assignment, dim_index: int, parity: int,
              rng, scheme: str, ready=None, features=None, fail=None,
              halo_axis=None, n_shards: int = 1):
    """Scheme dispatch.  With ``halo_axis`` set the shard-local halo
    variants run (they consume the LOCAL ``state`` block directly and
    return a third element, the replicated fail row); otherwise the
    legacy entry points run on ``state`` or on pre-gathered
    ``features``/``fail``."""
    if halo_axis is not None:
        if scheme == "matrix":
            return matrix_exchange_sharded(
                engine, state, grid, assignment, rng,
                axis_name=halo_axis, n_shards=n_shards)
        return neighbor_exchange_sharded(
            engine, state, grid, assignment, dim_index, parity, rng,
            axis_name=halo_axis, n_shards=n_shards, ready=ready)
    if scheme == "matrix":
        return matrix_exchange(engine, state, grid, assignment, rng,
                               features=features, fail=fail)
    return neighbor_exchange(engine, state, grid, assignment, dim_index,
                             parity, rng, ready=ready, features=features,
                             fail=fail)


def _cycle_core(engine, grid: ControlGrid, ens: Ensemble, *, pattern: str,
                md_steps: int, window_steps: int, dim_index, parity,
                scheme: str, execution, mesh, axis_name=None, n_shards=1,
                exchange_comm: str = "halo"
                ) -> Tuple[Ensemble, Dict[str, Any], jax.Array, Any]:
    """The ONE cycle body shared by every entry point.

    ``dim_index``/``parity`` may be host ints (legacy per-cycle jits) or
    traced scalars (fused scan) — the exchange gathers its sweep from the
    stacked :class:`PairTable` either way, so legacy and fused execution
    are the same trace by construction, not by manual lockstep.  With
    ``axis_name`` set the body runs per shard (see module docstring):
    propagate is local, and the exchange communicates via the
    ``exchange_comm`` wire protocol (halo ppermutes by default, the
    legacy all-gather when ``"gather"``).  Returns (new_ens,
    exchange_stats, ready_mask, fail_row) — ``fail_row`` is the
    replicated (R,) failure mask when sharded (reused by failure
    recovery so it never re-gathers), else None.

    The phases run under the scopes ``propagate``, ``features`` and
    ``exchange`` of a device trace: op metadata only, the compiled
    program is unchanged (docs/OBSERVABILITY.md).
    """
    k_md, k_ex, k_next = jax.random.split(ens.rng, 3)

    with jax.named_scope("propagate"):
        if pattern == "asynchronous":
            max_steps = 2 * window_steps
            n_steps = jnp.clip(
                jnp.round(window_steps * ens.speed).astype(jnp.int32),
                1, max_steps)
        else:
            max_steps = md_steps
            n_steps = jnp.full(ens.assignment.shape, md_steps, jnp.int32)
        if axis_name is None:
            state = _propagate(engine, ens, grid, n_steps, k_md, execution,
                               max_steps, mesh)
        else:
            state = _propagate_sharded(engine, ens, grid, n_steps, k_md,
                                       execution, max_steps, axis_name,
                                       n_shards)

    halo_axis = gather = features = None
    if axis_name is not None:
        if exchange_comm == "gather":
            # legacy PR-5 wire: all-gather the (R,)-per-field feature
            # rows and the (R,) failure mask, recompute the reduction
            # replicated (the exchange_scaling A/B baseline)
            gather = functools.partial(jax.lax.all_gather,
                                       axis_name=axis_name, tiled=True)
            features = replica_features(engine, state, gather)
        else:
            # halo wire: the sharded exchange variants reduce the local
            # block themselves and ring only O(B) exchange scalars +
            # failure flags per sweep — positions, features and neighbor
            # lists stay shard-local (HLO census: collective-permutes
            # only, tests/test_sharded.py)
            halo_axis = axis_name

    def run_exchange(ready):
        fail = None if gather is None else gather(engine.is_failed(state))
        out = _exchange(engine, state, grid, ens.assignment, dim_index,
                        parity, k_ex, scheme, ready=ready,
                        features=features, fail=fail,
                        halo_axis=halo_axis, n_shards=n_shards)
        if halo_axis is not None:
            return out                      # (assignment, stats, fail_row)
        return out + (fail,)                # gather-mode fail row (or None)

    with jax.named_scope("exchange"):
        if pattern == "asynchronous":
            debt = ens.debt + n_steps.astype(jnp.float32)
            ready = (debt >= md_steps) & ens.alive
            assignment, stats, fail_row = run_exchange(ready)
            debt = jnp.where(ready, debt - md_steps, debt)
            new_ens = ens._replace(state=state, assignment=assignment,
                                   rng=k_next, cycle=ens.cycle + 1,
                                   debt=debt)
        else:
            ready = ens.alive
            assignment, stats, fail_row = run_exchange(ready)
            new_ens = ens._replace(state=state, assignment=assignment,
                                   rng=k_next, cycle=ens.cycle + 1)
    return new_ens, stats, ready, fail_row


def _pop_pair_rows(stats: Dict[str, Any], keep: bool):
    """Remove the private per-pair telemetry rows from an exchange stats
    dict, returning them when ``keep``.  Popping happens INSIDE the trace
    but the rows only become jit outputs when kept — with ``keep=False``
    XLA dead-code-eliminates them and the compiled program is identical
    to one that never carried them (the telemetry-off HLO-identity
    contract).  The matrix (Gibbs) scheme re-draws its pairings every
    sweep, so it has no static pair-slot axis and emits no rows."""
    pa = stats.pop("_pair_attempt", None)
    pc = stats.pop("_pair_accept", None)
    if keep and pa is not None:
        return pa, pc
    return None, None


def sync_cycle(engine, grid: ControlGrid, ens: Ensemble, md_steps: int,
               dim_index: int, parity: int, scheme: str = "neighbor",
               execution=None, mesh=None, telemetry_rows: bool = False
               ) -> Tuple[Ensemble, Dict[str, Any]]:
    """One synchronous cycle: propagate-all barrier, then one exchange sweep
    along the scheduled dimension (DEO parity).  Paper Fig 1a.

    Synchronization contract: propagate is per-replica; the exchange
    sweep is per-ensemble (it is the barrier).  ``telemetry_rows``
    surfaces the per-pair attempt/accept rows as ``pair_attempt`` /
    ``pair_accept`` stats (neighbor scheme only)."""
    execution = execution or {"mode": "mode1", "n_waves": 1}
    new_ens, stats, _, _ = _cycle_core(
        engine, grid, ens, pattern="synchronous", md_steps=md_steps,
        window_steps=0, dim_index=dim_index, parity=parity, scheme=scheme,
        execution=execution, mesh=mesh)
    pa, pc = _pop_pair_rows(stats, telemetry_rows)
    out_stats: Dict[str, Any] = {f"dim{dim_index}": stats}
    if pa is not None:
        out_stats["pair_attempt"], out_stats["pair_accept"] = pa, pc
    return new_ens, out_stats


def async_cycle(engine, grid: ControlGrid, ens: Ensemble, md_steps: int,
                window_steps: int, dim_index: int, parity: int,
                scheme: str = "neighbor", execution=None, mesh=None,
                telemetry_rows: bool = False
                ) -> Tuple[Ensemble, Dict[str, Any]]:
    """One asynchronous real-time window.  Paper Fig 1b.

    Each replica advances by its own speed; replicas whose banked progress
    reaches ``md_steps`` become ready, exchange, and bank the remainder.

    Synchronization contract: propagate is per-replica (heterogeneous
    step counts); the exchange is per-ensemble but masked — pairs with
    an un-ready member auto-reject, so a straggler delays only its
    ladder neighbours."""
    execution = execution or {"mode": "mode1", "n_waves": 1}
    new_ens, stats, ready, _ = _cycle_core(
        engine, grid, ens, pattern="asynchronous", md_steps=md_steps,
        window_steps=window_steps, dim_index=dim_index, parity=parity,
        scheme=scheme, execution=execution, mesh=mesh)
    pa, pc = _pop_pair_rows(stats, telemetry_rows)
    out_stats: Dict[str, Any] = {f"dim{dim_index}": stats,
                                 "ready_frac": jnp.mean(
                                     ready.astype(jnp.float32))}
    if pa is not None:
        out_stats["pair_attempt"], out_stats["pair_accept"] = pa, pc
    return new_ens, out_stats


def fused_cycle(engine, grid: ControlGrid, ens: Ensemble, *,
                pattern: str, md_steps: int, window_steps: int,
                scheme: str = "neighbor", execution=None, mesh=None,
                axis_name=None, n_shards: int = 1,
                exchange_comm: str = "halo", telemetry_rows: bool = False
                ) -> Tuple[Ensemble, Dict[str, jax.Array]]:
    """One cycle with dim/parity derived ON DEVICE from ``ens.cycle``.

    The same ``_cycle_core`` as ``sync_cycle``/``async_cycle`` — same rng
    splits, same propagate, same exchange draw shapes — but with the sweep
    selected by a gather into the stacked :class:`PairTable` instead of
    host-static closure args.  That makes the whole cycle a legal
    ``lax.scan`` body: K cycles compile to ONE program with zero host
    round-trips inside the chunk.

    With ``axis_name`` set, the cycle body additionally runs per shard of
    a replica mesh (the ``run_sharded`` path — see module docstring):
    same scan-body property, but propagate touches only the local
    replica block and the per-cycle stats are reduced across shards
    (``lax.pmax`` on the neighbor-list counters; everything else is
    already replicated).

    Returns (new_ens, stats) where stats is a FLAT dict of fixed-shape
    arrays (``dim``, ``accepted``, ``attempted``, ``ready_frac``, the
    post-cycle ``assignment`` row, and the engine's neighbor-list health
    scalars ``nb_overflow`` / ``nb_rebuilds`` — zeros for dense engines)
    suitable for stacking into the scan's per-cycle ys.  ``mean_delta``
    is deliberately NOT carried: nothing downstream reads it per-cycle,
    and dropping it lets XLA dead-code-eliminate its reduction from the
    scan body (the fused hot loop is op-count-bound on CPU).  The
    per-cycle assignment trace is what the statistical-correctness
    suite consumes (rung occupancy, per-pair acceptance) — K cycles of
    discrete trajectory for one host fetch.

    ``telemetry_rows=True`` additionally carries the exchange's per-pair
    attempt/accept rows (``pair_attempt`` / ``pair_accept``, fixed width
    W — the stacked PairTable's slot axis) in the ys: per-pair counters
    for K cycles at the same one-fetch-per-chunk cost (zero host
    round-trips inside the chunk).  Off (the default), the rows are
    popped before they can become scan outputs, so the compiled program
    is IDENTICAL to one without telemetry (op-budget-pinned).  The
    matrix scheme emits no rows (its pairings are re-drawn per sweep).
    """
    execution = execution or {"mode": "mode1", "n_waves": 1}
    n_dims = len(grid.dims)
    dim_index = jnp.mod(ens.cycle, n_dims)
    parity = jnp.mod(ens.cycle // n_dims, 2)
    new_ens, stats, ready, fail_row = _cycle_core(
        engine, grid, ens, pattern=pattern, md_steps=md_steps,
        window_steps=window_steps, dim_index=dim_index, parity=parity,
        scheme=scheme, execution=execution, mesh=mesh,
        axis_name=axis_name, n_shards=n_shards,
        exchange_comm=exchange_comm)
    pa, pc = _pop_pair_rows(stats, telemetry_rows)
    flat = {
        "dim": dim_index.astype(jnp.int32),
        "accepted": stats["accepted"],
        "attempted": stats["attempted"],
        "ready_frac": jnp.mean(ready.astype(jnp.float32)),
        "assignment": new_ens.assignment,
    }
    if pa is not None:
        flat["pair_attempt"], flat["pair_accept"] = pa, pc
    if axis_name is not None and fail_row is not None:
        # the replicated (R,) failure row already rode the exchange halo
        # this cycle — hand it to the caller (repex._chunk_scan pops it
        # before the stats enter the scan ys) so failure recovery reuses
        # it instead of gathering a second time
        flat["_fail_row"] = fail_row
    nb = nb_health(engine, new_ens.state)
    if axis_name is not None:
        # worst-replica counters over ALL shards (max is exact in f32,
        # so the sharded stats match the unsharded ones bitwise)
        nb = {k: jax.lax.pmax(v, axis_name) for k, v in nb.items()}
    flat.update(nb)
    return new_ens, flat


def nb_health(engine, state) -> Dict[str, jax.Array]:
    """Engine-agnostic neighbor-list health scalars for cycle stats:
    engines exposing ``nb_stats`` (the sparse nonbonded path) report
    their cumulative overflow/rebuild counters; everything else reports
    zeros so the stats pytree keeps one shape across engines."""
    from repro.core.engine import nb_zero_stats
    fn = getattr(engine, "nb_stats", None)
    if callable(fn):
        return fn(state)
    return nb_zero_stats()
