"""The SimulationEngine protocol — RepEx's engine-agnosticism boundary.

This interface is the paper's central design move: the RE algorithm
(exchange math, ladder bookkeeping, scheduling, fault handling) never sees
inside the engine; engines never see the exchange logic.  The paper's
engines were Amber and NAMD; ours are a JAX MD engine (`repro.md.MDEngine`),
a Lennard-Jones fluid engine (`repro.md.LJEngine`, Pallas force kernel) and
an LM parallel-tempering engine (`repro.models.LMEngine`).

All methods are *stacked over replicas* (leading axis R) and jit-able; the
Execution-Mode layer decides how the replica axis maps to hardware.
"""
from __future__ import annotations

from typing import Any, Dict, Protocol, runtime_checkable

import jax

Ctrl = Dict[str, jax.Array]      # control parameters, each (R, ...)
StateStack = Any                 # pytree with leading replica axis


@runtime_checkable
class SimulationEngine(Protocol):
    """Contract every pluggable simulation engine implements."""

    def init_state(self, rng: jax.Array, n_replicas: int) -> StateStack:
        """Stacked initial states for R replicas."""
        ...

    def propagate(self, state: StateStack, ctrl: Ctrl, n_steps: jax.Array,
                  rng: jax.Array, max_steps: int = 0) -> StateStack:
        """The 'MD phase': advance each replica n_steps[i] steps under its
        control parameters.  n_steps is per-replica and traced (asynchronous
        pattern propagates replicas by different amounts); ``max_steps`` is
        the static compiled bound — replicas with n_i < max_steps mask their
        trailing updates (idle lanes, exactly like a straggler's slot)."""
        ...

    def energy(self, state: StateStack, ctrl: Ctrl) -> jax.Array:
        """Reduced (dimensionless) energy u_i(x_i) per replica: (R,)."""
        ...

    def cross_energy(self, state: StateStack, ctrl: Ctrl) -> jax.Array:
        """Full matrix u_j(x_i): row i = state of replica i, col j = ctrl j.
        Needed by U/S-type exchanges and the Gibbs (matrix) scheme — the
        paper's 'single-point energy calculation'."""
        ...

    def is_failed(self, state: StateStack) -> jax.Array:
        """(R,) bool — replica-level failure detection.  Every engine
        flags non-finite state (NaN/inf); engines may declare additional
        thresholds (kinetic-energy divergence, bond blow-up — see
        ``repro.md.MDEngine(max_energy=..., max_bond_stretch=...)``) and
        surface what they check via the duck-typed ``failure_detectors``
        tuple (``engine_capabilities``)."""
        ...


# Optional engine extensions (duck-typed, NOT part of the Protocol so
# that minimal engines stay minimal):
#
#   def energy_pair(self, state, ctrl_a: Ctrl, ctrl_b: Ctrl)
#           -> tuple[jax.Array, jax.Array]
#       The exchange phase evaluates the ensemble under its current AND
#       its proposed ctrl assignment.  Engines whose energy factors into
#       ctrl-independent features (the expensive O(N^2) part) times a
#       cheap ctrl reduction should implement ``energy_pair`` to compute
#       the features once, through the split form below:
#       ``repro.core.exchange.pair_energies`` calls
#       ``energy_pair_from_features(replica_features(state), ...)`` when
#       the engine has the split form and two ``energy`` calls otherwise.
#
#   def replica_features(self, state) -> feature pytree (leaves (R, ...))
#   def energy_pair_from_features(self, feats, ctrl_a, ctrl_b)
#   def cross_energy_from_features(self, feats, ctrl_grid)
#       The SPLIT form of the feature decomposition: ``replica_features``
#       is the expensive state pass, the ``*_from_features`` reductions
#       are cheap and state-free.  REQUIRED by the replica-sharded path
#       (``REMDDriver.run_sharded``): each shard computes features for
#       its local replicas, the small feature rows are all-gathered, and
#       every shard runs the reduction + swap decision replicated —
#       positions never cross devices.  ``cross_energy_from_features``
#       is only needed for the matrix (Gibbs) scheme.  Engines should
#       route ``energy_pair`` / ``cross_energy`` through these so the
#       sharded and unsharded exchanges share one reduction code path
#       (the bitwise-equivalence contract, docs/SCALING.md).
#
#   ctrl_keys: tuple[str, ...]
#       The only ctrl fields the engine reads — the driver skips
#       gathering the rest of the grid each cycle.
#
#   force_path: str
#       Which force implementation the engine's propagate uses
#       ("pallas" analytic kernels / "batched" autodiff / "vmap"
#       per-replica oracle / "fused" force+update single pass for the
#       stock MD engine).  Informational: surfaced by
#       ``engine_capabilities`` for logs and benchmarks.
#
#   force_paths: tuple[str, ...]
#       The full menu of force paths the engine CLASS supports
#       (``MDEngine.FORCE_PATHS``); benchmark sweeps enumerate their
#       per-path rows from this capability.


# The neighbor-list health extension (``nb_stats``) reports these keys,
# always, fixed-shape — THE one definition; engines' zero branches, the
# fused-cycle stats fallback and the driver's dead-path literal all
# derive from it, so adding a counter is a one-place change.
NB_STAT_KEYS = ("nb_overflow", "nb_rebuilds")


def nb_zero_stats() -> Dict[str, Any]:
    """The all-zero ``nb_stats`` pytree (same keys/shapes as a live
    report — fused-scan stats must keep one shape across engines)."""
    import jax.numpy as jnp
    z = jnp.zeros((), jnp.float32)
    return {k: z for k in NB_STAT_KEYS}


def engine_capabilities(engine) -> Dict[str, Any]:
    """Feature-detect the optional extensions of a SimulationEngine.

    Duck-typed (mirrors how the driver and exchange layer actually
    dispatch), so it works for any object satisfying the protocol.
    ``REMDDriver`` records the result as ``driver.capabilities``; the
    benchmark harness prints it so a perf row is attributable to the
    paths that produced it.
    """
    keys = getattr(engine, "ctrl_keys", None)
    return {
        "energy_pair": callable(getattr(engine, "energy_pair", None)),
        "replica_features": callable(
            getattr(engine, "replica_features", None)),
        # the state-free feature reductions — together with
        # replica_features these gate run_sharded (see module docstring)
        "energy_pair_from_features": callable(
            getattr(engine, "energy_pair_from_features", None)),
        "cross_energy_from_features": callable(
            getattr(engine, "cross_energy_from_features", None)),
        # None = not declared (engine reads every ctrl field); () is a
        # legitimate declaration of "reads none" and is preserved
        "ctrl_keys": tuple(keys) if keys is not None else None,
        "force_path": getattr(engine, "force_path", None),
        # the full menu of propagate implementations the engine can be
        # constructed with (None = engine has a single fixed path);
        # sweeps derive their per-path rows from this instead of
        # hardcoding the list
        "force_paths": (tuple(paths) if (paths := getattr(
            engine, "force_paths", None)) is not None else None),
        "batched": bool(getattr(engine, "batched", False)),
        # "dense" / "sparse" for the MD engine's nonbonded pass; None =
        # engine has no nonbonded selection.  Engines with nb_stats
        # surface neighbor-list health (overflow/rebuild counters) as
        # per-cycle driver stats.
        "nonbonded": getattr(engine, "nonbonded", None),
        "nb_stats": callable(getattr(engine, "nb_stats", None)),
        # which failure detectors the engine's is_failed applies —
        # ("nonfinite",) is the protocol minimum; threshold detectors
        # (kinetic-energy divergence, bond blow-up) are opt-in per engine
        "failure_detectors": tuple(
            getattr(engine, "failure_detectors", ("nonfinite",))),
    }
