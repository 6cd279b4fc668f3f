"""MD engines implementing the SimulationEngine protocol.

``MDEngine``       — the 'Amber' stand-in: toy chain molecules, BAOAB
                     Langevin, umbrella + salt control support (full T/U/S
                     exchange).
``LJEngine``       — the 'second engine' (the paper's NAMD swap): a
                     Lennard-Jones fluid with temperature exchange; its
                     force loop is the Pallas ``lj_forces`` kernel hot spot
                     (jnp oracle fallback on CPU).
``HarmonicEngine`` — the overhead probe: an exactly-integrable
                     Ornstein-Uhlenbeck process whose whole MD phase
                     compiles to ~a dozen ops, so cycle wall time is
                     almost purely the runtime-overhead terms of the
                     paper's Eq. (1) — the regime its scaling analysis
                     (and our cycle-fusion benchmark) targets.

``MDEngine`` selects its force evaluation via ``force_path``:

  "pallas" (default) — ANALYTIC forces: hand-derived gradients through
      the ``kernels.chain_forces`` bonded pass (bonds + angles +
      torsions + umbrella bias) and the ``kernels.lj_forces`` chain
      nonbonded pass (LJ + electrostatics, one sweep).  No autodiff
      graph: one propagate step issues ~2 fused passes instead of the
      ~60-thunk grad-of-energy subgraph.  On TPU the passes are the
      Pallas replica-grid kernels; off-TPU they are the jnp analytic
      oracles (the fast CPU path — interpret mode is a correctness
      harness, not a fast path).
  "batched" — the PR-2 autodiff path: ``jax.grad`` of the replica-major
      batched potential (analytic custom_vjp pairwise backward).  The
      tolerance oracle for the analytic path.
  "vmap" — the per-replica reference oracle: ``jax.vmap`` over
      scalar-sized single-replica programs (== ``batched=False``).  The
      bitwise-exchange-decision oracle.
  "fused" — one lean pass per BAOAB iteration: force evaluation and
      the masked B-A-O-A-B update share a single body (a replica-grid
      Pallas kernel per iteration on TPU for the dense sweep, the
      jitted fused jnp loop otherwise — ``kernels.fused_propagate``).
      Same analytic math, same noise stream, fewest ops/launches.

``batched`` still selects the energy/feature layout (replica-major
stacked gathers vs vmap-of-scalar programs); ``batched=False`` forces
``force_path="vmap"``.  All paths run a masked ``fori_loop`` over
``max_steps`` so per-replica step counts (async pattern) compile to one
program, and all fold the SAME per-replica keys, so trajectories agree
to float tolerance and exchange decisions bit-for-bit (pinned by
tests/test_batched_equivalence.py).  HarmonicEngine closes the step
loop analytically either way.

See docs/ENGINES.md for the full protocol contract, the force-path
selection table, and a worked custom engine.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

import numpy as np

from repro.kernels import default_interpret, default_use_kernel
from repro.kernels.chain_forces import kernel as chain_kernel
from repro.kernels.chain_forces import ops as chain_ops
from repro.kernels.fused_propagate import kernel as fused_kernel
from repro.kernels.lj_forces import kernel as nb_kernel
from repro.kernels.lj_forces import ops as nb_ops
from repro.md import energy as E
from repro.md import integrators as I
from repro.md import neighbors as NB
from repro.md.system import (MolecularSystem, base_positions,
                             chain_molecule, initial_positions)

FORCE_PATHS = ("pallas", "batched", "vmap", "fused")
NONBONDED_PATHS = ("dense", "sparse")
BONDED_PATHS = ("dense", "sparse")


def _any_nonfinite(state) -> jax.Array:
    """(R,) bool: replica-level NaN/inf scan — shared failure detector."""
    bad = jax.tree.map(
        lambda x: jnp.any(~jnp.isfinite(x), axis=tuple(range(1, x.ndim))),
        state)
    return functools.reduce(jnp.logical_or, jax.tree.leaves(bad))


def _kinetic_energy(vel: jax.Array, masses: jax.Array) -> jax.Array:
    """(R, N, 3) velocities -> (R,) kinetic energy.

    The energy-divergence detector keys on KINETIC energy: an integrator
    blow-up shows up as a velocity explosion (a temperature spike) one or
    more steps BEFORE positions overflow to inf/NaN, so a threshold here
    catches diverging replicas while their state is still finite — the
    regime the bare non-finite scan is blind to."""
    return 0.5 * jnp.sum(masses[None, :, None] * vel * vel, axis=(1, 2))


def _bond_overstretch(pos: jax.Array, bonds: jax.Array, r0: jax.Array,
                      max_stretch: float) -> jax.Array:
    """(R, N, 3) positions -> (R,) bool: any bond stretched past
    ``max_stretch`` x its equilibrium length (bond blow-up — SHAKE-style
    sanity check; a silently snapped chain is a failed replica even when
    every coordinate is finite)."""
    ri = pos[:, bonds[:, 0]]                    # (R, B, 3)
    rj = pos[:, bonds[:, 1]]
    r = jnp.sqrt(jnp.sum((ri - rj) ** 2, axis=-1))      # (R, B)
    return jnp.any(r > max_stretch * r0[None, :], axis=1)


class MDEngine:
    # every propagate implementation this engine can select — surfaced
    # by ``engine_capabilities`` so sweeps (benchmarks/run.py
    # cycle_fusion) enumerate paths without a hardcoded second list
    force_paths = FORCE_PATHS

    def __init__(self, system: Optional[MolecularSystem] = None,
                 dt: float = 5e-4, gamma: float = 5.0,
                 init_temperature: float = 300.0, batched: bool = True,
                 force_path: Optional[str] = None,
                 use_force_kernels: Optional[bool] = None,
                 nonbonded: str = "dense", cutoff: float = 9.0,
                 skin: float = 1.5, k_max: Optional[int] = None,
                 nlist_build: Optional[str] = None,
                 cell_capacity: Optional[int] = None,
                 bonded: str = "dense",
                 nb_pair_planes: Optional[bool] = None,
                 max_energy: Optional[float] = None,
                 max_bond_stretch: Optional[float] = None):
        """``force_path``: "pallas" (analytic, default), "batched"
        (autodiff of the replica-major potential), "vmap" (per-replica
        oracle) or "fused" (analytic force + BAOAB update in one pass
        per iteration).  ``batched=False`` implies "vmap" — requesting
        any other path with ``batched=False`` is a conflict and raises.
        ``use_force_kernels`` forces the Pallas kernels on/off for the
        analytic path (default: on only on TPU backends; off-TPU the
        analytic jnp oracle runs).

        ``nonbonded``: "dense" (default — every pair, every step, the
        oracle) or "sparse" (fixed-capacity neighbor list: O(N * k_max)
        force/energy passes over the TRUNCATED potential with radial
        ``cutoff``, lists rebuilt on device when an atom drifts more
        than ``skin / 2``).  Sparse REQUIRES the analytic force path
        (the default) and ``batched=True`` — requesting the autodiff or
        vmap oracles with it raises.  ``k_max`` / ``nlist_build``
        ("dense" | "cell") default to host-side heuristics from the
        system's reference geometry (see ``repro.md.neighbors``);
        capacity overflow is recorded in the list and surfaced per
        cycle as the ``nb_overflow`` driver stat, never silently
        ignored.  ``cell_capacity`` caps the "cell" build's per-cell
        slot count explicitly (the candidate buffer is N x 27*capacity,
        so the default occupancy heuristic can suggest a large buffer
        for dense geometries); atoms beyond a cell's capacity spill
        into the same ``nb_overflow`` accounting — an explicit cap
        bounds memory, and a too-tight one is visible in the driver
        stats, never silent.

        ``bonded``: "dense" (default — the signed-incidence GEMM
        contraction, O(N * W) per term class) or "sparse" (the
        slot-table contraction, O(N * S) with S a small topology
        constant — linear in N; see kernels/chain_forces).  Sparse
        requires the analytic force path.  Both contract the SAME
        per-edge gradients, so forces agree to float tolerance and
        exchange decisions bit-for-bit (the contraction feeds the
        integrator, not the feature pass); on TPU the Pallas kernel
        keeps its dense MXU contraction either way.

        ``nb_pair_planes``: precompute the sparse nonbonded pass's
        mixing-rule parameters (sig^2 / eps / COULOMB*qq) into the
        neighbor list at build time, dropping three per-step gathers.
        The planes path is bitwise-identical per evaluation to the
        gather path.  Default (None): enabled whenever
        ``nonbonded="sparse"`` on the jnp path — build cost is
        amortized over the list lifetime, and the per-step sweep
        becomes purely element-wise.  Only meaningful with
        ``nonbonded="sparse"``.

        ``max_energy`` / ``max_bond_stretch``: opt-in failure-detection
        thresholds broadening ``is_failed`` beyond the non-finite scan
        (docs/FAULT_TOLERANCE.md).  ``max_energy`` flags a replica whose
        KINETIC energy exceeds it (integrator blow-up = temperature
        spike before NaN); ``max_bond_stretch`` flags any bond stretched
        past that multiple of its equilibrium length (bond blow-up).
        ``None`` (default) keeps the detector off — bitwise-identical to
        the legacy NaN-only behavior.
        """
        self.system = system or chain_molecule()
        self.dt = dt
        self.gamma = gamma
        self.init_temperature = init_temperature
        self.batched = batched
        if not batched:
            if nonbonded == "sparse":
                raise ValueError(
                    "nonbonded='sparse' needs the batched analytic "
                    "path; it cannot run batched=False (the vmap "
                    "oracle)")
            if force_path not in (None, "vmap"):
                raise ValueError(
                    f"batched=False is the vmap oracle; it cannot run "
                    f"force_path={force_path!r}")
            force_path = "vmap"
        elif force_path is None:
            force_path = "pallas"
        if force_path not in FORCE_PATHS:
            raise ValueError(f"force_path must be one of {FORCE_PATHS}, "
                             f"got {force_path!r}")
        if nonbonded not in NONBONDED_PATHS:
            raise ValueError(f"nonbonded must be one of {NONBONDED_PATHS}, "
                             f"got {nonbonded!r}")
        if nonbonded == "sparse" and force_path not in ("pallas", "fused"):
            raise ValueError(
                f"nonbonded='sparse' is an analytic-force feature; it "
                f"cannot run force_path={force_path!r}")
        if bonded not in BONDED_PATHS:
            raise ValueError(f"bonded must be one of {BONDED_PATHS}, "
                             f"got {bonded!r}")
        if bonded == "sparse" and force_path not in ("pallas", "fused"):
            raise ValueError(
                f"bonded='sparse' is an analytic-force feature; it "
                f"cannot run force_path={force_path!r}")
        if nb_pair_planes and nonbonded != "sparse":
            raise ValueError(
                "nb_pair_planes=True needs nonbonded='sparse' (there is "
                "no neighbor list to carry the planes otherwise)")
        self.force_path = force_path
        self.nonbonded = nonbonded
        self.bonded = bonded
        self.max_energy = None if max_energy is None else float(max_energy)
        self.max_bond_stretch = (None if max_bond_stretch is None
                                 else float(max_bond_stretch))
        self.failure_detectors = (
            ("nonfinite",)
            + (("energy",) if self.max_energy is not None else ())
            + (("bond",) if self.max_bond_stretch is not None else ()))
        self._use_kernel = (default_use_kernel() if use_force_kernels is None
                            else use_force_kernels)
        self._interpret = default_interpret()
        self._check_kernel_limits()
        self._pack = (chain_ops.build_pack(self.system)
                      if force_path in ("pallas", "fused") else None)
        if nonbonded == "sparse":
            self.cutoff = float(cutoff)
            self.skin = float(skin)
            self.r_list = self.cutoff + self.skin
            # pair planes ride the jnp path only (the kernel gathers
            # params from its packed coordinate rows natively)
            if nb_pair_planes is None:
                nb_pair_planes = not self._use_kernel
            self._pair_params = (
                (self.system.lj_sigma, self.system.lj_eps,
                 self.system.charges) if nb_pair_planes else None)
            base = base_positions(self.system)
            mask = np.asarray(self.system.nb_mask)
            self.k_max = (NB.suggest_k_max(self.system.n_atoms, base, mask,
                                           self.r_list)
                          if k_max is None else int(k_max))
            extent = base.max(0) - base.min(0) + 2.0 * self.r_list
            self._grid_dims = NB.suggest_grid_dims(extent, self.r_list)
            self._cell_capacity = (
                int(cell_capacity) if cell_capacity is not None
                else NB.suggest_cell_capacity(base, self.r_list,
                                              self._grid_dims))
            if self._cell_capacity < 1:
                raise ValueError(f"cell_capacity must be >= 1, got "
                                 f"{self._cell_capacity}")
            if nlist_build is None:
                # occupancy-keyed choice: cells only pay when the
                # reference geometry spreads atoms thin relative to
                # r_list (see neighbors.suggest_build_method) — a raw
                # N-threshold flips compact chains to the strictly
                # slower cell build
                nlist_build = NB.suggest_build_method(
                    self.system.n_atoms, self._grid_dims,
                    self._cell_capacity)
            if nlist_build not in ("dense", "cell"):
                raise ValueError(f"nlist_build must be 'dense' or 'cell', "
                                 f"got {nlist_build!r}")
            self.nlist_build = nlist_build

    @property
    def force_kernels(self) -> str:
        """How this engine's force passes run, decided once at
        construction from the backend: ``"compiled"`` (Pallas kernels
        compiled for the chip), ``"interpret"`` (the Pallas interpreter,
        a CPU correctness harness) or ``"jnp"`` (the jnp reference
        passes).  A chip run asserts ``"compiled"``."""
        if self.force_path not in ("pallas", "fused") or not self._use_kernel:
            return "jnp"
        return "interpret" if self._interpret else "compiled"

    def _check_kernel_limits(self):
        """Refuse, at construction, a system larger than the compiled
        kernels this engine would launch hold in VMEM (limits from the
        v5e compile tests, tests/test_tpu_compile.py).  A compiled path
        never falls back to the jnp passes on its own."""
        if self.force_kernels != "compiled":
            return
        n = int(self.system.n_atoms)
        limits = [("chain_forces", chain_kernel.MAX_ATOMS)]
        if self.nonbonded == "sparse":
            limits.append(("sparse nonbonded", nb_kernel.SPARSE_MAX_ATOMS))
        elif self.force_path == "fused":
            limits.append(("fused_propagate", fused_kernel.MAX_ATOMS))
        for name, limit in limits:
            if n > limit:
                raise ValueError(
                    f"force_path={self.force_path!r} with "
                    f"nonbonded={self.nonbonded!r} runs the {name} Pallas "
                    f"kernel, which holds at most {limit} atoms in TPU "
                    f"VMEM; this system has {n}")

    # -- neighbor-list plumbing (nonbonded="sparse") -----------------------

    def _build_nlist(self, pos, prev=None):
        return NB.build_neighbor_list(
            pos, self.system.nb_mask, self.r_list, self.k_max,
            method=self.nlist_build, grid_dims=self._grid_dims,
            cell_capacity=self._cell_capacity, prev=prev,
            pair_params=self._pair_params)

    def _refresh_nlist(self, pos, nlist):
        # sync=True: one tripped replica refreshes the whole ensemble —
        # the batched build costs the same per event, and synchronized
        # skin budgets mean ~one build event per ensemble drift period
        # instead of one per replica (see neighbors.maybe_rebuild)
        return NB.maybe_rebuild(
            pos, nlist, self.system.nb_mask, self.r_list, self.skin,
            self.k_max, method=self.nlist_build,
            grid_dims=self._grid_dims,
            cell_capacity=self._cell_capacity, sync=True,
            pair_params=self._pair_params)

    def nb_stats(self, state):
        """Per-ensemble neighbor-list health scalars (fixed shape, so
        the fused cycle can stack them into its per-cycle stats):
        ``nb_overflow`` — cumulative dropped-pair count, worst replica;
        ``nb_rebuilds`` — cumulative rebuild count, worst replica."""
        if self.nonbonded != "sparse":
            from repro.core.engine import nb_zero_stats
            return nb_zero_stats()
        nl = state["nlist"]
        return {"nb_overflow": jnp.max(nl["overflow"]).astype(jnp.float32),
                "nb_rebuilds": jnp.max(nl["rebuilds"]).astype(jnp.float32)}

    # -- protocol ----------------------------------------------------------

    def init_state(self, rng: jax.Array, n_replicas: int):
        keys = jax.random.split(rng, n_replicas)

        def one(key):
            kp, kv = jax.random.split(key)
            pos = initial_positions(self.system, kp)
            vel = I.maxwell_boltzmann(kv, self.system.masses,
                                      self.init_temperature,
                                      (self.system.n_atoms, 3))
            return {"pos": pos, "vel": vel}

        state = jax.vmap(one)(keys)
        if self.nonbonded == "sparse":
            state["nlist"] = self._build_nlist(state["pos"])
        return state

    def propagate(self, state, ctrl, n_steps, rngs, max_steps: int = 0):
        """``rngs``: per-replica key array (R,) — mode-invariant."""
        max_steps = max_steps or int(jnp.max(n_steps))
        if self.force_path == "vmap":
            return self._propagate_vmap(state, ctrl, n_steps, rngs,
                                        max_steps)
        if self.force_path == "fused":
            return self._propagate_fused(state, ctrl, n_steps, rngs,
                                         max_steps)
        sys = self.system
        if self.nonbonded == "sparse":
            return self._propagate_sparse(state, ctrl, n_steps, rngs,
                                          max_steps)
        if self.force_path == "batched":
            # Replicas are independent, so the gradient of the
            # replica-summed batched potential is the stacked per-replica
            # force field — one wide backward pass instead of R small ones.
            force_fn = jax.grad(
                lambda p: -jnp.sum(E.batched_potential_energy(p, sys, ctrl)))
        else:
            force_fn = self._analytic_force_fn(ctrl)
        return I.propagate_replica_major(state, force_fn, sys.masses,
                                         ctrl["temperature"], n_steps, rngs,
                                         max_steps, self.dt, self.gamma)

    def _sparse_force_aux(self, ctrl):
        """The sparse force field with its neighbor-list aux carry:
        every evaluation runs the skin check (a conditional on-device
        rebuild) and then ONE O(N * k_max) force pass.  Shared by the
        per-pass sparse loop and the fused path, so both thread the
        identical physics + list maintenance through their iteration
        bodies."""
        sys = self.system
        salt = ctrl.get("salt")
        salt_scale = None if salt is None else 1.0 - 0.5 * salt
        u_c = ctrl.get("umbrella_center")
        u_k = ctrl.get("umbrella_k")

        def force_aux(pos, nlist):
            nlist = self._refresh_nlist(pos, nlist)
            f, _ = chain_ops.bonded_forces(pos, self._pack, u_c, u_k,
                                           use_kernel=self._use_kernel,
                                           interpret=self._interpret,
                                           sparse=self.bonded == "sparse")
            f = f + nb_ops.nonbonded_force_sparse(
                pos, sys.lj_sigma, sys.lj_eps, sys.charges,
                nlist["idx"], nlist["valid"], self.cutoff, salt_scale,
                use_kernel=self._use_kernel, interpret=self._interpret,
                pair=nlist.get("pair"))
            return f, nlist

        return force_aux

    def _propagate_sparse(self, state, ctrl, n_steps, rngs,
                          max_steps: int):
        """The sparse MD loop: the neighbor list rides the loop carry
        and comes back in the returned state, so the fused cycle scan
        threads it across cycles with zero host round-trips."""
        md_state = {"pos": state["pos"], "vel": state["vel"]}
        out, nlist = I.propagate_replica_major_aux(
            md_state, self._sparse_force_aux(ctrl), state["nlist"],
            self.system.masses, ctrl["temperature"], n_steps, rngs,
            max_steps, self.dt, self.gamma)
        out["nlist"] = nlist
        return out

    def _propagate_fused(self, state, ctrl, n_steps, rngs,
                         max_steps: int):
        """``force_path="fused"``: one lean pass per BAOAB iteration.

        Dispatch rules (docs/ENGINES.md §Force paths): on TPU with the
        dense nonbonded sweep, each iteration is ONE replica-grid
        Pallas launch (``kernels.fused_propagate``).  Off-TPU, and for
        ``nonbonded="sparse"`` (whose neighbor-list aux carry and
        ``nb_pair_planes`` ride the loop), the jitted fused jnp body
        runs — hoisted scales, in-loop jax.random noise, the
        shared ``baoab_fused_iteration`` update.  Both keep every force
        evaluation inside the loop body, so the bitwise-across-chunk-
        sizes guarantee carries over unchanged."""
        sys = self.system
        if self.nonbonded == "sparse":
            md_state = {"pos": state["pos"], "vel": state["vel"]}
            out, nlist = I.propagate_replica_major_fused(
                md_state, self._sparse_force_aux(ctrl), state["nlist"],
                sys.masses, ctrl["temperature"], n_steps, rngs,
                max_steps, self.dt, self.gamma)
            out["nlist"] = nlist
            return out
        if self._use_kernel:
            from repro.kernels.fused_propagate import ops as fused_ops
            return fused_ops.fused_propagate(
                state, self._pack, sys, ctrl, n_steps, rngs, max_steps,
                self.dt, self.gamma, interpret=self._interpret)
        force_fn = self._analytic_force_fn(ctrl)
        out, _ = I.propagate_replica_major_fused(
            {"pos": state["pos"], "vel": state["vel"]},
            lambda pos, aux: (force_fn(pos), aux), (), sys.masses,
            ctrl["temperature"], n_steps, rngs, max_steps, self.dt,
            self.gamma)
        return out

    def _analytic_force_fn(self, ctrl):
        """The fused analytic force field: one bonded pass + one
        nonbonded pass, hand-derived gradients — no autodiff graph.
        Ctrl terms the grid does not carry (T-only ladders) constant-fold
        out, exactly like the batched energy path."""
        sys = self.system
        u_c = ctrl.get("umbrella_center")
        u_k = ctrl.get("umbrella_k")
        salt = ctrl.get("salt")

        salt_scale = None if salt is None else 1.0 - 0.5 * salt

        def force_fn(pos):
            f, _ = chain_ops.bonded_forces(pos, self._pack, u_c, u_k,
                                           use_kernel=self._use_kernel,
                                           interpret=self._interpret,
                                           sparse=self.bonded == "sparse")
            return f + nb_ops.nonbonded_force(
                pos, sys.lj_sigma, sys.lj_eps, sys.charges, sys.nb_mask,
                salt_scale, use_kernel=self._use_kernel,
                interpret=self._interpret)

        return force_fn

    def _propagate_vmap(self, state, ctrl, n_steps, rngs, max_steps: int):
        """Reference oracle: vmap over single-replica programs."""
        sys = self.system
        dt, gamma = self.dt, self.gamma
        keys = rngs

        def one(pos, vel, ctrl_row, n, key):
            def u_fn(p):
                return E.potential_energy(p, sys, ctrl_row)
            force_fn = jax.grad(lambda p: -u_fn(p))
            temp = ctrl_row["temperature"]

            def body(t, carry):
                pos, vel = carry
                k = jax.random.fold_in(key, t)
                npos, nvel = I.baoab_step(pos, vel, k, force_fn, sys.masses,
                                          temp, dt, gamma)
                active = t < n
                pos = jnp.where(active, npos, pos)
                vel = jnp.where(active, nvel, vel)
                return pos, vel

            pos, vel = lax.fori_loop(0, max_steps, body, (pos, vel))
            return {"pos": pos, "vel": vel}

        return jax.vmap(one)(state["pos"], state["vel"], ctrl, n_steps, keys)

    def energy(self, state, ctrl):
        if self.batched:
            f = self.replica_features(state)
            return E.batched_reduced_energy_from_features(f, ctrl)
        sys = self.system

        def one(pos, ctrl_row):
            f = E.features(pos, sys)
            return E.reduced_energy_from_features(f, ctrl_row)

        return jax.vmap(one)(state["pos"], ctrl)

    def replica_features(self, state):
        if self.nonbonded == "sparse":
            # features of the TRUNCATED potential, via the same list the
            # propagate loop used — exchange decisions and dynamics see
            # one consistent physics (the list is fresh to within one
            # cycle's skin budget by the in-loop check)
            nl = state["nlist"]
            return E.sparse_features(state["pos"], self.system,
                                     nl["idx"], nl["valid"], self.cutoff,
                                     use_kernel=self._use_kernel,
                                     pair=nl.get("pair"),
                                     interpret=self._interpret)
        if self.batched:
            return E.batched_features(state["pos"], self.system)
        sys = self.system
        return jax.vmap(lambda p: E.features(p, sys))(state["pos"])

    def energy_pair(self, state, ctrl_a, ctrl_b):
        """u(x; ctrl_a), u(x; ctrl_b) from ONE feature pass.

        The O(N^2) pair sums in ``features`` are ctrl-independent, so the
        exchange phase's self/swap evaluation needs them only once; each
        ctrl assignment is then an O(1) reduction over the features."""
        return self.energy_pair_from_features(self.replica_features(state),
                                              ctrl_a, ctrl_b)

    def energy_pair_from_features(self, feats, ctrl_a, ctrl_b):
        """The ctrl reduction half of ``energy_pair`` — O(R) on the
        (R,)-per-field feature rows, no state access.  The sharded
        exchange path calls this on all-gathered features; ``energy_pair``
        routes through it too, so both paths reduce identically."""
        if self.batched:
            return (E.batched_reduced_energy_from_features(feats, ctrl_a),
                    E.batched_reduced_energy_from_features(feats, ctrl_b))
        red = jax.vmap(E.reduced_energy_from_features)
        return red(feats, ctrl_a), red(feats, ctrl_b)

    def cross_energy(self, state, ctrl_grid):
        """(R, C) matrix u_c(x_i) via the feature decomposition.

        Features are computed once per replica (O(R N^2), one batched
        pass); matrix assembly is the tiled ``exchange_matrix`` kernel
        (jnp oracle by default)."""
        return self.cross_energy_from_features(self.replica_features(state),
                                               ctrl_grid)

    def cross_energy_from_features(self, feats, ctrl_grid):
        """Matrix assembly half of ``cross_energy`` (feature rows ->
        (R, C)); state-free, so the sharded Gibbs exchange can run it
        replicated on gathered features."""
        from repro.kernels.exchange_matrix import ops as xops
        return xops.exchange_matrix(feats, ctrl_grid)

    def is_failed(self, state):
        bad = _any_nonfinite(state)
        # threshold detectors compile only when declared: the default
        # engine's compiled program (and its HLO op census) is unchanged
        if self.max_energy is not None:
            ke = _kinetic_energy(state["vel"], self.system.masses)
            bad = bad | (ke > self.max_energy)
        if self.max_bond_stretch is not None:
            bad = bad | _bond_overstretch(state["pos"], self.system.bonds,
                                          self.system.bond_r0,
                                          self.max_bond_stretch)
        return bad


class _TOnlyFeatureAPI:
    """Shared exchange reductions for T-only engines: u(x; ctrl) =
    beta(ctrl) * U(x), so the single feature is the bare potential.
    Subclasses provide ``replica_features(state) -> {"u": (R,)}``; this
    mixin supplies the four reduction entry points (including the
    state-free ``*_from_features`` forms ``run_sharded`` requires) so
    the T-only reduction lives in exactly one place."""

    def energy_pair(self, state, ctrl_a, ctrl_b):
        return self.energy_pair_from_features(self.replica_features(state),
                                              ctrl_a, ctrl_b)

    def energy_pair_from_features(self, feats, ctrl_a, ctrl_b):
        return ctrl_a["beta"] * feats["u"], ctrl_b["beta"] * feats["u"]

    def cross_energy(self, state, ctrl_grid):
        return self.cross_energy_from_features(self.replica_features(state),
                                               ctrl_grid)

    def cross_energy_from_features(self, feats, ctrl_grid):
        return feats["u"][:, None] * ctrl_grid["beta"][None, :]  # (R, C)


class HarmonicEngine(_TOnlyFeatureAPI):
    """Replicas in a 3-D harmonic well, propagated by the EXACT
    Ornstein-Uhlenbeck solution of overdamped Langevin dynamics:

        x_{t+1} = a x_t + sigma(T) xi_t,   a = exp(-gamma dt),
        sigma(T)^2 = (kB T / k_spring) (1 - a^2)

    ``n`` masked steps fold into one closed-form update (prefix products
    over the per-step decay + accumulated noise), so ``propagate``
    compiles to ~a dozen ops regardless of step count.  That makes this
    the overhead-characterization engine: with T_MD ~ 0, cycle wall time
    isolates T_data + T_RepEx_over + T_runtime_over — and the stationary
    distribution N(0, kB T / k_spring) makes exchange statistics
    analytically checkable.  Temperature exchange only.
    """

    KB = I.KB
    # the only ctrl fields this engine reads (skips the umbrella/salt
    # gathers in the exchange/propagate hot path)
    ctrl_keys = ("temperature", "beta")

    def __init__(self, n_dim: int = 3, k_spring: float = 1.0,
                 dt: float = 1e-2, gamma: float = 1.0,
                 init_temperature: float = 300.0, batched: bool = True):
        self.n_dim = n_dim
        self.k_spring = k_spring
        self.dt = dt
        self.gamma = gamma
        self.init_temperature = init_temperature
        self.batched = batched

    def init_state(self, rng, n_replicas: int):
        std = (self.KB * self.init_temperature / self.k_spring) ** 0.5
        x = jax.random.normal(rng, (n_replicas, self.n_dim)) * std
        return {"x": x}

    def propagate(self, state, ctrl, n_steps, rngs, max_steps: int = 0):
        max_steps = max_steps or int(jnp.max(n_steps))
        a = jnp.exp(-self.gamma * self.dt)
        k_spring, kb = self.k_spring, self.KB
        ts = jnp.arange(max_steps)

        if not self.batched:
            def one(x, ctrl_row, n, key):
                var = kb * ctrl_row["temperature"] / k_spring
                sigma = jnp.sqrt(var * (1.0 - a * a))
                xi = jax.vmap(lambda t: jax.random.normal(
                    jax.random.fold_in(key, t), x.shape))(ts)     # (S, D)
                active = ts < n
                decay = jnp.where(active, a, 1.0)                 # (S,)
                noise = jnp.where(active[:, None], sigma * xi, 0.0)
                # x_S = (prod_i f_i) x_0 + sum_i (prod_{j>i} f_j) g_i
                cp = jnp.cumprod(decay[::-1])[::-1]               # prod_{j>=i}
                suffix = jnp.concatenate([cp[1:], jnp.ones(1)])   # prod_{j>i}
                return {"x": cp[0] * x
                        + jnp.sum(suffix[:, None] * noise, axis=0)}

            return jax.vmap(one)(state["x"], ctrl, n_steps, rngs)

        x = state["x"]                                            # (R, D)
        n_rep = x.shape[0]
        var = kb * ctrl["temperature"] / k_spring                 # (R,)
        sigma = jnp.sqrt(var * (1.0 - a * a))
        xi = jax.vmap(lambda key: jax.vmap(lambda t: jax.random.normal(
            jax.random.fold_in(key, t), x.shape[1:]))(ts))(rngs)  # (R, S, D)
        active = ts[None, :] < n_steps[:, None]                   # (R, S)
        decay = jnp.where(active, a, 1.0)
        noise = jnp.where(active[..., None],
                          sigma[:, None, None] * xi, 0.0)
        cp = jnp.cumprod(decay[:, ::-1], axis=1)[:, ::-1]
        suffix = jnp.concatenate([cp[:, 1:], jnp.ones((n_rep, 1))], axis=1)
        return {"x": cp[:, 0:1] * x
                + jnp.sum(suffix[..., None] * noise, axis=1)}

    def _potential_stack(self, x):
        """(R, D) -> (R,)."""
        if self.batched:
            return 0.5 * self.k_spring * jnp.sum(x * x, axis=-1)
        return jax.vmap(
            lambda xi: 0.5 * self.k_spring * jnp.sum(xi * xi))(x)

    def energy(self, state, ctrl):
        return ctrl["beta"] * self._potential_stack(state["x"])

    def replica_features(self, state):
        """T-only exchange feature: the bare potential, (R,)."""
        return {"u": self._potential_stack(state["x"])}

    def is_failed(self, state):
        return _any_nonfinite(state)


class LJEngine(_TOnlyFeatureAPI):
    """Lennard-Jones fluid; temperature exchange only (the engine-swap
    demonstration).  Forces optionally via the Pallas kernel — with
    ``batched=True`` (default) the kernel runs with a leading REPLICA
    grid dimension, so all R fluids stream through one kernel launch."""

    ctrl_keys = ("temperature", "beta")

    def __init__(self, n_particles: int = 64, box: float = 12.0,
                 dt: float = 2e-3, gamma: float = 2.0,
                 use_pallas: bool = False, batched: bool = True,
                 max_energy: Optional[float] = None):
        self.n = n_particles
        self.box = box
        self.dt = dt
        self.gamma = gamma
        self.use_pallas = use_pallas
        self.batched = batched
        self.masses = jnp.full(n_particles, 39.9)    # argon
        self.sigma = 3.4
        self.eps = 0.238
        # opt-in kinetic-energy divergence threshold (None = NaN-only)
        self.max_energy = None if max_energy is None else float(max_energy)
        self.failure_detectors = (
            ("nonfinite",)
            + (("energy",) if self.max_energy is not None else ()))

    def _potential(self, pos):
        """Single-replica (N, 3) -> scalar (reference path)."""
        if self.use_pallas:
            from repro.kernels.lj_forces import ops as ljops
            return ljops.lj_energy(pos, self.sigma, self.eps, self.box)
        from repro.kernels.lj_forces import ref as ljref
        return ljref.lj_energy(pos, self.sigma, self.eps, self.box)

    def _potential_stack(self, pos):
        """Replica stack (R, N, 3) -> (R,)."""
        if not self.batched:
            return jax.vmap(self._potential)(pos)
        if self.use_pallas:
            from repro.kernels.lj_forces import ops as ljops
            return ljops.lj_energy_batched(pos, self.sigma, self.eps,
                                           self.box)
        from repro.kernels.lj_forces import ref as ljref
        return ljref.lj_energy(pos, self.sigma, self.eps, self.box)

    def init_state(self, rng, n_replicas: int):
        keys = jax.random.split(rng, n_replicas)
        side = int(jnp.ceil(self.n ** (1 / 3)))
        grid = jnp.stack(jnp.meshgrid(*[jnp.arange(side)] * 3,
                                      indexing="ij"), -1).reshape(-1, 3)
        base = (grid[: self.n] + 0.5) * (self.box / side)

        def one(key):
            kp, kv = jax.random.split(key)
            pos = base + jax.random.normal(kp, (self.n, 3)) * 0.05
            vel = I.maxwell_boltzmann(kv, self.masses, 120.0, (self.n, 3))
            return {"pos": pos, "vel": vel}

        return jax.vmap(one)(keys)

    def _force_stack(self, pos):
        """Analytic forces for the stack — the direct force pass (one
        kernel launch / one jnp pairwise sweep), not autodiff of the
        energy: the hot loop never materializes the energy forward."""
        if self.use_pallas:
            from repro.kernels.lj_forces import ops as ljops
            return ljops.lj_forces_batched(pos, self.sigma, self.eps,
                                           self.box)
        from repro.kernels.lj_forces import ref as ljref
        return ljref.lj_forces(pos, self.sigma, self.eps, self.box)

    def propagate(self, state, ctrl, n_steps, rngs, max_steps: int = 0):
        max_steps = max_steps or int(jnp.max(n_steps))
        if not self.batched:
            return self._propagate_vmap(state, ctrl, n_steps, rngs,
                                        max_steps)
        temp = ctrl["temperature"]
        # The shared force is evaluated at the wrapped positions; the
        # vmap oracle evaluates its trailing half-B at the pre-wrap
        # positions, which agrees up to fp rounding (the minimum-image
        # force is wrap-invariant).
        return I.propagate_replica_major(state, self._force_stack,
                                         self.masses, temp, n_steps, rngs,
                                         max_steps, self.dt, self.gamma,
                                         box=self.box)

    def _propagate_vmap(self, state, ctrl, n_steps, rngs, max_steps: int):
        """Reference oracle: vmap over single-replica programs."""
        keys = rngs
        force_fn = jax.grad(lambda p: -self._potential(p))

        def one(pos, vel, ctrl_row, n, key):
            temp = ctrl_row["temperature"]

            def body(t, carry):
                pos, vel = carry
                k = jax.random.fold_in(key, t)
                npos, nvel = I.baoab_step(pos, vel, k, force_fn, self.masses,
                                          temp, self.dt, self.gamma)
                npos = jnp.mod(npos, self.box)
                active = t < n
                return (jnp.where(active, npos, pos),
                        jnp.where(active, nvel, vel))

            pos, vel = lax.fori_loop(0, max_steps, body, (pos, vel))
            return {"pos": pos, "vel": vel}

        return jax.vmap(one)(state["pos"], state["vel"], ctrl, n_steps, keys)

    def energy(self, state, ctrl):
        return ctrl["beta"] * self._potential_stack(state["pos"])

    def replica_features(self, state):
        """T-only exchange feature: the bare potential, (R,) — one
        O(N^2) evaluation serves both exchange assignments."""
        return {"u": self._potential_stack(state["pos"])}

    def is_failed(self, state):
        bad = _any_nonfinite(state)
        if self.max_energy is not None:
            ke = _kinetic_energy(state["vel"], self.masses)
            bad = bad | (ke > self.max_energy)
        return bad
