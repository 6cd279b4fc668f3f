"""REMDDriver — the top-level RepEx runtime.

Host-side orchestration (the paper's EMM/AMM roles), device-side compiled
cycles.  Per-cycle wall time is decomposed exactly as the paper's Eq. (1):

    T_c = T_MD + T_EX + T_data + T_RepEx_over + T_runtime_over

  T_MD           — compiled propagate phase
  T_EX           — compiled exchange phase
  T_data         — host<->device movement of assignments/energies
  T_RepEx_over   — host-side task preparation (scheduling, ladder bookkeeping)
  T_runtime_over — dispatch/launch overhead of the compiled step (the
                   RADICAL-Pilot analogue in our stack is the XLA dispatch)

Two execution paths pay these terms very differently:

``run()``        — one dispatch per cycle, with 4+ host<->device syncs
                   (cycle fetch for scheduling, block on the step, failure
                   fetch, stats fetch).  Every cycle pays the FULL
                   T_data + T_RepEx_over + T_runtime_over.

``run_fused()``  — a single jitted ``lax.scan`` runs ``chunk_cycles = K``
                   complete propagate -> exchange -> detect -> recover
                   cycles per dispatch with zero host round-trips inside
                   the chunk.  Sweep scheduling becomes a device gather
                   (stacked pair tables), failure recovery carries the
                   backup state in the scan carry, and per-cycle stats
                   accumulate into (K,)-shaped device arrays fetched ONCE
                   per chunk.  T_MD and T_EX are unchanged, while
                   T_data, T_RepEx_over and T_runtime_over are amortized
                   by 1/K — the overhead terms Eq. (1) blames for poor
                   scaling shrink toward zero as K grows, which is what
                   lets short-cycle workloads (md_steps_per_cycle <= 10)
                   run at hardware speed.  Discrete trajectories
                   (assignments, acceptance, failure counts) are
                   identical to ``run()`` for the same seed; float state
                   matches to XLA-fusion rounding (~1 ulp) and is
                   bitwise-invariant across chunk sizes.

A third path scales the FUSED chunk across devices:

``run_sharded()`` — ``run_fused`` with the replica axis block-sharded
                   over a ``("replica",)`` mesh via ``shard_map`` (the
                   paper's spatial Execution-Mode dimension made a mesh
                   shape).  Propagate, feature AND exchange reductions
                   are shard-local; per sweep only O(R / n_shards)
                   exchange scalars and failure flags hop the ladder
                   ring via ``lax.ppermute`` halos (positions never
                   cross devices; ``cfg.exchange_comm = "gather"``
                   selects the legacy all-gather wire).  The swap
                   decision is computed replicated from the
                   reassembled rows, so the discrete trajectory is
                   bitwise-identical to ``run_fused`` on one device.
                   T_MD and the exchange reduction drop by ~1/n_shards
                   while T_EX gains a ring of tiny permutes per cycle
                   (Eq. (1)'s T_data, between devices instead of
                   host<->device).  See docs/SCALING.md.

The driver supports both patterns, both execution modes, failure
injection/recovery, and periodic ensemble checkpointing (restart-able,
mesh-independent; the fused and sharded paths checkpoint at chunk
boundaries).

Every history entry also records the post-cycle ``assignment`` row (the
discrete RE trajectory — what the statistical-correctness suite analyses
for rung occupancy and per-pair acceptance) and the engine's
neighbor-list health counters ``nb_overflow`` / ``nb_rebuilds`` (zero
for dense engines): a sparse run that dropped pairs to capacity is
visible in the stats, never silent.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

import dataclasses

import numpy as np

from repro.config import RepExConfig
from repro.core import failures as F
from repro.core import patterns
from repro.core.controls import ControlGrid, build_grid
from repro.core.engine import NB_STAT_KEYS, engine_capabilities
from repro.core.ensemble import Ensemble, make_ensemble
from repro.core.modes import auto_mode
from repro.ckpt import CheckpointError, CheckpointManager, load_checkpoint
from repro.obs import build_report
from repro.obs.spans import span

# checkpoint 'extra' schema carried alongside the ensemble payload (the
# host-side driver state resume() restores); bump when the layout changes
CKPT_DRIVER_SCHEMA = 1

# config fields that do NOT affect the per-cycle trajectory — a resume may
# differ in these (e.g. extending a run's length) without invalidating the
# bitwise-resume contract
_CFG_RESUME_EXEMPT = ("n_cycles",)


class REMDDriver:
    def __init__(self, engine, cfg: RepExConfig, mesh=None,
                 slots: Optional[int] = None, ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 0, failure_rate: float = 0.0,
                 telemetry=None):
        self.engine = engine
        self.capabilities = engine_capabilities(engine)
        # can nb_stats ever be nonzero?  (an engine reporting a dense
        # nonbonded path declares its own counters dead)
        self._nb_live = (self.capabilities["nb_stats"]
                         and self.capabilities["nonbonded"] != "dense")
        self.cfg = cfg
        self.mesh = mesh
        self.grid: ControlGrid = build_grid(cfg)
        n = self.grid.n_ctrl
        if slots is None:
            slots = n * cfg.cores_per_replica
        eff_slots = max(slots // max(cfg.cores_per_replica, 1), 1)
        if cfg.execution_mode == "mode1":
            self.execution = {"mode": "mode1", "n_waves": 1}
        elif cfg.execution_mode == "mode2":
            self.execution = auto_mode(n, eff_slots)
            if self.execution["mode"] != "mode2":      # force at least 2 waves
                # mode2 pads non-dividing waves, so 2 waves always works
                self.execution = {"mode": "mode2", "n_waves": min(2, n)}
        else:
            self.execution = auto_mode(n, eff_slots)
        self.failure_rate = failure_rate
        self.ckpt = (CheckpointManager(ckpt_dir, every=ckpt_every)
                     if ckpt_dir else None)
        self._compiled: Dict[Any, Any] = {}
        self.history: List[Dict[str, float]] = []
        self.acceptance = {f"dim{d.index}": [0.0, 0.0]
                           for d in self.grid.dims}
        # observability (repro.obs): optional Telemetry accumulator.
        # ``telemetry=None`` is a TRUE no-op — not one compiled op
        # differs from an un-instrumented driver (tests/test_telemetry).
        self.telemetry = telemetry
        self.last_report = None
        self._wire_budgets: Dict[int, Any] = {}
        # host-span bookkeeping: chunks run over the driver's lifetime,
        # and the chunk functions that have run at least once
        self._chunks_run = 0
        self._dispatched: set = set()
        # (backup, fail_key) restored by resume()/restore(), consumed by
        # the next run*() call so the scan carry continues bit-exactly
        self._resume_carry = None

    # -- telemetry plumbing ------------------------------------------------

    @property
    def _tel(self):
        """The live telemetry accumulator, or None when observability is
        off (absent or disabled — both compile the identical program)."""
        t = self.telemetry
        return t if (t is not None and t.enabled) else None

    @property
    def _obs_rows(self) -> bool:
        """Carry the per-pair attempt/accept rows in cycle stats?  Part
        of every compiled-fn cache key that consumes it."""
        t = self._tel
        return bool(t is not None and t.exchange_counters)

    # -- compiled cycle factory (one per dim x parity x pattern) ----------

    def _cycle_fn(self, dim_index: int, parity: int):
        rows = self._obs_rows
        key = (dim_index, parity, self.cfg.pattern, rows)
        if key in self._compiled:
            return self._compiled[key]
        cfg = self.cfg
        if cfg.pattern == "asynchronous":
            fn = functools.partial(
                patterns.async_cycle, self.engine, self.grid,
                md_steps=cfg.md_steps_per_cycle,
                window_steps=max(int(cfg.md_steps_per_cycle
                                     * cfg.async_window), 1),
                dim_index=dim_index, parity=parity,
                scheme=cfg.exchange_scheme, execution=self.execution,
                mesh=self.mesh, telemetry_rows=rows)
        else:
            fn = functools.partial(
                patterns.sync_cycle, self.engine, self.grid,
                md_steps=cfg.md_steps_per_cycle,
                dim_index=dim_index, parity=parity,
                scheme=cfg.exchange_scheme, execution=self.execution,
                mesh=self.mesh, telemetry_rows=rows)
        jitted = jax.jit(lambda ens: fn(ens))
        self._compiled[key] = jitted
        return jitted

    # -- public API --------------------------------------------------------

    def init(self, seed: Optional[int] = None) -> Ensemble:
        rng = jax.random.key(self.cfg.seed if seed is None else seed)
        hetero = self.cfg.pattern == "asynchronous"
        return make_ensemble(self.engine, rng, self.grid.n_ctrl,
                             hetero_speed=hetero)

    def run(self, ens: Ensemble, n_cycles: Optional[int] = None,
            verbose: bool = False) -> Ensemble:
        """The legacy per-cycle path: one dispatch + 4 host syncs per cycle.

        Synchronization contract: propagate is per-replica (per-wave
        under Mode II), the exchange sweep is per-ensemble, and the
        HOST synchronizes with the device once per cycle — this path
        pays Eq. (1)'s T_data + T_RepEx_over + T_runtime_over in full
        every cycle (the paper's per-cycle pilot loop, §Eq. (1)).  Kept
        as the semantics oracle for ``run_fused``/``run_sharded``.
        """
        n_cycles = n_cycles or self.cfg.n_cycles
        n_dims = len(self.grid.dims)
        # Backup carry for relaunch recovery: a reference is enough — JAX
        # arrays are immutable, so the snapshot can never be mutated out
        # from under us.  The carry only advances on clean cycles.
        backup, fail_key = self._start_carry(ens)
        dr = self._detect_recover_fn()

        for c in range(n_cycles):
            t0 = time.perf_counter()
            cyc = int(jax.device_get(ens.cycle))
            dim_index = cyc % n_dims
            parity = (cyc // n_dims) % 2
            step = self._cycle_fn(dim_index, parity)
            t_prep = time.perf_counter() - t0        # T_RepEx_over

            # (optional) failure injection between cycles
            if self.failure_rate > 0:
                fail_key, k = jax.random.split(fail_key)
                ens = F.inject_failures(ens, k, self.failure_rate)

            t1 = time.perf_counter()
            new_ens, stats = step(ens)
            jax.block_until_ready(new_ens.assignment)
            t_step = time.perf_counter() - t1        # T_MD + T_EX fused
            # nb counters are read from the PRE-recovery state, exactly
            # like the fused path (fused_cycle stats are computed before
            # detect_recover): a replica that overflowed and then failed
            # still reports its overflow even after relaunch rewinds it
            nb_state = new_ens.state

            # failure detection + escalation + recovery: the SAME jitted
            # detect_recover the fused scan body runs (one code path, so
            # the escalation ladder cannot drift between run paths)
            t2 = time.perf_counter()
            new_ens, backup, esc = dr(new_ens, backup)
            esc = {k: int(v) for k, v in jax.device_get(esc).items()}
            t_recover = time.perf_counter() - t2

            # bookkeeping (T_data: pull scalars to host)
            t3 = time.perf_counter()
            dkey = f"dim{dim_index}"
            s = jax.device_get(stats[dkey])
            self.acceptance[dkey][0] += float(s["accepted"])
            self.acceptance[dkey][1] += float(s["attempted"])
            # engines whose nb_stats can only ever report zeros (no
            # neighbor list: dense MD, harmonic, ...) skip the
            # per-cycle dispatch + device round-trip entirely
            if self._nb_live:
                nb = jax.device_get(
                    patterns.nb_health(self.engine, nb_state))
                nb = {k: float(v) for k, v in nb.items()}
            else:
                nb = dict.fromkeys(NB_STAT_KEYS, 0.0)
            assignment = jax.device_get(new_ens.assignment)
            pair_rows = (jax.device_get((stats["pair_attempt"],
                                         stats["pair_accept"]))
                         if "pair_attempt" in stats else (None, None))
            t_data = time.perf_counter() - t3

            self.history.append({
                "cycle": cyc, "dim": dim_index,
                "t_step": t_step, "t_prep": t_prep,
                "t_recover": t_recover, "t_data": t_data,
                "accept": float(s["accepted"]),
                "attempt": float(s["attempted"]),
                "failed": esc["failed"],
                "esc_relaunch": esc["esc_relaunch"],
                "esc_reinit": esc["esc_reinit"],
                "esc_dead": esc["esc_dead"],
                "assignment": assignment,
                "nb_overflow": float(nb["nb_overflow"]),
                "nb_rebuilds": float(nb["nb_rebuilds"]),
            })
            ens = new_ens

            tel = self._tel
            if tel is not None:
                tel.note_cycles(
                    cycles=[cyc], dims=[dim_index],
                    assignments=assignment[None],
                    n_dims=n_dims, n_ctrl=self.grid.n_ctrl,
                    pair_attempt=pair_rows[0], pair_accept=pair_rows[1],
                    t_cycle=t_step, t_data=t_data, t_prep=t_prep)

            if self.ckpt is not None:
                self._save_ckpt(cyc, ens, backup, fail_key)
            if verbose:
                acc = (s["accepted"] / max(s["attempted"], 1)) * 100
                print(f"cycle {cyc:4d} dim {dim_index} "
                      f"acc {acc:5.1f}%  t {t_step*1e3:7.1f} ms")
        self.last_report = build_report(self, "run")
        return ens

    # -- fused multi-cycle path -------------------------------------------

    def _chunk_scan(self, chunk_cycles: int, axis_name=None,
                    n_shards: int = 1):
        """The K-cycle scan body shared by the fused AND sharded paths.

        ONE builder so the two paths cannot drift: the carry protocol
        (ensemble, recovery backup, failure key), the
        inject -> cycle -> detect/recover order, and the per-cycle ys
        dict consumed by ``_chunk_loop`` are defined here exactly once.
        ``axis_name=None`` is the single-mesh fused path;
        ``axis_name="replica"`` runs the same body per shard (local
        propagate, halo exchange, sharded recovery).  The replicated
        failure row produced by the sharded exchange rides the stats
        dict as ``"_fail_row"`` — popped HERE, before the ys enter the
        scan, and handed to recovery so the failure mask crosses
        devices exactly once per cycle.
        """
        cfg = self.cfg
        policy = "relaunch" if cfg.relaunch_failed else "continue"
        inject = self.failure_rate > 0
        window_steps = max(int(cfg.md_steps_per_cycle * cfg.async_window), 1)
        sharded = axis_name is not None
        obs_rows = self._obs_rows

        def one_cycle(carry, _):
            ens, backup, fail_key = carry
            if inject:
                with jax.named_scope("inject"):
                    fail_key, k = jax.random.split(fail_key)
                    ens = F.inject_failures(ens, k, self.failure_rate,
                                            axis_name=axis_name,
                                            n_shards=n_shards)
            cyc = ens.cycle
            new_ens, stats = patterns.fused_cycle(
                self.engine, self.grid, ens, pattern=cfg.pattern,
                md_steps=cfg.md_steps_per_cycle,
                window_steps=window_steps, scheme=cfg.exchange_scheme,
                execution=self.execution,
                mesh=None if sharded else self.mesh,
                axis_name=axis_name, n_shards=n_shards,
                exchange_comm=cfg.exchange_comm,
                telemetry_rows=obs_rows)
            fail_row = stats.pop("_fail_row", None)
            with jax.named_scope("detect_recover"):
                if sharded:
                    new_ens, backup, esc = F.detect_recover_sharded(
                        self.engine, new_ens, policy, backup, axis_name,
                        n_shards, fail_row=fail_row,
                        relaunch_budget=cfg.relaunch_budget)
                else:
                    new_ens, backup, esc = F.detect_recover(
                        self.engine, new_ens, policy, backup,
                        relaunch_budget=cfg.relaunch_budget)
            ys = dict(stats, cycle=cyc, **esc)
            return (new_ens, backup, fail_key), ys

        def chunk(ens, backup, fail_key):
            (ens, backup, fail_key), ys = jax.lax.scan(
                one_cycle, (ens, backup, fail_key), xs=None,
                length=chunk_cycles)
            return ens, backup, fail_key, ys

        return chunk

    def _fused_chunk_fn(self, chunk_cycles: int):
        """Jitted scan over ``chunk_cycles`` complete cycles (cached)."""
        key = ("fused", chunk_cycles, self.failure_rate, self._obs_rows)
        if key in self._compiled:
            return self._compiled[key]
        jitted = jax.jit(self._chunk_scan(chunk_cycles))
        self._compiled[key] = jitted
        return jitted

    def run_fused(self, ens: Ensemble, n_cycles: Optional[int] = None,
                  chunk_cycles: int = 16, verbose: bool = False) -> Ensemble:
        """``run()`` with K cycles fused per dispatch (see module docstring).

        Semantically identical to ``run()`` — same trajectories, same
        ``history``/``acceptance`` bookkeeping — but the per-cycle overhead
        terms of Eq. (1) are paid once per chunk instead of once per cycle.
        Checkpointing happens at chunk boundaries (a chunk that crosses the
        cadence saves its final state).

        Synchronization contract: identical to ``run()`` inside a cycle
        (per-replica propagate, per-ensemble exchange); the HOST only
        synchronizes once per K-cycle chunk.  Implements the paper's
        overhead-amortization argument (§Eq. (1)) on a single device /
        default mesh; ``run_sharded`` is the same chunk distributed over
        a replica mesh.
        """
        if chunk_cycles < 1:
            raise ValueError(f"chunk_cycles must be >= 1, got {chunk_cycles}")
        with span("start"):
            backup, fail_key = self._start_carry(ens)
            c0 = int(jax.device_get(ens.cycle))
        ens = self._chunk_loop(ens, backup, fail_key, c0,
                               n_cycles or self.cfg.n_cycles, chunk_cycles,
                               verbose, self._fused_chunk_fn)
        with span("report"):
            self.last_report = build_report(self, "fused", chunk_cycles)
        return ens

    # -- replica-sharded multi-device path --------------------------------

    def _sharded_chunk_fn(self, chunk_cycles: int, mesh, ens: Ensemble):
        """Jitted shard_map(scan) over ``chunk_cycles`` cycles (cached).

        The whole K-cycle scan lives INSIDE one ``shard_map`` over the
        mesh's ``"replica"`` axis: the carry (local state block, local
        backup block, replicated control plane) never leaves its device
        between cycles, and the per-cycle collectives (feature rows +
        failure masks, see ``patterns.fused_cycle``) compile into the
        scan body.
        """
        from jax.sharding import PartitionSpec as P

        from repro.sharding import ensemble_specs

        n_shards = mesh.shape["replica"]
        # the mesh's device identity is part of the key: the jitted
        # shard_map closes over the mesh, so two same-shaped meshes on
        # different device sets must not share a cache entry
        devs = tuple(d.id for d in mesh.devices.flat)
        tel = self._tel
        wire = bool(tel is not None and tel.wire_ledger)
        key = ("sharded", chunk_cycles, self.failure_rate, n_shards, devs,
               self._obs_rows, wire)
        if key in self._compiled:
            return self._compiled[key]
        chunk = self._chunk_scan(chunk_cycles, axis_name="replica",
                                 n_shards=n_shards)
        espec = ensemble_specs(ens)
        # check_vma=False: the replicated outputs (assignment, stats, ...)
        # come out of all_gather-fed replicated math, which shard_map's
        # static varying-axes checker cannot infer through lax.scan
        body = jax.shard_map(chunk, mesh=mesh,
                             in_specs=(espec, espec.state, P()),
                             out_specs=(espec, espec.state, P(), P()),
                             check_vma=False)
        jitted = jax.jit(body)
        if wire:
            # wire ledger: AOT-compile the chunk (lower -> compile) so
            # the compiled HLO is in hand for a collective census, and
            # use THAT executable as the step function — one compile,
            # not two, and byte-identical code to the jit path (the
            # ledger is a static census of the program that actually
            # runs, scaled by invocations in _chunk_loop).
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch.hlo_analysis import collective_budget
            fk = jax.device_put(jax.random.key(0),
                                NamedSharding(mesh, PartitionSpec()))
            compiled = jitted.lower(ens, ens.state, fk).compile()
            self._wire_budgets[chunk_cycles] = collective_budget(
                compiled.as_text())
            jitted = compiled
        self._compiled[key] = jitted
        return jitted

    def run_sharded(self, ens: Ensemble, mesh=None,
                    n_cycles: Optional[int] = None, chunk_cycles: int = 16,
                    verbose: bool = False) -> Ensemble:
        """``run_fused()`` with the replica axis sharded over a mesh.

        ``mesh`` must carry a ``"replica"`` axis whose size divides the
        replica count (``launch.mesh.make_replica_mesh``); by default the
        largest usable device count is taken.  Each device owns a
        contiguous block of R / n_shards replicas — the paper's spatial
        Execution-Mode dimension (§Execution Modes) realized as a mesh
        shape; Mode II's ``n_waves`` still time-multiplexes WITHIN each
        shard's block (see ``repro.core.modes``).

        Synchronization contract: propagate and feature passes are
        per-replica and fully shard-local; the exchange is the one
        per-ensemble phase and (with the default
        ``cfg.exchange_comm="halo"``) communicates exactly the
        shard-local energy rows + failure flags over a static
        collective-permute ring — O(R / n_shards) scalars per shard per
        hop, no all_gather of per-replica feature rows; ``"gather"``
        keeps the legacy replicated wire.  Positions never cross
        devices either way; the host synchronizes once per chunk, as in
        ``run_fused``.
        Discrete trajectories (assignments, acceptance, failures,
        nb-counters) are bitwise-identical to ``run_fused`` on ANY mesh
        shape, including the 1-shard mesh (tests/test_sharded.py pins
        this and the no-position-gather property).

        Requires the engine's split feature API (``replica_features`` +
        ``energy_pair_from_features``; ``cross_energy_from_features``
        for the matrix scheme) — see ``repro.core.engine``.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_replica_mesh
        from repro.sharding import ensemble_shardings

        if chunk_cycles < 1:
            raise ValueError(f"chunk_cycles must be >= 1, got {chunk_cycles}")
        R = self.grid.n_ctrl
        if mesh is None:
            from repro.launch.mesh import best_replica_shards
            mesh = make_replica_mesh(best_replica_shards(R))
        if "replica" not in mesh.shape:
            raise ValueError(f"run_sharded needs a mesh with a 'replica' "
                             f"axis, got axes {tuple(mesh.shape)}")
        n_shards = mesh.shape["replica"]
        if R % n_shards:
            raise ValueError(f"replica count {R} is not divisible by the "
                             f"mesh's {n_shards} shards")
        caps = self.capabilities
        needed = ["replica_features", "energy_pair_from_features"]
        if self.cfg.exchange_scheme == "matrix":
            needed.append("cross_energy_from_features")
        missing = [c for c in needed if not caps[c]]
        if missing:
            raise TypeError(
                f"engine {type(self.engine).__name__} lacks the feature "
                f"API required by run_sharded: {missing} (see "
                f"repro.core.engine optional extensions)")

        shardings = ensemble_shardings(mesh, ens)
        with span("start"):
            ens = jax.device_put(ens, shardings)
            # a resumed carry may live on the host / a DIFFERENT mesh
            # (elastic restart): place it like a fresh one — backup
            # shards with the state, the failure key is replicated
            backup, fail_key = self._start_carry(ens)
            backup = jax.device_put(backup, shardings.state)
            fail_key = jax.device_put(fail_key, NamedSharding(mesh, P()))
            c0 = int(jax.device_get(ens.cycle))
        ens = self._chunk_loop(
            ens, backup, fail_key, c0, n_cycles or self.cfg.n_cycles,
            chunk_cycles, verbose,
            lambda k: self._sharded_chunk_fn(k, mesh, ens))
        with span("report"):
            self.last_report = build_report(self, "sharded", chunk_cycles)
        return ens

    # -- the chunked host loop shared by run_fused / run_sharded ----------

    def _chunk_loop(self, ens: Ensemble, backup, fail_key, c0: int,
                    n_cycles: int, chunk_cycles: int, verbose: bool,
                    step_for) -> Ensemble:
        """Drive ``step_for(k)`` chunk functions for ``n_cycles`` cycles
        from cycle ``c0``, fetching stats once per chunk and keeping
        ``history``/``acceptance``/checkpoint bookkeeping identical across
        the fused and sharded paths.  Each chunk runs inside the host
        spans of ``repro.obs.spans``."""
        done = 0
        while done < n_cycles:
            k = min(chunk_cycles, n_cycles - done)
            with span("chunk", chunk=self._chunks_run, cycles=k):
                ens, backup, fail_key = self._one_chunk(
                    ens, backup, fail_key, c0 + done, k, verbose,
                    step_for)
            self._chunks_run += 1
            done += k
        return ens

    def _one_chunk(self, ens: Ensemble, backup, fail_key, c_start: int,
                   k: int, verbose: bool, step_for):
        """One K-cycle chunk from cycle ``c_start``: dispatch, wait, the
        one stats fetch, the host bookkeeping, a due checkpoint."""
        with span("dispatch") as dispatch:
            step = step_for(k)
            dispatch.set_metadata(
                first_call=id(step) not in self._dispatched)
            self._dispatched.add(id(step))
            t0 = time.perf_counter()
            ens, backup, fail_key, ys = step(ens, backup, fail_key)
        with span("wait"):
            jax.block_until_ready(ens.assignment)
        t_chunk = time.perf_counter() - t0          # K x (T_MD + T_EX)

        t1 = time.perf_counter()
        with span("fetch"):
            ys = jax.device_get(ys)                 # ONE fetch per chunk
        t_data = time.perf_counter() - t1

        with span("bookkeep"):
            self._note_chunk(ys, k, t_chunk, t_data)

        if self.ckpt is not None and self.ckpt.every > 0:
            lo, hi = c_start, c_start + k - 1
            if hi // self.ckpt.every > (lo - 1) // self.ckpt.every:
                with span("ckpt"):
                    self._save_ckpt(hi, ens, backup, fail_key, force=True)
        if verbose:
            acc = sum(float(a) for a in ys["accepted"])
            att = max(sum(float(a) for a in ys["attempted"]), 1.0)
            print(f"chunk @cycle {c_start + k:4d} K={k} "
                  f"acc {acc / att * 100:5.1f}%  "
                  f"t {t_chunk / k * 1e3:7.2f} ms/cycle")
        return ens, backup, fail_key

    def _note_chunk(self, ys, k: int, t_chunk: float, t_data: float):
        """Fold one chunk's fetched (K,) stats into ``history``,
        ``acceptance`` and the telemetry accumulator."""
        # batch-convert the (K,) stat arrays once; per-cycle history
        # entries are then plain python — the bookkeeping stays O(K)
        # cheap instead of K x numpy-scalar boxing
        dims = ys["dim"].tolist()
        acc = ys["accepted"].tolist()
        att = ys["attempted"].tolist()
        cycles = ys["cycle"].tolist()
        failed = ys["failed"].tolist()
        esc_rel = ys["esc_relaunch"].tolist()
        esc_rei = ys["esc_reinit"].tolist()
        esc_dead = ys["esc_dead"].tolist()
        rfrac = ys["ready_frac"].tolist()
        overfl = ys["nb_overflow"].tolist()
        rebuilds = ys["nb_rebuilds"].tolist()
        assignment = ys["assignment"]              # (K, R) int32
        t_step, t_d = t_chunk / k, t_data / k
        for i in range(k):
            dkey = f"dim{dims[i]}"
            bucket = self.acceptance[dkey]
            bucket[0] += acc[i]
            bucket[1] += att[i]
            self.history.append({
                "cycle": cycles[i], "dim": dims[i],
                "t_step": t_step, "t_prep": 0.0,
                "t_recover": 0.0, "t_data": t_d,
                "accept": acc[i], "attempt": att[i],
                "failed": failed[i], "esc_relaunch": esc_rel[i],
                "esc_reinit": esc_rei[i], "esc_dead": esc_dead[i],
                "ready_frac": rfrac[i],
                "assignment": assignment[i],
                "nb_overflow": overfl[i],
                "nb_rebuilds": rebuilds[i],
            })

        tel = self._tel
        if tel is not None:
            budget = self._wire_budgets.get(k)
            if budget is not None and tel.wire_ledger:
                tel.note_wire_budget(k, budget)
                tel.note_wire_invocation(k)
            tel.note_cycles(
                cycles=cycles, dims=dims, assignments=assignment,
                n_dims=len(self.grid.dims), n_ctrl=self.grid.n_ctrl,
                pair_attempt=ys.get("pair_attempt"),
                pair_accept=ys.get("pair_accept"),
                t_cycle=t_chunk, t_data=t_data)

    def acceptance_ratios(self) -> Dict[str, float]:
        return {k: (a / max(n, 1.0))
                for k, (a, n) in self.acceptance.items()}

    # -- fault tolerance: shared detect/recover + carry plumbing ----------

    def _detect_recover_fn(self):
        """The jitted detect/escalate/recover step ``run()`` shares with
        the fused scan body (one code path — the escalation ladder cannot
        drift between run paths)."""
        key = ("detect_recover",)
        if key in self._compiled:
            return self._compiled[key]
        policy = "relaunch" if self.cfg.relaunch_failed else "continue"
        budget = self.cfg.relaunch_budget

        def step(ens, backup):
            with jax.named_scope("detect_recover"):
                return F.detect_recover(self.engine, ens, policy, backup,
                                        relaunch_budget=budget)

        jitted = jax.jit(step)
        self._compiled[key] = jitted
        return jitted

    def _start_carry(self, ens: Ensemble):
        """The scan carry's (backup, fail_key) start values: the pair a
        resume()/restore() loaded from the checkpoint (consumed exactly
        once), or the fresh-run values."""
        carry, self._resume_carry = self._resume_carry, None
        if carry is not None:
            return carry
        return ens.state, jax.random.key(self.cfg.seed + 999)

    # -- checkpoint payload / driver-state extra --------------------------

    def _ckpt_payload(self, ens: Ensemble, backup, fail_key):
        """The FULL device-side restart state: the ensemble plus the scan
        carry (recovery backup — which lags the ensemble whenever a
        failure froze it — and the failure-injection key chain).  All
        three are required for a bitwise-identical resume."""
        return {"ensemble": ens._asdict(), "backup": backup,
                "fail_key": fail_key}

    def _cfg_fingerprint(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self.cfg)
        for k in _CFG_RESUME_EXEMPT:
            d.pop(k, None)
        d["_failure_rate"] = float(self.failure_rate)
        # JSON round-trip normalizes tuples -> lists so the fingerprint
        # compares equal to what the manifest stored
        import json as _json
        return _json.loads(_json.dumps(d))

    def _ckpt_extra(self) -> Dict[str, Any]:
        """Host-side driver state riding the checkpoint manifest: cycle
        history (with assignment rows), per-dim acceptance, telemetry
        accumulators and the config fingerprint resume() validates."""
        hist = []
        for h in self.history:
            h2 = dict(h)
            if h2.get("assignment") is not None:
                h2["assignment"] = np.asarray(h2["assignment"]).tolist()
            hist.append(h2)
        tel = self._tel
        return {"repex": {
            "schema": CKPT_DRIVER_SCHEMA,
            "config": self._cfg_fingerprint(),
            "acceptance": {k: [float(v[0]), float(v[1])]
                           for k, v in self.acceptance.items()},
            "history": hist,
            "telemetry": tel.state_dict() if tel is not None else None,
        }}

    def _save_ckpt(self, step: int, ens: Ensemble, backup, fail_key,
                   force: bool = False):
        self.ckpt.maybe_save(step, self._ckpt_payload(ens, backup, fail_key),
                             extra=self._ckpt_extra(), force=force)

    # -- restart paths ----------------------------------------------------

    def _load_ckpt(self, step: Optional[int] = None):
        """Load the newest INTACT checkpoint into a template payload."""
        ens_like = self.init()
        like = self._ckpt_payload(ens_like, ens_like.state,
                                  jax.random.key(0))
        return load_checkpoint(self.ckpt.directory, like, step=step)

    def restore(self, ens_like: Ensemble) -> Optional[Ensemble]:
        """Restart from the latest ensemble checkpoint (node-failure path).

        Returns just the ensemble (legacy API); the recovery backup and
        failure-key carry are staged so the NEXT ``run*`` call continues
        bit-exactly.  :meth:`resume` is the full-state restart that also
        restores history/acceptance/telemetry."""
        if self.ckpt is None or self.ckpt.latest_step() is None:
            return None
        tree, _, _ = self._load_ckpt()
        self._resume_carry = (tree["backup"], tree["fail_key"])
        return Ensemble(**tree["ensemble"])

    def resume(self, via: str = "fused", n_cycles: Optional[int] = None,
               chunk_cycles: int = 16, mesh=None,
               step: Optional[int] = None,
               verbose: bool = False) -> Ensemble:
        """Continue a killed run from its newest intact checkpoint.

        Restores the ensemble, the scan carry (recovery backup + failure
        key chain) AND the host bookkeeping (cycle history, per-dim
        acceptance, telemetry accumulators), then runs the remaining
        ``n_cycles - cycle`` cycles via ``via`` in {"run", "fused",
        "sharded"}.  The stitched run's discrete trajectory and RunReport
        counters are identical to an uninterrupted run of the same
        configuration (tests/test_fault_tolerance.py pins this).  For
        ``via="sharded"`` the ensemble is resharded onto ``mesh`` (or the
        best mesh for the CURRENT device count — the elastic-restart
        path: a checkpoint from an 8-shard run restarts on 4 surviving
        devices unchanged).  The checkpoint's config fingerprint must
        match this driver's (``n_cycles`` exempt); a mismatch raises
        :class:`~repro.ckpt.CheckpointError` instead of silently
        diverging.
        """
        if self.ckpt is None:
            raise ValueError("resume() needs a driver constructed with "
                             "ckpt_dir")
        if via not in ("run", "fused", "sharded"):
            raise ValueError(f"via must be run|fused|sharded, got {via!r}")
        tree, step_no, extra = self._load_ckpt(step=step)
        meta = (extra or {}).get("repex")
        if not meta:
            raise CheckpointError(
                f"checkpoint step {step_no} carries no driver state "
                f"('repex' extra missing) — it was written by "
                f"ckpt.maybe_save directly, not the driver; use restore()")
        saved_cfg = meta.get("config", {})
        cur_cfg = self._cfg_fingerprint()
        if saved_cfg != cur_cfg:
            diff = sorted(k for k in set(saved_cfg) | set(cur_cfg)
                          if saved_cfg.get(k) != cur_cfg.get(k))
            raise CheckpointError(
                f"checkpoint config does not match this driver "
                f"(differing fields: {diff}) — resume with the original "
                f"configuration")

        self.history = [
            dict(h, assignment=np.asarray(h["assignment"], np.int32))
            if h.get("assignment") is not None else dict(h)
            for h in meta.get("history", [])]
        self.acceptance = {k: [float(v[0]), float(v[1])]
                           for k, v in meta.get("acceptance", {}).items()}
        if self.telemetry is not None and meta.get("telemetry") is not None:
            self.telemetry.load_state_dict(meta["telemetry"])

        ens = Ensemble(**tree["ensemble"])
        self._resume_carry = (tree["backup"], tree["fail_key"])
        total = n_cycles or self.cfg.n_cycles
        remaining = total - int(jax.device_get(ens.cycle))
        if remaining <= 0:
            self._resume_carry = None
            self.last_report = build_report(
                self, via, None if via == "run" else chunk_cycles)
            return ens
        if via == "run":
            return self.run(ens, n_cycles=remaining, verbose=verbose)
        if via == "sharded":
            return self.run_sharded(ens, mesh=mesh, n_cycles=remaining,
                                    chunk_cycles=chunk_cycles,
                                    verbose=verbose)
        return self.run_fused(ens, n_cycles=remaining,
                              chunk_cycles=chunk_cycles, verbose=verbose)
