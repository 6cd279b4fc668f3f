"""Deployment kind ``tremd_chain``: 1-D temperature REMD of the chain
molecule with neighbor (DEO) exchange, run by the program's
``REMDDriver``.

A configuration names its kind (its ``"kind"`` key) and the harness
finds this file by that name (``spec.kind_module``), so a deployment of
another kind (other ladder dimensions, another system) arrives as a file
of its own beside this one.  A kind file gives

  driver(config, traffic, seed)        the program's ``REMDDriver`` as the
                                       configuration states it
  compare(config, traffic, limits, io) every number of the check with
                                       its limit (see below)
  control(config, traffic, io, dtype)  the reference in the program's
                                       place, its force field in ``dtype``:
                                       the chunk it would have produced

and may give ``look(config, io, driver, ens)``, readings that explain a
limit (``bench/calibrate.py`` prints them).  Only ``driver`` and
``look`` touch the program; ``compare`` and ``control`` run the plain
reference (``bench/reference.py``), which imports nothing of it.

The numbers ``compare`` gives, over what the timed window produced:

  pos_gap_ulp      The window's last chunk is replayed by the reference
                   for every replica: from the chunk's input state, with
                   the rungs the program held each cycle, the same noise
                   stream, the same steps.  The number is the widest gap
                   between the program's and the reference's final
                   positions, per atom in units of one float32 spacing of
                   that atom's distance from the origin (at least
                   FLOOR_A).  The chain reaches 4,000 A from the origin,
                   where one spacing is 4.9e-4 A, and a gap of a few
                   spacings there is rounding; near the origin the spacing
                   is 1e-7 A and the same unit keeps a lower-precision
                   force field visible.
  swap_errors      The last cycle's exchange is decided again from the
                   reference's energies of the program's final positions
                   and the same uniforms.  A swap decision that differs
                   counts when the reference puts it further from the
                   Metropolis boundary, |log u + max(delta, 0)| in kT,
                   than DELTA_SPACINGS float32 spacings of the reduced
                   energies (beta U, ~7e4 kT here) that the program sums
                   delta from: closer than that, float32 cannot decide it.
  rung_errors      Replicas whose final rung is neither the kept nor the
                   swapped one of the reference's sweep, plus rungs lost
                   or duplicated (the assignment stays a permutation),
                   plus replicas whose rung changed between the cycle
                   before the chunk and the chunk's start.
  failed_replicas  Replica failures the driver recovered in the window.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import reference as ref
from bench.check import ChunkIO

FLOOR_A = 8.0           # a few bond lengths: atoms near the origin still
                        # feel the rounding of their bonded neighbours
DELTA_SPACINGS = 8      # delta = (a + b) - (c + d), each term rounded
BATCH = 8               # replicas the reference advances at once per chip
CHECKS = ("pos_gap_ulp", "swap_errors", "rung_errors", "failed_replicas")


def driver(config: dict, traffic: dict, seed: int):
    """The program as the configuration states it: the chain molecule
    on ``MDEngine``'s compiled force kernels (on a TPU), a geometric
    temperature ladder, the traffic's steps per exchange and cycles per
    host sync."""
    from repro.config import RepExConfig
    from repro.core import REMDDriver
    from repro.md import MDEngine
    from repro.md.system import chain_molecule

    sysc, integ, lad = config["system"], config["integrator"], config["ladder"]
    temperatures(lad)                   # the ladder this kind covers
    engine = MDEngine(system=chain_molecule(sysc["n_atoms"],
                                            seed=sysc["topology_seed"]),
                      dt=integ["dt_ps"], gamma=integ["gamma_per_ps"],
                      init_temperature=integ["init_temperature_K"])
    if (jax.devices()[0].platform == "tpu"
            and engine.force_kernels != "compiled"):
        raise RuntimeError(f"force kernels run as {engine.force_kernels!r}")
    cfg = RepExConfig(
        dimensions=tuple((k, int(n)) for k, n in lad["dimensions"]),
        t_min=lad["t_min_K"], t_max=lad["t_max_K"],
        md_steps_per_cycle=traffic["md_steps_per_exchange"],
        n_cycles=traffic["cycles_per_sync"],
        pattern=config["exchange"]["pattern"],
        exchange_scheme=config["exchange"]["scheme"],
        exchange_comm=config["exchange"]["comm"],
        seed=seed)
    return REMDDriver(engine, cfg, failure_rate=traffic["failure_rate"])


def temperatures(ladder_cfg: dict) -> np.ndarray:
    """Temperatures of the ladder's rungs (K), float32."""
    (dim, n), = ladder_cfg["dimensions"]
    if dim != "temperature" or ladder_cfg["spacing"] != "geometric":
        raise ValueError(f"kind tremd_chain covers geometric temperature "
                         f"ladders only, got {ladder_cfg}")
    return np.geomspace(ladder_cfg["t_min_K"], ladder_cfg["t_max_K"],
                        int(n)).astype(np.float32)


def _replay_fn(config: dict, n_steps: int, dtype):
    """Every replica's BAOAB chunk of one cycle, (R, N, 3) in and out.
    The replicas are spread over the cell's chips and advanced BATCH at
    a time on each."""
    top = ref.chain_topology(config["system"])
    integrator = config["integrator"]

    def one(args):
        pos, vel, temp, key = args
        return ref.baoab(pos, vel, temp, jax.random.wrap_key_data(key),
                         n_steps, top, integrator, dtype)

    def block(pos, vel, temp, keys):
        return jax.lax.map(one, (pos, vel, temp, keys), batch_size=BATCH)

    shards = int(config["replica_shards"])
    if shards == 1:
        return jax.jit(block)
    mesh = Mesh(np.array(jax.devices()[:shards]), ("replica",))
    rows = NamedSharding(mesh, P("replica"))
    sharded = jax.jit(jax.shard_map(block, mesh=mesh, in_specs=P("replica"),
                                    out_specs=P("replica"), check_vma=False))
    return lambda *args: sharded(*(jax.device_put(a, rows) for a in args))


def _advance(fn, temps, pos, vel, assign, k_md):
    n_rep = assign.shape[0]
    keys = jax.random.key_data(jax.random.split(k_md, n_rep))
    return fn(pos, vel, jnp.asarray(temps[assign]), keys)


def replay(config: dict, traffic: dict, io: ChunkIO) -> np.ndarray:
    """The reference's positions of every replica after the chunk,
    (R, N, 3)."""
    temps = temperatures(config["ladder"])
    fn = _replay_fn(config, int(traffic["md_steps_per_exchange"]),
                    jnp.float32)
    pos, vel = jnp.asarray(io.pos_in), jnp.asarray(io.vel_in)
    key, assign = io.key_in, io.assign_in
    with jax.default_matmul_precision("highest"):
        for row in io.assign_rows:
            k_md, _, key = ref.cycle_keys(key)
            pos, vel = _advance(fn, temps, pos, vel, assign, k_md)
            assign = row
    return np.asarray(pos)


def energies(config: dict, pos: np.ndarray, dtype=jnp.float32) -> np.ndarray:
    """Reference potential energy of every replica, (R,) float64: the
    terms on the device, their sum on the host."""
    top = ref.chain_topology(config["system"])
    fn = jax.jit(lambda p: jax.lax.map(
        lambda x: ref.energy_terms(x, top, dtype), p, batch_size=4))
    with jax.default_matmul_precision("highest"):
        terms = np.asarray(fn(jnp.asarray(pos)))
    return terms.astype(np.float64).sum(axis=1)


def last_exchange(config: dict, io: ChunkIO, energy: np.ndarray):
    """Reference decision of the chunk's last sweep on the program's
    final positions: (accepted, margin, rung before, left rungs, right
    rungs)."""
    temps = temperatures(config["ladder"])
    key = io.key_in
    for _ in io.assign_rows:
        _, k_ex, key = ref.cycle_keys(key)
    cycle = io.cycle_in + len(io.assign_rows) - 1
    before = io.assign_rows[-2] if len(io.assign_rows) > 1 else io.assign_in
    parity = cycle % 2
    _, accept, margin = ref.exchange(before, energy, temps, k_ex, parity)
    left, right, _ = ref.sweep_pairs(len(temps), parity)
    return accept, margin, before, left, right


def swaps(config: dict, io: ChunkIO, energy: np.ndarray):
    """The last sweep's pairs: (program accepted, reference accepted,
    margin in kT, the float32 resolution of the program's delta in kT,
    rung before, rung pairs)."""
    accept, margin, before, left, right = last_exchange(config, io, energy)
    inv = np.argsort(before)
    prog_accept = io.assign_rows[-1][inv[left]] == right
    beta = 1.0 / (ref.KB * temperatures(config["ladder"]).astype(np.float64))
    scale = 2 * np.maximum(beta[left], beta[right]) * np.maximum(
        np.abs(energy[inv[left]]), np.abs(energy[inv[right]]))
    resolution = DELTA_SPACINGS * np.spacing(scale.astype(np.float32))
    return prog_accept, accept, margin, resolution, before, (left, right)


def compare(config: dict, traffic: dict, limits: dict, io: ChunkIO):
    """Every number of the check with its limit."""
    pos_ref = replay(config, traffic, io)
    gap = np.max(np.abs(io.pos_out - pos_ref), axis=-1)
    scale = np.spacing(np.maximum(np.linalg.norm(pos_ref, axis=-1),
                                  FLOOR_A).astype(np.float32))
    pos_gap = float(np.max(gap / scale))

    prog_accept, accept, margin, resolution, before, (left, right) = swaps(
        config, io, energies(config, io.pos_out))
    swap_errors = int(np.sum((prog_accept != accept) & (margin > resolution)))
    prog = io.assign_rows[-1]
    inv = np.argsort(before)
    swapped = before.copy()
    swapped[inv[left]], swapped[inv[right]] = right, left
    rung_errors = int(np.sum((prog != before) & (prog != swapped))
                      + (len(prog) - len(np.unique(prog))))
    if io.assign_prev is not None:
        # the chunk starts from the rungs the previous cycle ended with
        rung_errors += int(np.sum(io.assign_in != io.assign_prev))
    numbers = {"pos_gap_ulp": pos_gap, "swap_errors": float(swap_errors),
               "rung_errors": float(rung_errors),
               "failed_replicas": float(io.failed)}
    return {k: {"value": numbers[k], "limit": float(limits[k])}
            for k in CHECKS}


def control(config: dict, traffic: dict, io: ChunkIO, dtype) -> ChunkIO:
    """The reference in the program's place, its force field computed in
    ``dtype``: the chunk it would have produced from the same input."""
    temps = temperatures(config["ladder"])
    fn = _replay_fn(config, int(traffic["md_steps_per_exchange"]), dtype)
    key, assign = io.key_in, io.assign_in
    pos, vel = jnp.asarray(io.pos_in), jnp.asarray(io.vel_in)
    rows, cycle = [], io.cycle_in
    for _ in io.assign_rows:
        k_md, k_ex, key = ref.cycle_keys(key)
        pos, vel = _advance(fn, temps, pos, vel, assign, k_md)
        assign, _, _ = ref.exchange(assign, energies(config, np.asarray(pos),
                                                     dtype),
                                    temps, k_ex, cycle % 2)
        rows.append(assign)
        cycle += 1
    return ChunkIO(pos_in=io.pos_in, vel_in=io.vel_in,
                   assign_in=io.assign_in, key_in=io.key_in,
                   cycle_in=io.cycle_in, pos_out=np.asarray(pos),
                   assign_rows=rows, failed=0)


def look(config: dict, io: ChunkIO, driver=None, ens=None) -> dict:
    """Why swaps may differ: the widest margin of a flipped decision,
    the float32 resolution of delta, and (given the program's final
    ensemble) the gap between its energies and the reference's."""
    u_ref = energies(config, io.pos_out)
    p_acc, r_acc, margin, res, _, _ = swaps(config, io, u_ref)
    out = {"widest_flip_kT": float(np.max(margin[p_acc != r_acc],
                                          initial=0)),
           "delta_resolution_kT": float(np.min(res))}
    if driver is not None:
        f = driver.engine.replica_features(ens.state)
        u_prog = np.asarray(f["u_base"] + f["u_elec"], np.float64)
        out.update(energy_gap_kcal=float(np.max(np.abs(u_prog - u_ref))),
                   energy_max_kcal=float(np.max(np.abs(u_ref))))
    return out
