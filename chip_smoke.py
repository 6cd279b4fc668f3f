"""Smoke run of the T-REMD main path on a TPU chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: run_sharded only

One chip: 1-D T-REMD on ``MDEngine``'s default analytic force path (the
``chain_forces`` bonded kernel plus the dense ``lj_forces`` nonbonded
kernel) driven by ``REMDDriver.run_fused``, on the 2,881-atom chain
stand-in for the paper's smaller system.  Three phases, each checked
against the jnp reference path (``use_force_kernels=False``) or the
driver's own invariants:

  force check      one bonded + nonbonded force evaluation, kernels vs
                   reference, max relative error;
  short-run check  ``run_fused`` at R = 8 on both paths: equal exchange
                   decisions, positions within ``POS_ULPS`` f32 spacings;
  main run         ``run_fused`` at R = 64, 200 MD steps per cycle,
                   8 cycles in chunks of 4: finite final state, control
                   multiset intact, no failure recovered.

``--chips 4`` runs only ``run_sharded`` on a 4-device replica mesh (16
replicas per chip) at the main run's size, against ``run_fused`` on one
chip: equal discrete trajectories, and each chip holding only its block.

Times and memory printed here are smoke figures, not benchmark results.
The script exits non-zero when JAX finds no TPU; the last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_ATOMS = 2881          # the paper's smaller system (md/system.py)
MAIN_REPLICAS = 64
MAIN_STEPS = 200
MAIN_CYCLES = 8
CHUNK = 4
SHORT_REPLICAS = 8
SHORT_STEPS = 20
SHORT_CYCLES = 4
FORCE_TOL = 1e-4        # max |F_kernel - F_ref| / max |F_ref|
# after the short run, positions agree to POS_ULPS f32 spacings of the
# largest coordinate (the extended chain reaches x ~ 4,000 A, where one
# spacing is ~5e-4 A)
POS_ULPS = 16


def log(msg):
    print(msg, flush=True)


def require_compiled(engine):
    """The chip run must take the compiled kernels: never interpret
    mode, never the jnp passes."""
    assert engine.force_kernels == "compiled", engine.force_kernels


def t_remd(n_replicas, steps, cycles):
    from repro.config import RepExConfig
    return RepExConfig(dimensions=(("temperature", n_replicas),),
                       md_steps_per_cycle=steps, n_cycles=cycles)


def force_check(system, n_replicas):
    """Kernel vs reference forces on one initial ensemble state."""
    import jax
    import numpy as np

    from repro.core import build_grid, ctrl_for_assignment
    from repro.md import MDEngine

    kern = MDEngine(system=system)
    require_compiled(kern)
    ref = MDEngine(system=system, use_force_kernels=False)
    ctrl = ctrl_for_assignment(
        build_grid(t_remd(n_replicas, 1, 1)),
        jax.numpy.arange(n_replicas))
    pos = kern.init_state(jax.random.key(0), n_replicas)["pos"]
    f_k = np.asarray(jax.jit(kern._analytic_force_fn(ctrl))(pos))
    with jax.default_matmul_precision("highest"):
        f_r = np.asarray(jax.jit(ref._analytic_force_fn(ctrl))(pos))
    err = float(np.max(np.abs(f_k - f_r)) / np.max(np.abs(f_r)))
    log(f"force check: N={system.n_atoms} R={n_replicas} "
        f"max|F_ref|={float(np.max(np.abs(f_r))):.6g} "
        f"max relative error={err:.3e} (tolerance {FORCE_TOL:g})")
    assert np.all(np.isfinite(f_k)), "non-finite kernel forces"
    assert err < FORCE_TOL, err


def decisions(driver):
    return [(h["cycle"], h["accept"], h["attempt"], h["failed"],
             tuple(int(a) for a in h["assignment"]))
            for h in driver.history]


def short_run_check(system, n_replicas, steps, cycles, chunk):
    """run_fused on the kernel and reference paths: same exchanges."""
    import jax
    import numpy as np

    from repro.core import REMDDriver
    from repro.md import MDEngine

    cfg = t_remd(n_replicas, steps, cycles)
    kern = MDEngine(system=system)
    require_compiled(kern)
    d_k = REMDDriver(kern, cfg)
    e_k = d_k.run_fused(d_k.init(), chunk_cycles=chunk)
    d_r = REMDDriver(MDEngine(system=system, use_force_kernels=False), cfg)
    with jax.default_matmul_precision("highest"):
        e_r = d_r.run_fused(d_r.init(), chunk_cycles=chunk)
    same = decisions(d_k) == decisions(d_r)
    pos_r = np.asarray(e_r.state["pos"])
    dpos = float(np.max(np.abs(np.asarray(e_k.state["pos"]) - pos_r)))
    tol = POS_ULPS * float(np.spacing(np.max(np.abs(pos_r))))
    log(f"short-run check: N={system.n_atoms} R={n_replicas} "
        f"{cycles}x{steps} steps: exchange decisions equal={same} "
        f"accepted={[h['accept'] for h in d_k.history]} "
        f"max|dpos|={dpos:.3e} A (tolerance {tol:.3e} A = {POS_ULPS} "
        f"f32 spacings at max|pos|={float(np.max(np.abs(pos_r))):.1f} A)")
    assert same, (decisions(d_k), decisions(d_r))
    assert dpos <= tol, dpos


def check_run(driver, ens, label):
    import jax.numpy as jnp

    from repro.core import control_multiset_ok

    finite = all(bool(jnp.all(jnp.isfinite(x)))
                 for x in (ens.state["pos"], ens.state["vel"]))
    multiset = bool(control_multiset_ok(ens))
    failed = sum(h["failed"] for h in driver.history)
    log(f"{label}: cycles={len(driver.history)} finite={finite} "
        f"control_multiset_ok={multiset} failures_recovered={failed} "
        f"acceptance={driver.acceptance_ratios()}")
    assert finite and multiset and failed == 0


def chunk_times(driver, chunk):
    """(first chunk, warm chunk) wall seconds from the driver history:
    the first includes compilation."""
    per_chunk = [h["t_step"] * chunk for h in driver.history[::chunk]]
    return per_chunk[0], per_chunk[1:]


def main_run(system, n_replicas, steps, cycles, chunk):
    import jax

    from repro.core import REMDDriver
    from repro.md import MDEngine

    eng = MDEngine(system=system)
    require_compiled(eng)
    driver = REMDDriver(eng, t_remd(n_replicas, steps, cycles))
    ens = driver.run_fused(driver.init(), chunk_cycles=chunk)
    check_run(driver, ens, f"main run (run_fused, R={n_replicas}, "
                           f"N={system.n_atoms})")
    first, warm = chunk_times(driver, chunk)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"smoke figures, not benchmark results: first chunk "
        f"{first:.3f} s (compile included), warm chunk s {warm}, "
        f"compile ~{first - min(warm):.3f} s, "
        f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    return driver, ens


def four_chip_run(system, n_replicas, steps, cycles, chunk):
    """run_sharded on a 4-device replica mesh vs run_fused on one chip."""
    import jax
    import numpy as np

    from repro.core import REMDDriver
    from repro.launch.mesh import make_replica_mesh
    from repro.md import MDEngine

    assert len(jax.devices()) == 4, jax.devices()
    cfg = t_remd(n_replicas, steps, cycles)
    eng = MDEngine(system=system)
    require_compiled(eng)
    d_s = REMDDriver(eng, cfg)
    e_s = d_s.run_sharded(d_s.init(), mesh=make_replica_mesh(4),
                          chunk_cycles=chunk)
    check_run(d_s, e_s, f"run_sharded (4 chips, R={n_replicas}, "
                        f"N={system.n_atoms})")
    shards = e_s.state["pos"].addressable_shards
    blocks = {str(s.device): tuple(s.data.shape) for s in shards}
    mem = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.devices()}
    log(f"run_sharded position blocks per device: {blocks}")
    log(f"smoke figures, not benchmark results: run_sharded chunk s "
        f"{[h['t_step'] * chunk for h in d_s.history[::chunk]]}, "
        f"peak_bytes_in_use per device {mem}")
    assert len(shards) == 4 and all(
        s.data.shape[0] == n_replicas // 4 for s in shards), blocks

    d_f = REMDDriver(MDEngine(system=system), cfg)
    e_f = d_f.run_fused(d_f.init(), chunk_cycles=chunk)
    check_run(d_f, e_f, f"run_fused (1 chip, R={n_replicas})")
    same = decisions(d_s) == decisions(d_f)
    dpos = float(np.max(np.abs(np.asarray(e_s.state["pos"])
                               - np.asarray(e_f.state["pos"]))))
    log(f"run_sharded vs run_fused: discrete trajectories equal={same} "
        f"max|dpos|={dpos:.3e} A")
    assert same, (decisions(d_s), decisions(d_f))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the run_sharded phase on 4 chips")
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {dev.platform})")
    from repro.launch.cache import enable_compile_cache
    from repro.md.system import chain_molecule

    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache: {enable_compile_cache()}")
    system = chain_molecule(N_ATOMS)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_run(system, MAIN_REPLICAS, MAIN_STEPS, MAIN_CYCLES, CHUNK)
    else:
        force_check(system, SHORT_REPLICAS)
        short_run_check(system, SHORT_REPLICAS, SHORT_STEPS, SHORT_CYCLES,
                        CHUNK // 2)
        main_run(system, MAIN_REPLICAS, MAIN_STEPS, MAIN_CYCLES, CHUNK)
    log(f"wall {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
