"""RepEx simulation launcher — the paper's user-facing entry point.

Everything is specified by flags/config (the paper's 'fully specified by
configuration files' usability requirement):

  python -m repro.launch.repex_run --engine md \
      --dims temperature:8 --cycles 10 --md-steps 100 --pattern async
  python -m repro.launch.repex_run --engine md \
      --dims temperature:6,umbrella:8,umbrella:8 --slots 128
  # fused chunks / replica-sharded execution (docs/SCALING.md):
  python -m repro.launch.repex_run --dims temperature:8 --chunk 16
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m repro.launch.repex_run --dims temperature:8 --shards 8
  # a profiler trace of the run, for the Eq. (1) split
  # (docs/OBSERVABILITY.md):
  python -m repro.launch.repex_run --dims temperature:8 --chunk 4 \
      --profile-dir /tmp/repex-trace
"""
from __future__ import annotations

import argparse
import contextlib

import jax
import numpy as np

from repro.config import RepExConfig
from repro.core import REMDDriver, control_multiset_ok
from repro.launch.cache import enable_compile_cache
from repro.md import LJEngine, MDEngine
from repro.md.system import chain_molecule


def parse_dims(text: str):
    dims = []
    for part in text.split(","):
        kind, _, n = part.partition(":")
        dims.append((kind.strip(), int(n)))
    return tuple(dims)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="md", choices=["md", "lj", "lm"])
    ap.add_argument("--dims", default="temperature:8")
    ap.add_argument("--cycles", type=int, default=10)
    ap.add_argument("--md-steps", type=int, default=100)
    ap.add_argument("--pattern", default="sync",
                    choices=["sync", "async"])
    ap.add_argument("--scheme", default="neighbor",
                    choices=["neighbor", "matrix"])
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "mode1", "mode2"])
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--atoms", type=int, default=22)
    ap.add_argument("--failure-rate", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", default=None, metavar="CKPT_DIR",
                    help="continue a killed run from its newest INTACT "
                         "checkpoint in CKPT_DIR (bitwise-identical "
                         "trajectory; --cycles is the TOTAL cycle count "
                         "of the stitched run; pass the original run's "
                         "flags — a config mismatch is refused).  "
                         "--report-out reflects the stitched run.  "
                         "docs/FAULT_TOLERANCE.md")
    ap.add_argument("--relaunch-budget", type=int, default=0,
                    help="escalation budget B: relaunch a replica at most "
                         "B consecutive times, then reinit from the peer "
                         "rung, then continue degraded (0 = unlimited "
                         "relaunches)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=0,
                    help="fuse K cycles per dispatch (run_fused)")
    ap.add_argument("--shards", type=int, default=0,
                    help="replica-shard over N devices "
                         "(run_sharded; uses --chunk or 16)")
    ap.add_argument("--report-out", default=None, metavar="PATH",
                    help="write the structured RunReport JSON here "
                         "(enables telemetry: per-pair counters, "
                         "occupancy, wire ledger — docs/OBSERVABILITY.md)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="record a profiler trace of the run into DIR: "
                         "the cycle body's scopes and the driver's host "
                         "spans give the Eq. (1) split "
                         "(docs/OBSERVABILITY.md)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = RepExConfig(
        engine=args.engine,
        dimensions=parse_dims(args.dims),
        md_steps_per_cycle=args.md_steps,
        n_cycles=args.cycles,
        pattern="asynchronous" if args.pattern == "async" else "synchronous",
        exchange_scheme=args.scheme,
        execution_mode=args.mode,
        seed=args.seed,
        relaunch_budget=args.relaunch_budget,
    )
    if args.engine == "lj":
        engine = LJEngine()
    elif args.engine == "lm":
        from repro.models import registry
        from repro.models.lm_engine import LMEngine
        engine = LMEngine(registry.get_smoke_config("olmo_1b"))
    else:
        engine = MDEngine(system=chain_molecule(args.atoms))

    telemetry = None
    if args.report_out:
        from repro.obs import Telemetry
        telemetry = Telemetry()
    ckpt_dir = args.resume or args.ckpt_dir
    driver = REMDDriver(engine, cfg, slots=args.slots,
                        ckpt_dir=ckpt_dir,
                        ckpt_every=1 if ckpt_dir else 0,
                        failure_rate=args.failure_rate,
                        telemetry=telemetry)
    print(f"replicas={driver.grid.n_ctrl} execution={driver.execution} "
          f"pattern={cfg.pattern} scheme={cfg.exchange_scheme}")
    with (jax.profiler.trace(args.profile_dir) if args.profile_dir
          else contextlib.nullcontext()):
        ens = _run(driver, args)
    print("\nmultiset ok:", control_multiset_ok(ens))
    print("acceptance:", {k: f"{v*100:.1f}%"
                          for k, v in driver.acceptance_ratios().items()})
    print("failures recovered:", sum(h["failed"] for h in driver.history))
    if args.report_out:
        driver.last_report.save(args.report_out)
        print(f"report -> {args.report_out}")
    if args.profile_dir:
        print(f"profile -> {args.profile_dir}")


def _run(driver, args):
    """The run the flags ask for: resumed, replica-sharded, fused or
    per-cycle."""
    if args.resume:
        via = "sharded" if args.shards else ("fused" if args.chunk
                                             else "run")
        mesh = None
        if args.shards:
            from repro.launch.mesh import make_replica_mesh
            mesh = make_replica_mesh(args.shards)
        return driver.resume(via=via, n_cycles=args.cycles,
                             chunk_cycles=args.chunk or 16, mesh=mesh,
                             verbose=True)
    if args.shards:
        from repro.launch.mesh import make_replica_mesh
        return driver.run_sharded(driver.init(),
                                  mesh=make_replica_mesh(args.shards),
                                  chunk_cycles=args.chunk or 16,
                                  verbose=True)
    if args.chunk:
        return driver.run_fused(driver.init(), chunk_cycles=args.chunk,
                                verbose=True)
    return driver.run(driver.init(), verbose=True)


if __name__ == "__main__":
    main()
