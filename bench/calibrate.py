"""Readings that a cell's limits are set from, on the chip.

    python bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 [--chunks 2]

For each seed it builds the ensemble, runs ``--chunks`` chunks through the
program's own entry exactly as a run's window does, and compares the last
chunk with the reference (the kind's ``compare``): the sound readings.  For
each control seed it puts the reference itself in the program's place,
its force field computed in bfloat16 (one precision step below the
configuration's float32), and compares that chunk the same way: the
control's readings.  One JSON line per reading on stdout.  The program is
built and compiled once; every seed reuses it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--chunks", type=int, default=2)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench import check, harness, spec, workload

    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    cell = spec.cell(args.workload)
    harness.look_for_chips(int(cell["workload"]["chips"]))
    harness.enable_cache()
    prog = workload.build(cell, seeds[0] if seeds else controls[0])
    conf, traffic, limits = cell["config"], cell["traffic"], cell["limits"]
    kind = spec.kind_module(conf["kind"])
    for seed in sorted(set(seeds) | set(controls)):
        t0 = time.perf_counter()
        ens = prog.driver.init(workload.program_seed(seed))
        rows0 = len(prog.driver.history)
        for _ in range(args.chunks):
            before = ens
            ens = prog.entry(ens)
        jax.block_until_ready(ens)
        rows = prog.driver.history[rows0:]
        io = check.chunk_io(before, ens, rows, prog.chunk_cycles,
                            sum(int(r["failed"]) for r in rows))
        t1 = time.perf_counter()
        kinds = []
        if seed in seeds:
            kinds.append(("program", io))
        if seed in controls:
            kinds.append(("control_bf16", kind.control(conf, traffic, io,
                                                       jnp.bfloat16)))
        for name, chunk in kinds:
            t2 = time.perf_counter()
            checks = kind.compare(conf, traffic, limits, chunk)
            check_s = time.perf_counter() - t2
            look = {}
            if hasattr(kind, "look"):
                look = (kind.look(conf, chunk, prog.driver, ens)
                        if name == "program" else kind.look(conf, chunk))
            print(json.dumps({
                "workload": args.workload, "seed": seed, "kind": name,
                "run_s": t1 - t0, "check_s": check_s,
                **{k: c["value"] for k, c in checks.items()},
                **look}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
