"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (see paper_figures for the figure
catalogue; roofline.py emits the dry-run-derived §Roofline table).

    python benchmarks/run.py [FILTER] [--json-out PATH]

``FILTER`` selects benchmarks by substring; ``--json-out`` redirects the
JSON payload of benches that emit one (``cycle_fusion`` ->
``BENCH_cycle_fusion.json``, ``neighbor_list`` ->
``BENCH_neighbor_list.json``, ``bonded_scaling`` ->
``BENCH_bonded_scaling.json`` by default) — e.g.
``cycle_fusion --json-out BENCH_force_kernel.json`` records the
force-kernel sweep.  An explicit ``--json-out`` requires the FILTER to
select at most ONE JSON-emitting bench — the harness refuses to let
several benches silently clobber the same path.
"""
from __future__ import annotations

import argparse


def _sanitize(msg: str) -> str:
    """Exception text -> CSV-safe derived field: the output stream is
    ``name,us_per_call,derived`` rows, so an error message carrying
    commas or newlines would split into phantom columns/rows for any
    consumer."""
    return " ".join(str(msg).split()).replace(",", ";")


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("only", nargs="?", default=None,
                        help="substring filter on benchmark names")
    parser.add_argument("--json-out", default=None,
                        help="path for the JSON payload of benches that "
                             "emit one (default: bench-specific name)")
    args = parser.parse_args()

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import paper_figures as PF
    selected = [fn for fn in PF.ALL
                if not args.only or args.only in fn.__name__]
    if args.json_out:
        emitters = [fn.__name__ for fn in selected
                    if fn.__name__ in PF.JSON_BENCHES]
        if len(emitters) > 1:
            parser.error(
                f"--json-out selects one output path but the filter "
                f"matches {len(emitters)} JSON-emitting benches "
                f"({', '.join(emitters)}); narrow FILTER so only one "
                f"bench writes there")
        PF.JSON_OUT = args.json_out
    print("name,us_per_call,derived", flush=True)
    for fn in selected:
        rows = []
        try:
            fn(rows)
        except Exception as e:  # noqa: BLE001 — keep the harness running
            rows.append(f"{fn.__name__},0,"
                        f"ERROR={type(e).__name__}:{_sanitize(e)}")
        for r in rows:
            print(r, flush=True)


if __name__ == '__main__':
    main()
