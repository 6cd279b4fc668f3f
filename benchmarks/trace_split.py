"""Eq. (1) split of one benchmark cell from a profiler trace: device busy
time by the program's scopes, host time by the driver's ``repex.*`` spans.

    python benchmarks/trace_split.py <workload> <seed> <untraced_chunks> \
        <traced_chunks> <out.json> [--root DIR]

Run from the root of a checkout, on the cell's chips.  It builds and
warms the cell like ``bench/run.py`` (with JAX's own compilation cache
settings: it turns no cache on), times untraced chunks, then traces
whole chunks inside the harness's own ``bench:window`` span (host tracer
level 2, no Python tracer) and writes the reduction below as JSON.  A
tool beside the benchmark, not one of its metrics: no cell runs it.

The reduction (``split``), all inside the window:

* an op's scope.  A v5e trace's ``XLA Ops`` events carry no ``op_name``
  stat, only ``device_duration_ps``, ``device_offset_ps`` and ``Time
  Scale Multiplier``.  So the op's instruction name (its event name up
  to `` = ``) is joined with the ``op_name`` metadata of the driver's
  compiled chunk functions, lowered again on the window's arguments
  (``chunk_op_names``); the last path component that is one of
  ``SCOPES`` is the scope, else ``none``;
* busy time per chip: the union of leaf ops (``bench.trace.leaves``).
  ``t_md_ms_per_cycle`` is that union over ``propagate`` ops,
  ``t_ex_ms_per_cycle`` over ``features`` and ``exchange`` ops, each
  averaged over chips and divided by cycles; ``scope_cover`` is the
  scoped union over the whole union, per chip;
* host spans: each ``repex.*`` span clipped to the window and summed by
  name, per chunk.  ``t_data_ms_per_chunk`` is ``repex.fetch``;
  ``t_over_ms_per_chunk`` is ``repex.`` ``start`` + ``dispatch`` +
  ``bookkeep`` + ``ckpt`` + ``report``;
* boundary idle, as ``host_gap_ms_per_chunk`` reads it: idle time outside
  the executions of the longest-running module, averaged over chips, per
  chunk; ``host_gap_in_repex_span_share`` is the part of it that some
  ``repex.*`` span covers; each idle gap is named ``<harness span>/<innermost
  repex span>`` open at its middle.
"""
from __future__ import annotations

import argparse
import glob
import json
import re
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Sequence

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402

from bench import trace as tr  # noqa: E402

SCOPES = ("propagate", "features", "exchange", "detect_recover", "inject")
T_EX_SCOPES = ("features", "exchange")
OVER_SPANS = ("start", "dispatch", "bookkeep", "ckpt", "report")
SPAN_PREFIX = "repex."
_INSTR = re.compile(r'\s*(?:ROOT )?%(\S+) = .*op_name="([^"]*)"')


def scope_of(op_name: str) -> str:
    """The innermost program scope on an ``op_name`` path, or ``none``."""
    hits = [p for p in op_name.split("/") if p in SCOPES]
    return hits[-1] if hits else "none"


def op_names(compiled_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` metadata of a compiled module."""
    out = {}
    for line in compiled_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def chunk_op_names(driver, ens) -> Dict[str, str]:
    """The join table of every fused or sharded chunk function the driver
    has compiled, lowered and compiled again on ``ens``.  An executable
    that XLA:CPU loads from the persistent compilation cache comes back
    without its op metadata; that join would read every op as ``none``,
    so it raises instead."""
    out: Dict[str, str] = {}
    key = jax.random.key(0)
    for (kind, *_), fn in driver._compiled.items():
        if kind not in ("fused", "sharded"):
            continue
        if kind == "sharded":
            from jax.sharding import NamedSharding, PartitionSpec as P
            key = jax.device_put(key, NamedSharding(
                ens.assignment.sharding.mesh, P()))
        text = fn.lower(ens, ens.state, key).compile().as_text()
        names = op_names(text)
        if not names:
            raise RuntimeError(
                f"the compiled {kind} chunk carries no op_name metadata "
                "(loaded from a persistent compilation cache?)")
        out.update(names)
    return out


def trace_window(prog, ens, chunks: int, log_dir: str):
    """Run ``chunks`` chunks under the profiler, each inside a
    ``bench:<entry>`` span, all inside ``bench:window``; returns the
    ensemble and the chunks' seconds."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    times = []
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(chunks):
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX
                                              + prog.entry_name):
                ens = prog.entry(ens)
                jax.block_until_ready(ens)
            times.append(time.perf_counter() - t)
    jax.profiler.stop_trace()
    return ens, times


def read_profile(log_dir: str, device_ids: Sequence[int]):
    """The newest profile under ``log_dir``: per chip, its ``XLA Ops`` and
    ``XLA Modules`` events as (name, start_ns, end_ns); from the host, the
    ``bench:`` and ``repex.`` spans as (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))[-1]
    keep = set(device_ids)
    ops: Dict[int, list] = {}
    modules: Dict[int, list] = {}
    host = []
    for plane in ProfileData.from_file(path).planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in keep:
            for line in plane.lines:
                dest = {tr.OPS_LINE: ops, tr.MODULES_LINE: modules}.get(
                    line.name)
                if dest is not None:
                    dest.setdefault(int(m.group(1)), []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)) for e in line.events
                            if e.name.startswith((SPAN_PREFIX,
                                                  tr.SPAN_PREFIX)))
    return ops, modules, host


def _innermost(host, t: float, prefix: str):
    best = None
    for s in host:
        if s[0].startswith(prefix) and s[1] <= t < s[2] and (
                best is None or s[1] >= best[1]):
            best = s
    return best[0] if best else None


def split(ops, modules, host, op_name_of: Dict[str, str], chunks: int,
          cycles: int) -> dict:
    """Reduce one traced window (module docstring) to the Eq. (1) terms."""
    lo, hi = [(a, b) for n, a, b, _ in host if n == tr.WINDOW_SPAN][-1]
    nd = max(len(ops), 1)

    def scope(name: str) -> str:
        return scope_of(op_name_of.get(name.split(" = ")[0].lstrip("%"),
                                       ""))

    def busy(ivs) -> float:
        return tr.total(tr.union(tr.clip(ivs, lo, hi)))

    by_scope: Dict[str, float] = {}
    cover, t_md, t_ex, unscoped = [], 0.0, 0.0, {}
    for rows in ops.values():
        lv = tr.leaves([r for r in rows if r[2] > lo and r[1] < hi])
        by: Dict[str, List] = {}
        for n, a, b in lv:
            by.setdefault(scope(n), []).append((a, b))
            if scope(n) == "none":
                k = tr.short_name(n)
                unscoped[k] = unscoped.get(k, 0.0) + (
                    min(b, hi) - max(a, lo)) / 1e9 / nd
        for k, iv in by.items():
            by_scope[k] = by_scope.get(k, 0.0) + busy(iv) / 1e9 / nd
        scoped = [iv for k, v in by.items() if k != "none" for iv in v]
        cover.append(busy(scoped) / busy([(a, b) for _, a, b in lv]))
        t_md += busy(by.get("propagate", []))
        t_ex += busy([iv for k in T_EX_SCOPES for iv in by.get(k, [])])

    spans = [s for s in host if s[0].startswith(SPAN_PREFIX)
             and s[2] > lo and s[1] < hi]
    per_span: Dict[str, float] = {}
    for n, a, b, _ in spans:
        per_span[n] = per_span.get(n, 0.0) + min(b, hi) - max(a, lo)

    gap, uncovered, named = 0.0, 0.0, []
    in_span = [(a, b) for _, a, b, _ in spans]
    for dev, rows in ops.items():
        longest: Dict[str, float] = {}
        for n, a, b in modules.get(dev, []):
            longest[n] = longest.get(n, 0.0) + b - a
        if not longest:
            continue
        main = max(longest, key=longest.get)
        runs = tr.union((a, b) for n, a, b in modules[dev] if n == main)
        lv = tr.leaves([r for r in rows if r[2] > lo and r[1] < hi])
        idle = tr.gaps(tr.union(tr.clip([(a, b) for _, a, b in lv], lo, hi)),
                       lo, hi)
        outside = [iv for a, b in idle for iv in tr.gaps(runs, a, b)]
        gap += tr.total(outside)
        uncovered += tr.subtract(outside, in_span)
        for a, b in idle:
            mid = (a + b) / 2
            h = _innermost(host, mid, tr.SPAN_PREFIX)
            r = _innermost(host, mid, SPAN_PREFIX)
            name = (h[len(tr.SPAN_PREFIX):] if h else "outside") + (
                "/" + r if r else "")
            named.append((f"{name} (device {dev})", (b - a) / 1e9))
    named.sort(key=lambda kv: -kv[1])
    idle_by_name: Dict[str, float] = {}
    for n, s in named:
        k = n.rsplit(" (device", 1)[0]
        idle_by_name[k] = idle_by_name.get(k, 0.0) + s * 1e3 / nd / chunks

    return {
        "window_s": (hi - lo) / 1e9, "chunks": chunks, "cycles": cycles,
        "busy_by_scope_s": by_scope,
        "scope_cover": cover,
        "t_md_ms_per_cycle": t_md / nd / cycles / 1e6,
        "t_ex_ms_per_cycle": t_ex / nd / cycles / 1e6,
        "unscoped_top": sorted(unscoped.items(), key=lambda kv: -kv[1])[:12],
        "span_ms_per_chunk": {k: v / chunks / 1e6
                              for k, v in per_span.items()},
        "t_data_ms_per_chunk": per_span.get(SPAN_PREFIX + "fetch", 0.0)
        / chunks / 1e6,
        "t_over_ms_per_chunk": sum(per_span.get(SPAN_PREFIX + k, 0.0)
                                   for k in OVER_SPANS) / chunks / 1e6,
        "chunk_args": [s[3] for s in spans if s[0] == SPAN_PREFIX + "chunk"],
        "dispatch_args": [s[3] for s in spans
                          if s[0] == SPAN_PREFIX + "dispatch"],
        "host_gap_ms_per_chunk": gap / nd / chunks / 1e6,
        "host_gap_in_repex_span_share": 1 - uncovered / gap if gap else None,
        "idle_gaps": named[:16],
        "idle_by_name_ms_per_chunk": dict(sorted(idle_by_name.items(),
                                                 key=lambda kv: -kv[1])),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("workload")
    p.add_argument("seed", type=int)
    p.add_argument("untraced", type=int)
    p.add_argument("traced", type=int)
    p.add_argument("out")
    p.add_argument("--root", default=None,
                   help="checkout whose BENCHMARK.json names the cell")
    a = p.parse_args(argv)
    t0 = time.perf_counter()
    from bench import spec, workload
    root = Path(a.root) if a.root else spec.ROOT
    cell = spec.cell(a.workload, root)
    prog = workload.build(cell, a.seed, root=root)
    ens = workload.warm_up(prog)
    print(f"set-up {time.perf_counter() - t0:.2f} s", flush=True)
    untraced = []
    for _ in range(a.untraced):
        t = time.perf_counter()
        ens = prog.entry(ens)
        jax.block_until_ready(ens)
        untraced.append(time.perf_counter() - t)
    with tempfile.TemporaryDirectory() as d:
        ens, traced = trace_window(prog, ens, a.traced, d)
        ops, modules, host = read_profile(d, [x.id for x in prog.devices])
    res = {"workload": a.workload, "seed": a.seed,
           "untraced_chunk_s": untraced, "traced_chunk_s": traced}
    names = chunk_op_names(prog.driver, ens)
    joined: Dict[str, int] = {}
    for op_name in names.values():
        joined[scope_of(op_name)] = joined.get(scope_of(op_name), 0) + 1
    res["joined_instructions"] = joined
    res.update(split(ops, modules, host, names, a.traced,
                     a.traced * prog.chunk_cycles))
    Path(a.out).parent.mkdir(parents=True, exist_ok=True)
    Path(a.out).write_text(json.dumps(res, indent=1, default=str))
    print(json.dumps(res, default=str)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
