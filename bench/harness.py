"""One run of one cell: set-up, window, check, metrics, result line.

``bench/run.py`` is the command; this module does the work, so the CPU
tests can drive a whole run with the chip look switched off and a fault
planted in the program.
"""
from __future__ import annotations

import contextlib
import gc
import json
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from bench import check, spec, trace as tr, workload


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Run:
    """What a metric reader gets: plain numbers of one run."""
    workload: str
    config: dict
    traffic: dict
    setup_s: float
    window_s: float
    chunks: int
    cycles: int
    chunk_seconds: List[float]
    n_replicas: int
    md_steps: int
    replica_steps: int
    n_devices: int
    device_kind: str
    memory_peak_bytes: Optional[int]
    peaks: Optional[dict]
    trace: Optional[tr.Trace] = None


def look_for_chips(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs


def enable_cache():
    """JAX's persistent compilation cache in the checkout (the program's
    own ``.jax_cache/``, or ``$JAX_COMPILATION_CACHE_DIR``), every
    program cached however fast it compiled, so that only the first run
    of a cell in a checkout compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run(workload_name: str, seed: int, seconds: float, traced: bool,
        t_start: float, on_chip: bool = True,
        fault: Optional[Callable] = None, out=None,
        root: Path = spec.ROOT) -> int:
    """Print one result line to ``out`` (stdout); returns the exit code.
    ``root`` is the checkout holding ``BENCHMARK.json``."""
    import jax

    out = out or sys.stdout
    cell = spec.cell(workload_name, root)
    chips = int(cell["workload"]["chips"])
    if on_chip:
        devs = look_for_chips(chips)
        log(f"compile cache: {enable_cache()}")
    else:
        devs = jax.devices()
    kind = devs[0].device_kind
    peaks = spec.peaks(kind, root) if on_chip else None
    log(f"device: {devs[0].platform} {kind} x{chips}")

    log(f"set-up: {time.perf_counter() - t_start:.3f} s to the device look")
    prog = workload.build(cell, seed, fault=fault, root=root)
    log(f"set-up: {time.perf_counter() - t_start:.3f} s with the program "
        f"built")
    ens = workload.warm_up(prog)
    log(f"set-up: {time.perf_counter() - t_start:.3f} s with one chunk "
        f"warmed up")

    annotate: Callable = lambda name: contextlib.nullcontext()  # noqa
    trace_dir = tempfile.TemporaryDirectory() if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
        annotate = lambda name: jax.profiler.TraceAnnotation(  # noqa
            tr.SPAN_PREFIX + name)
    setup_s = time.perf_counter() - t_start
    with annotate("window"):
        win = workload.measure(prog, ens, seconds, annotate)
    if traced:
        jax.profiler.stop_trace()
    peak = workload.memory_peak_bytes(prog.devices)
    log(f"window: {win.chunks} chunks x {prog.chunk_cycles} cycles in "
        f"{win.seconds:.6f} s; chunk seconds min {min(win.chunk_seconds)!r}"
        f" median {statistics.median(win.chunk_seconds)!r}"
        f" max {max(win.chunk_seconds)!r}; "
        f"programs compiled or loaded inside the window: {win.compiles}")

    # the program and its state go before the reference runs on the device
    failed = sum(int(r["failed"]) for r in win.history)
    io = check.chunk_io(win.ens_before_last, win.ens_after,
                        prog.driver.history, prog.chunk_cycles, failed)
    n_replicas, md_steps = prog.n_replicas, prog.md_steps
    device_ids = [d.id for d in prog.devices]
    del prog, ens, win.ens_before_last, win.ens_after
    gc.collect()

    trace = None
    if traced:
        trace = tr.load(trace_dir.name, device_ids)
        trace_dir.cleanup()

    r = Run(workload=workload_name, config=cell["config"],
            traffic=cell["traffic"], setup_s=setup_s, window_s=win.seconds,
            chunks=win.chunks, cycles=win.cycles,
            chunk_seconds=win.chunk_seconds, n_replicas=n_replicas,
            md_steps=md_steps, replica_steps=win.replica_steps,
            n_devices=chips, device_kind=kind, memory_peak_bytes=peak,
            peaks=peaks, trace=trace)
    metrics = read_metrics(cell["per_layer" if traced else "end_to_end"], r,
                           root)

    t = time.perf_counter()
    deployment = spec.kind_module(cell["config"]["kind"], root)
    checks = deployment.compare(cell["config"], cell["traffic"],
                                cell["limits"], io)
    log(f"check took {time.perf_counter() - t:.3f} s")
    correct = check.passed(checks)
    device: Dict[str, Any] = {"platform": devs[0].platform, "kind": kind,
                              "count": chips, "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": win.cycles * n_replicas,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = trace.window_ns / 1e9
        result["breakdown"] = tr.breakdown(trace)
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), file=out, flush=True)
    return 0


def read_metrics(entries: List[dict], run_: Run, root: Path = spec.ROOT
                 ) -> Dict[str, dict]:
    """Each metric's reader, by name; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = spec.metric_module(m["name"], root).read(run_)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
