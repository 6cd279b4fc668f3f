"""Drive the program's own entry for one cell: build, warm up, measure.

The program under test is ``REMDDriver`` from ``src/repro``: the cell's
configuration builds its system, engine and ladder (through its kind,
``bench/kinds/<kind>.py``), the traffic mix sets the MD steps per
exchange and the cycles per host sync, and the window
calls ``REMDDriver.run_fused`` (one chip) or ``run_sharded`` (a replica
mesh) once per chunk of K cycles, the same call a user makes.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, List, Optional

import jax

from bench import spec

# JAX records these once per program it compiles or loads from the
# persistent cache; either inside the window means set-up leaked into it
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


@dataclass
class Program:
    """The system under test, built from a cell's files and a seed."""
    driver: Any
    entry: Callable            # ensemble -> ensemble, one chunk of K cycles
    entry_name: str
    n_replicas: int
    md_steps: int
    chunk_cycles: int
    devices: List[Any]


@dataclass
class Window:
    """What one measured window did and produced."""
    seconds: float
    chunk_seconds: List[float]
    chunks: int
    cycles: int
    replica_steps: int
    compiles: int
    ens_before_last: Any        # input of the window's last chunk
    ens_after: Any              # the window's output
    history: List[dict] = field(default_factory=list)


def program_seed(seed: int) -> int:
    """The program's RepExConfig.seed: JAX keys take 32 bits."""
    return int(seed) % 2 ** 32


def build(cell: dict, seed: int, fault: Optional[Callable] = None,
          root: Path = spec.ROOT) -> Program:
    """The program as the cell's configuration states it, built by the
    configuration's kind (``bench/kinds/<kind>.py``).  ``fault`` (tests
    only) wraps the driver's chunk entry to plant a fault."""
    conf, traffic = cell["config"], cell["traffic"]
    kind = spec.kind_module(conf["kind"], root)
    driver = kind.driver(conf, traffic, program_seed(seed))
    k = int(traffic["cycles_per_sync"])
    shards = int(conf["replica_shards"])
    devices = jax.devices()[:shards]
    if shards > 1:
        from repro.launch.mesh import make_replica_mesh
        mesh = make_replica_mesh(shards)
        name = "REMDDriver.run_sharded"

        def entry(ens):
            return driver.run_sharded(ens, mesh=mesh, n_cycles=k,
                                      chunk_cycles=k)
    else:
        name = "REMDDriver.run_fused"

        def entry(ens):
            return driver.run_fused(ens, n_cycles=k, chunk_cycles=k)
    if fault is not None:
        entry = fault(entry, driver)
    return Program(driver=driver, entry=entry, entry_name=name,
                   n_replicas=driver.cfg.n_replicas,
                   md_steps=driver.cfg.md_steps_per_cycle, chunk_cycles=k,
                   devices=devices)


@contextlib.contextmanager
def count_compiles():
    """Count programs JAX compiles or loads inside the block."""
    seen = []

    def listener(event, _duration, **_kw):
        if event in COMPILE_EVENTS:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def warm_up(prog: Program):
    """Initial ensemble plus one chunk: every program the window calls is
    compiled (or loaded from the cache) here."""
    ens = prog.driver.init()
    ens = prog.entry(ens)
    jax.block_until_ready(ens)
    return ens


def measure(prog: Program, ens, seconds: float,
            annotate: Callable = contextlib.nullcontext) -> Window:
    """Whole chunks until ``seconds`` is reached to within half a chunk.
    ``annotate(name)`` opens a host span around each chunk call."""
    hist0 = len(prog.driver.history)
    times: List[float] = []
    with count_compiles() as compiles:
        t0 = time.perf_counter()
        while True:
            prev = ens
            t = time.perf_counter()
            with annotate(prog.entry_name):
                ens = prog.entry(ens)
                jax.block_until_ready(ens)
            times.append(time.perf_counter() - t)
            elapsed = time.perf_counter() - t0
            if elapsed + 0.5 * elapsed / len(times) >= seconds:
                break
        window_s = time.perf_counter() - t0
    cycles = len(times) * prog.chunk_cycles
    return Window(seconds=window_s, chunk_seconds=times, chunks=len(times),
                  cycles=cycles,
                  replica_steps=cycles * prog.n_replicas * prog.md_steps,
                  compiles=len(compiles), ens_before_last=prev,
                  ens_after=ens,
                  history=prog.driver.history[hist0:])


def memory_peak_bytes(devices) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
