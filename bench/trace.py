"""Reduce a profiler trace to the intervals the per-layer metrics read.

A traced run records the window with ``jax.profiler`` and the harness's
own host spans (``jax.profiler.TraceAnnotation``, named ``bench:...``):
one around the window and one around each call into the program.  The
loader keeps, per device, the operations of its ``XLA Ops`` line and
the program executions of its ``XLA Modules`` line, and from the host
the harness's spans; everything else in the trace is dropped.

The reduced form is plain JSON (``Trace.to_json``), so the arithmetic
below is tested on a small recorded trace without a chip.  Times are
nanoseconds on the profiler's common clock.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Event = Tuple[str, float, float]            # (name, start_ns, end_ns)
Interval = Tuple[float, float]


@dataclass
class Trace:
    window: Interval
    ops: Dict[int, List[Event]] = field(default_factory=dict)
    modules: Dict[int, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def to_json(self) -> dict:
        return {"window": list(self.window),
                "ops": {str(k): v for k, v in self.ops.items()},
                "modules": {str(k): v for k, v in self.modules.items()},
                "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        ev = lambda rows: [(str(n), float(a), float(b))   # noqa: E731
                           for n, a, b in rows]
        return cls(window=tuple(d["window"]),
                   ops={int(k): ev(v) for k, v in d["ops"].items()},
                   modules={int(k): ev(v) for k, v in d["modules"].items()},
                   spans=ev(d["spans"]))


def load(log_dir: str, device_ids: Optional[Iterable[int]] = None) -> Trace:
    """Read the newest ``*.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    keep = None if device_ids is None else set(device_ids)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if keep is not None and dev not in keep:
                continue
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    rows = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(dev, []).extend(rows)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    _, lo, hi = windows[-1]
    for d in (ops, modules):
        for dev in d:
            d[dev] = sorted(e for e in d[dev] if e[2] > lo and e[1] < hi)
    return Trace(window=(lo, hi), ops=ops, modules=modules,
                 spans=sorted(spans, key=lambda s: s[1]))


# -- interval arithmetic ---------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events of one line that hold no other event of positive
    length: a loop op (``while``) spans its whole body, so counting it
    would cover every gap between the body's ops."""
    evs = sorted((e for e in events if e[2] > e[1]),
                 key=lambda e: (e[1], -e[2]))
    holds = [False] * len(evs)
    stack: List[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            holds[stack[-1]] = True
        stack.append(i)
    return [e for e, h in zip(evs, holds) if not h]


def busy(trace: Trace, dev: int) -> List[Interval]:
    """Union of the device's leaf operation intervals inside the
    window."""
    return union(clip(((a, b) for _, a, b in leaves(trace.ops.get(dev, []))),
                      *trace.window))


def gaps(covered: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    """The parts of [lo, hi] that ``covered`` (disjoint) leaves open."""
    out, t = [], lo
    for a, b in covered:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def subtract(intervals: Iterable[Interval], covered: Sequence[Interval]
             ) -> float:
    """Total length of ``intervals`` not covered by ``covered``."""
    cov = union(covered)
    return sum(total(gaps(cov, a, b)) for a, b in union(intervals))


def matching(events: Iterable[Event], patterns: Sequence[str]
             ) -> List[Event]:
    """Events whose name matches any of the regular expressions."""
    rx = [re.compile(p) for p in patterns]
    return [e for e in events if any(r.search(e[0]) for r in rx)]


def span_at(trace: Trace, t: float) -> str:
    """The innermost harness span open at time ``t``, or "outside"."""
    best: Optional[Event] = None
    for s in trace.spans:
        if s[1] <= t < s[2] and (best is None or s[1] >= best[1]):
            best = s
    return best[0][len(SPAN_PREFIX):] if best else "outside"


def device_ids(trace: Trace) -> List[int]:
    return sorted(trace.ops)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = device_ids(trace)
    return sum(total(busy(trace, d)) for d in devs) / max(len(devs), 1) / 1e9


def self_times(events: Iterable[Event]) -> List[Tuple[str, float]]:
    """(name, self ns) of each event of one line: its duration less the
    events nested inside it (a loop op holds its body's ops)."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[n, b - a] for n, a, b in evs]
    stack: List[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]][1] -= min(b, evs[stack[-1]][2]) - a
        stack.append(i)
    return [(n, t) for n, t in out]


def short_name(hlo: str) -> str:
    """``%name = type op(...)`` -> ``name op type``, the type cut short."""
    name, _, rest = hlo.partition(" = ")
    m = re.search(r"[)}\]] ([a-z][\w-]*)\(", rest)
    kind = m.group(1) if m else ""
    typ = rest[:m.start() + 1] if m else rest
    return f"{name.lstrip('%')} {kind} {typ[:48]}".strip()


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time (self seconds, averaged
    over the devices) and the longest idle gaps, named by the harness
    span open at their middle."""
    devs = device_ids(trace)
    per_op: Dict[str, float] = {}
    for d in devs:
        clipped = [(n, max(a, trace.window[0]), min(b, trace.window[1]))
                   for n, a, b in trace.ops[d]]
        for name, t in self_times(clipped):
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + t / 1e9 / len(devs)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    idle = []
    for d in devs:
        for a, b in gaps(busy(trace, d), *trace.window):
            idle.append((f"{span_at(trace, (a + b) / 2)} (device {d})",
                         (b - a) / 1e9))
    idle.sort(key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle[:top]]}


def roofline_share(run, names: Sequence[str], work) -> Optional[float]:
    """Share (%) of its roofline that the kernels matching ``names``
    reach: (calls x least time of one call) / their device time, over
    all chips.  ``work(system, replicas)`` gives one call's (flops,
    bytes) at a chip's replica block; the least time is the larger of
    flops / peak FLOP/s and bytes / peak bytes/s."""
    t = run.trace
    if t is None or run.peaks is None:
        return None
    calls, busy_ns = 0, 0.0
    for ops in t.ops.values():
        hits = matching(ops, names)
        calls += len(hits)
        busy_ns += total(clip([(a, b) for _, a, b in hits], *t.window))
    if not calls or busy_ns <= 0:
        return None
    flops, nbytes = work(run.config["system"],
                         run.n_replicas // run.n_devices)
    least = max(flops / run.peaks["peak_flops"],
                nbytes / run.peaks["peak_bytes_per_s"])
    return 100.0 * calls * least / (busy_ns / 1e9)
