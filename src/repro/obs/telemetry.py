"""On-device observability for REMD runs: the counters.

The paper's performance argument decomposes cycle time as

    T_c = T_MD + T_EX + T_data + T_RepEx_over + T_runtime_over     (Eq. 1)

and a fused K-cycle scan only ever shows the host their SUM.  The split
comes from a profiler trace: the cycle body's ``jax.named_scope``s and
the driver's host spans (``repro.obs.spans``) name each term
(docs/OBSERVABILITY.md).  This module keeps the counters a trace cannot
give, without perturbing the run:

  * **Exchange/wire counters** ride the fused cycle scan itself as extra
    per-cycle ys rows (``pair_attempt`` / ``pair_accept``, one row per
    DEO sweep, threaded ``exchange._decide_sweep`` ->
    ``patterns.fused_cycle`` -> ``repex._chunk_loop`` exactly like PR-6's
    ``_fail_row``): zero host round-trips inside a chunk, one fetch per
    chunk, and when telemetry is OFF the rows are popped before the jit
    boundary so the compiled program is IDENTICAL (op-budget-pinned,
    tests/test_telemetry.py).
  * **Rung occupancy / round trips** are folded on the host from the
    per-cycle ``assignment`` trace the driver already fetches (PR-4) —
    no extra device work at all.
  * **Wire ledger** (``run_sharded``): the compiled chunk's HLO is
    census'd with ``launch.hlo_analysis.collective_budget`` and scaled by
    the number of chunk invocations — measured bytes-per-collective for
    the run, attached to the :class:`~repro.obs.report.RunReport`.

A :class:`Telemetry` instance is both the configuration (which counters
are on) and the host-side accumulator (cleared by :meth:`reset`, e.g.
after a warm-up period).  ``REMDDriver(..., telemetry=Telemetry())``
activates it; the default ``telemetry=None`` changes NOTHING — not one
compiled op (the off switch is a true no-op).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np


def accumulate_occupancy(trace: np.ndarray, n_ctrl: int,
                         out: Optional[np.ndarray] = None) -> np.ndarray:
    """Fold a (C, R) assignment trace into (R, n_ctrl) occupancy counts.

    ``out[r, c]`` = number of cycles replica r held ctrl c.  Rows sum to
    the number of cycles folded (each replica holds exactly one ctrl per
    cycle), and the result is invariant under any permutation of the
    cycle axis — both pinned by tests/test_property.py.  Pass ``out`` to
    accumulate incrementally (chunk-by-chunk feeding is exactly
    equivalent to one-shot feeding).
    """
    trace = np.asarray(trace)
    if trace.ndim == 1:
        trace = trace[None, :]
    n_rep = trace.shape[1]
    if out is None:
        out = np.zeros((n_rep, n_ctrl), np.int64)
    np.add.at(out, (np.arange(n_rep)[None, :], trace), 1)
    return out


def round_trip_fold(trace: np.ndarray, n_ctrl: int,
                    phase: Optional[np.ndarray] = None,
                    counts: Optional[np.ndarray] = None):
    """Fold a (C, R) assignment trace into per-replica round-trip counts.

    A replica completes one round trip when it returns to the BOTTOM
    rung (ctrl 0) after having touched the TOP rung (ctrl n_ctrl - 1)
    since its previous bottom visit — the standard ladder-diffusion
    diagnostic (round-trip rate is what DEO/exchange-move optimization
    maximizes, Bittner et al. arXiv:0708.3627).  ``phase`` per replica:
    0 = never touched bottom, 1 = heading up (bottom touched), 2 = top
    touched (heading down).  Returns (phase, counts); pass them back to
    accumulate incrementally — chunked feeding == one-shot feeding
    (tests/test_property.py).
    """
    trace = np.asarray(trace)
    if trace.ndim == 1:
        trace = trace[None, :]
    n_rep = trace.shape[1]
    if phase is None:
        phase = np.zeros(n_rep, np.int8)
    if counts is None:
        counts = np.zeros(n_rep, np.int64)
    for row in trace:
        bottom = row == 0
        top = row == (n_ctrl - 1)
        counts = counts + ((phase == 2) & bottom)
        phase = np.where(bottom, 1, phase)          # 2 -> 1 counted above
        phase = np.where(top & (phase == 1), 2, phase)
    return phase, counts


@dataclass
class Telemetry:
    """Observability configuration + host-side accumulator (one run or
    several — ``REMDDriver`` accumulates across ``run*`` calls like
    ``driver.history``; :meth:`reset` clears, e.g. post-warm-up).

    ``enabled=False`` (or passing ``telemetry=None`` to the driver) is a
    TRUE no-op: the compiled programs are identical to an
    un-instrumented driver (pinned by tests/test_telemetry.py).
    """
    enabled: bool = True
    # per-pair attempt/accept counter rows riding the cycle scan
    # (neighbor/DEO scheme only — the Gibbs matrix scheme's pairings are
    # re-drawn per sweep, so a static pair-slot axis does not exist)
    exchange_counters: bool = True
    # census the compiled sharded chunk's collectives (run_sharded only)
    wire_ledger: bool = True

    # -- accumulators (host state, not config) ----------------------------
    pair_attempt: Optional[np.ndarray] = field(default=None, repr=False)
    pair_accept: Optional[np.ndarray] = field(default=None, repr=False)
    occupancy: Optional[np.ndarray] = field(default=None, repr=False)
    rt_phase: Optional[np.ndarray] = field(default=None, repr=False)
    round_trips: Optional[np.ndarray] = field(default=None, repr=False)
    wire: Dict[int, Dict[str, Any]] = field(default_factory=dict,
                                            repr=False)
    n_cycles_seen: int = field(default=0, repr=False)
    t_cycle_total: float = field(default=0.0, repr=False)
    t_data_total: float = field(default=0.0, repr=False)
    t_prep_total: float = field(default=0.0, repr=False)

    # -- lifecycle --------------------------------------------------------

    def reset(self) -> None:
        """Clear every accumulator (config flags are kept).  Call after a
        warm-up period so report counters cover only production cycles
        (tests/test_statistics.py does exactly this)."""
        self.pair_attempt = None
        self.pair_accept = None
        self.occupancy = None
        self.rt_phase = None
        self.round_trips = None
        self.wire = {}
        self.n_cycles_seen = 0
        self.t_cycle_total = 0.0
        self.t_data_total = 0.0
        self.t_prep_total = 0.0

    # -- per-chunk / per-cycle feeding ------------------------------------

    def note_cycles(self, *, cycles, dims, assignments, n_dims: int,
                    n_ctrl: int, pair_attempt=None, pair_accept=None,
                    t_cycle: float = 0.0, t_data: float = 0.0,
                    t_prep: float = 0.0) -> None:
        """Fold one chunk's fetched stats (K cycles) into the counters.

        ``assignments``: (K, R) post-cycle assignment rows.  ``cycles``:
        (K,) cycle indices (parity derives as (cycle // n_dims) % 2,
        matching ``patterns.fused_cycle``).  ``pair_attempt`` /
        ``pair_accept``: (K, W) per-sweep rows, or None when the counter
        rows are off / the scheme is matrix.  Timing args are TOTALS over
        the K cycles.
        """
        cycles = np.asarray(cycles).reshape(-1)
        dims = np.asarray(dims).reshape(-1)
        assignments = np.asarray(assignments)
        if assignments.ndim == 1:
            assignments = assignments[None, :]
        k = assignments.shape[0]

        self.occupancy = accumulate_occupancy(assignments, n_ctrl,
                                              self.occupancy)
        self.rt_phase, self.round_trips = round_trip_fold(
            assignments, n_ctrl, self.rt_phase, self.round_trips)

        if pair_attempt is not None:
            att = np.asarray(pair_attempt, np.float64)
            acc = np.asarray(pair_accept, np.float64)
            if att.ndim == 1:
                att, acc = att[None, :], acc[None, :]
            parity = (cycles // n_dims) % 2
            if self.pair_attempt is None:
                w = att.shape[-1]
                self.pair_attempt = np.zeros((n_dims, 2, w), np.float64)
                self.pair_accept = np.zeros((n_dims, 2, w), np.float64)
            np.add.at(self.pair_attempt, (dims, parity), att)
            np.add.at(self.pair_accept, (dims, parity), acc)

        self.n_cycles_seen += k
        self.t_cycle_total += t_cycle
        self.t_data_total += t_data
        self.t_prep_total += t_prep

    def note_wire_budget(self, chunk_cycles: int,
                         budget: Dict[str, Dict[str, int]]) -> None:
        """Record the compiled chunk's per-collective budget (one entry
        per distinct compiled chunk length)."""
        self.wire.setdefault(int(chunk_cycles),
                             {"per_chunk": budget, "invocations": 0})

    def note_wire_invocation(self, chunk_cycles: int) -> None:
        entry = self.wire.get(int(chunk_cycles))
        if entry is not None:
            entry["invocations"] += 1

    # -- checkpoint serialization (bitwise-resume contract) ---------------

    _ARRAY_FIELDS = ("pair_attempt", "pair_accept", "occupancy",
                     "rt_phase", "round_trips")

    def state_dict(self) -> Dict[str, Any]:
        """JSON-serializable snapshot of every accumulator (NOT the
        config flags — those belong to the relaunching driver).  Rides
        the driver checkpoint so a resumed run's RunReport counters equal
        an uninterrupted run's (docs/FAULT_TOLERANCE.md)."""
        out: Dict[str, Any] = {}
        for f in self._ARRAY_FIELDS:
            a = getattr(self, f)
            out[f] = (None if a is None
                      else {"dtype": str(a.dtype), "data": a.tolist()})
        out["wire"] = {str(k): v for k, v in self.wire.items()}
        out["n_cycles_seen"] = self.n_cycles_seen
        out["t_cycle_total"] = self.t_cycle_total
        out["t_data_total"] = self.t_data_total
        out["t_prep_total"] = self.t_prep_total
        return out

    def load_state_dict(self, d: Dict[str, Any]) -> None:
        """Inverse of :meth:`state_dict` (config flags untouched).  Keys
        it does not know are ignored: an older checkpoint's
        ``phase_samples`` and ``chunks_seen`` load."""
        for f in self._ARRAY_FIELDS:
            v = d.get(f)
            setattr(self, f, None if v is None
                    else np.asarray(v["data"], dtype=np.dtype(v["dtype"])))
        self.wire = {int(k): v for k, v in d.get("wire", {}).items()}
        self.n_cycles_seen = int(d.get("n_cycles_seen", 0))
        self.t_cycle_total = float(d.get("t_cycle_total", 0.0))
        self.t_data_total = float(d.get("t_data_total", 0.0))
        self.t_prep_total = float(d.get("t_prep_total", 0.0))

    # -- summaries --------------------------------------------------------

    def wire_totals(self) -> Dict[str, Dict[str, float]]:
        """Measured bytes per collective for the whole run: the static
        per-chunk budget (``hlo_analysis.collective_budget`` of the
        compiled chunk) scaled by how many times each compiled chunk
        actually ran."""
        totals: Dict[str, Dict[str, float]] = {}
        for entry in self.wire.values():
            inv = entry["invocations"]
            for op, b in entry["per_chunk"].items():
                t = totals.setdefault(op, {"count": 0.0, "bytes": 0.0})
                t["count"] += b["count"] * inv
                t["bytes"] += b["bytes"] * inv
        return totals
