"""RunReport — the structured run summary every driver path emits.

One dataclass, JSON-serializable, built by :func:`build_report` from a
driver (+ its optional :class:`~repro.obs.telemetry.Telemetry`
accumulator) at the end of ``run()`` / ``run_fused()`` /
``run_sharded()`` and stored as ``driver.last_report``.  Consumers: the
``repex_run`` CLI (``--report-out``) and CI (schema validation via
:func:`validate_report`).  Phase times are not in it: the Eq. (1) split
comes from a profiler trace (``repex_run --profile-dir``,
docs/OBSERVABILITY.md).

Schema (``docs/OBSERVABILITY.md`` is the narrative version):

  version, path, engine, force_path, pattern, scheme, exchange_comm,
  n_replicas, n_dims, chunk_cycles,
  cycles      {total, counted}            total = driver history rows;
                                          counted = cycles the telemetry
                                          counters cover (post-reset)
  phases      {t_cycle_mean, t_data_mean,     host-clock seconds per
               t_prep_mean}                     cycle
  exchange    {attempted, accepted, rate, per_dim{...},
               pair_attempt, pair_accept,       (D, 2, W) nested lists or
               occupancy, round_trips}          null (matrix scheme / off)
  failures    {total, relaunched, reinit_peer, degraded}
                                          escalation-ladder rollups
                                          (docs/FAULT_TOLERANCE.md)
  neighbor    {nb_overflow, nb_rebuilds}        end-of-run cumulative max
  wire        {per_chunk{K: {op: {count, bytes}}}, totals{op: ...}}
  meta        {backend, n_devices}

The report is an OBSERVATION — building it never touches device state,
so emitting it obeys the same observer-effect contract as the telemetry
itself (tests/test_telemetry.py).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Dict, Optional

import numpy as np

# v2: failures section gained the escalation-ladder counters
# (relaunched / reinit_peer / degraded)
# v3: phases lost the probe samples, means and eq1 (the split is read
# from a profiler trace instead)
REPORT_VERSION = 3

# top-level keys every report must carry (CI schema check)
_REQUIRED = ("version", "path", "engine", "pattern", "scheme",
             "n_replicas", "n_dims", "cycles", "phases", "exchange",
             "failures", "neighbor", "wire", "meta")


def _jsonable(x):
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return _jsonable(x.tolist())
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        return float(x)
    return x


@dataclasses.dataclass
class RunReport:
    """Structured summary of one driver run (see module docstring)."""
    version: int
    path: str                       # "run" | "fused" | "sharded"
    engine: str
    force_path: Optional[str]
    pattern: str
    scheme: str
    exchange_comm: str
    n_replicas: int
    n_dims: int
    chunk_cycles: Optional[int]
    cycles: Dict[str, int]
    phases: Dict[str, Any]
    exchange: Dict[str, Any]
    failures: Dict[str, Any]
    neighbor: Dict[str, float]
    wire: Dict[str, Any]
    meta: Dict[str, Any]

    def to_dict(self) -> Dict[str, Any]:
        return _jsonable(dataclasses.asdict(self))

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")
        return path


def validate_report(d: Dict[str, Any]) -> Dict[str, Any]:
    """Schema check for a report dict (CI runs this on --report-out
    output).  Raises ``ValueError`` with every problem found."""
    problems = []
    for k in _REQUIRED:
        if k not in d:
            problems.append(f"missing key {k!r}")
    if not problems:
        if d["version"] != REPORT_VERSION:
            problems.append(f"version {d['version']} != {REPORT_VERSION}")
        if d["path"] not in ("run", "fused", "sharded"):
            problems.append(f"bad path {d['path']!r}")
        cyc = d["cycles"]
        if not (isinstance(cyc, dict) and "total" in cyc
                and "counted" in cyc):
            problems.append("cycles must carry total/counted")
        ex = d["exchange"]
        for k in ("attempted", "accepted", "rate", "per_dim"):
            if k not in ex:
                problems.append(f"exchange missing {k!r}")
        if not problems and ex["accepted"] > ex["attempted"]:
            problems.append("accepted > attempted")
        for k in ("nb_overflow", "nb_rebuilds"):
            if k not in d["neighbor"]:
                problems.append(f"neighbor missing {k!r}")
        for k in ("total", "relaunched", "reinit_peer", "degraded"):
            if k not in d["failures"]:
                problems.append(f"failures missing {k!r}")
    if problems:
        raise ValueError("invalid RunReport: " + "; ".join(problems))
    return d


def build_report(driver, path: str,
                 chunk_cycles: Optional[int] = None) -> RunReport:
    """Assemble a :class:`RunReport` from a driver's bookkeeping.

    Works with or without a live telemetry accumulator: counters the
    telemetry did not collect (disabled, or ``telemetry=None``) fall
    back to what ``driver.history`` already carries — pair-resolved
    counters, occupancy/round-trips and the wire ledger are
    telemetry-only and reported as null/empty when absent.
    """
    import jax

    tel = getattr(driver, "telemetry", None)
    if tel is not None and not tel.enabled:
        tel = None
    hist = driver.history
    caps = driver.capabilities
    cfg = driver.cfg

    # -- exchange totals (driver.acceptance is always maintained) --------
    per_dim = {}
    att_tot = acc_tot = 0.0
    for k, (a, n) in driver.acceptance.items():
        per_dim[k] = {"attempted": n, "accepted": a,
                      "rate": a / max(n, 1.0)}
        att_tot += n
        acc_tot += a

    exchange: Dict[str, Any] = {
        "attempted": att_tot, "accepted": acc_tot,
        "rate": acc_tot / max(att_tot, 1.0), "per_dim": per_dim,
        "pair_attempt": None, "pair_accept": None,
        "occupancy": None, "round_trips": None,
    }
    counted = 0
    if tel is not None:
        counted = tel.n_cycles_seen
        if tel.pair_attempt is not None:
            exchange["pair_attempt"] = tel.pair_attempt
            exchange["pair_accept"] = tel.pair_accept
        if tel.occupancy is not None:
            exchange["occupancy"] = tel.occupancy
            exchange["round_trips"] = tel.round_trips

    # -- phases ----------------------------------------------------------
    if tel is not None and tel.n_cycles_seen:
        t_cycle = tel.t_cycle_total / tel.n_cycles_seen
        t_data = tel.t_data_total / tel.n_cycles_seen
        t_prep = tel.t_prep_total / tel.n_cycles_seen
    elif hist:
        t_cycle = float(np.mean([h["t_step"] for h in hist]))
        t_data = float(np.mean([h["t_data"] for h in hist]))
        t_prep = float(np.mean([h["t_prep"] for h in hist]))
    else:
        t_cycle = t_data = t_prep = 0.0
    phases = {"t_cycle_mean": t_cycle, "t_data_mean": t_data,
              "t_prep_mean": t_prep}

    # -- failures / neighbor-list rollups --------------------------------
    failures = {
        "total": int(sum(h["failed"] for h in hist)),
        "relaunched": int(sum(h.get("esc_relaunch", 0) for h in hist)),
        "reinit_peer": int(sum(h.get("esc_reinit", 0) for h in hist)),
        "degraded": int(sum(h.get("esc_dead", 0) for h in hist)),
    }
    # nb counters are cumulative per run — the rollup is the running max
    neighbor = {
        "nb_overflow": float(max((h["nb_overflow"] for h in hist),
                                 default=0.0)),
        "nb_rebuilds": float(max((h["nb_rebuilds"] for h in hist),
                                 default=0.0)),
    }

    wire: Dict[str, Any] = {}
    if tel is not None and tel.wire:
        wire = {"per_chunk": {str(k): v["per_chunk"]
                              for k, v in tel.wire.items()},
                "invocations": {str(k): v["invocations"]
                                for k, v in tel.wire.items()},
                "totals": tel.wire_totals()}

    return RunReport(
        version=REPORT_VERSION,
        path=path,
        engine=type(driver.engine).__name__,
        force_path=caps.get("force_path"),
        pattern=cfg.pattern,
        scheme=cfg.exchange_scheme,
        exchange_comm=cfg.exchange_comm,
        n_replicas=driver.grid.n_ctrl,
        n_dims=len(driver.grid.dims),
        chunk_cycles=chunk_cycles,
        cycles={"total": len(hist), "counted": counted},
        phases=phases,
        exchange=exchange,
        failures=failures,
        neighbor=neighbor,
        wire=wire,
        meta={"backend": jax.default_backend(),
              "n_devices": jax.device_count()},
    )
