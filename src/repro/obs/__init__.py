"""repro.obs — on-device observability for REMD runs.

:class:`Telemetry` (config + host accumulator) rides the fused cycle
scan; :class:`RunReport` is the structured summary every driver path
emits; :func:`span` names the driver's host work in a profiler trace.
See docs/OBSERVABILITY.md for the Eq. (1) mapping onto the trace's
scopes and spans and the observer-effect contract (telemetry off =
identical HLO; telemetry on = bitwise-identical trajectory).
"""
from repro.obs.report import (REPORT_VERSION, RunReport, build_report,
                              validate_report)
from repro.obs.spans import span
from repro.obs.telemetry import (Telemetry, accumulate_occupancy,
                                 round_trip_fold)

__all__ = [
    "REPORT_VERSION", "RunReport", "Telemetry", "accumulate_occupancy",
    "build_report", "round_trip_fold", "span", "validate_report",
]
