"""What the check reads from a run, and how its verdict is formed.

The comparison itself belongs to the configuration's deployment kind
(``bench/kinds/<kind>.py``, ``compare``): it replays what the timed
window produced with the plain reference, at the timed sizes, once the
window has closed, and gives each number with its limit.  Limits come
from ``bench/limits/<workload>.json``, set from readings of sound runs
and of the control (see PERF.md).  A number that is not finite fails.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import numpy as np


@dataclass
class ChunkIO:
    """Host copies of what the checked chunk took and gave."""
    pos_in: np.ndarray          # (R, N, 3)
    vel_in: np.ndarray
    assign_in: np.ndarray       # (R,) rung held by each replica
    key_in: jax.Array           # the ensemble's PRNG key before the chunk
    cycle_in: int
    pos_out: np.ndarray
    assign_rows: List[np.ndarray]   # rung per replica after each cycle
    failed: int
    assign_prev: Optional[np.ndarray] = None   # row of the cycle before


def chunk_io(before, after, history: List[dict], k: int, failed: int
             ) -> ChunkIO:
    """From the program's ensembles around the last chunk of ``k``
    cycles, the driver's whole history and the failures it recovered."""
    return ChunkIO(
        pos_in=np.asarray(before.state["pos"]),
        vel_in=np.asarray(before.state["vel"]),
        assign_in=np.asarray(before.assignment),
        key_in=jax.device_put(before.rng, jax.devices()[0]),
        cycle_in=int(before.cycle),
        pos_out=np.asarray(after.state["pos"]),
        assign_rows=[np.asarray(r["assignment"]) for r in history[-k:]],
        failed=int(failed),
        assign_prev=(np.asarray(history[-k - 1]["assignment"])
                     if len(history) > k else None))


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
