"""Aggregate simulated nanoseconds per day: every replica-MD-step the
window completed, times the configuration's timestep, over the window's
wall seconds (host clock, whole chunks)."""

SECONDS_PER_DAY = 86400.0


def read(run):
    dt_ps = float(run.config["integrator"]["dt_ps"])
    return run.replica_steps * dt_ps * 1e-3 * SECONDS_PER_DAY / run.window_s
