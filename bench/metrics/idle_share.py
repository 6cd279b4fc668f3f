"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's leaf operation intervals) / window, averaged over
the cell's chips."""
from bench import trace as tr


def read(run):
    t = run.trace
    if t is None or not tr.device_ids(t):
        return None
    return 100.0 * (1.0 - tr.busy_s(t) * 1e9 / t.window_ns)
