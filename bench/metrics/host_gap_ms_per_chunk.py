"""Device-idle time between the chunk program's executions, per chunk:
the idle time the host loop leaves at each chunk boundary (dispatch,
the stats fetch, the driver's bookkeeping), averaged over the chips.

The chunk program is the module that ran longest on the device; idle
time inside its executions belongs to the cycle body, not here.
"""
from bench import trace as tr


def read(run):
    t = run.trace
    if t is None or not t.modules:
        return None
    per_dev = []
    for dev, mods in t.modules.items():
        longest = {}
        for name, a, b in mods:
            longest[name] = longest.get(name, 0.0) + b - a
        main = max(longest, key=longest.get)
        chunk = [(a, b) for name, a, b in mods if name == main]
        idle = tr.gaps(tr.busy(t, dev), *t.window)
        per_dev.append(tr.subtract(idle, chunk))
    return sum(per_dev) / len(per_dev) / run.chunks / 1e6
