"""Collective time on a chip during which no other leaf operation runs
on it, per exchange cycle; the largest over the chips.  Only cells on a
replica mesh have collectives; elsewhere there is nothing to read."""
from bench import trace as tr

COLLECTIVES = (r"collective-permute", r"all-reduce", r"all-gather",
               r"reduce-scatter", r"all-to-all")


def read(run):
    t = run.trace
    if t is None:
        return None
    worst = None
    for dev, ops in t.ops.items():
        coll = tr.matching(ops, COLLECTIVES)
        if not coll:
            continue
        names = {e[0] for e in coll}
        # leaf ops only: a loop op around the collectives is no compute
        other = [(a, b) for n, a, b in tr.leaves(ops) if n not in names]
        exposed = tr.subtract(tr.clip([(a, b) for _, a, b in coll],
                                      *t.window), other)
        worst = exposed if worst is None else max(worst, exposed)
    if worst is None:
        return None
    return worst / run.cycles / 1e6
