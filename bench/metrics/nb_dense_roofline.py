"""Share of its roofline that the dense nonbonded force kernel reaches.

Least time of one call = max(work flops / peak FLOP/s, work bytes /
peak bytes/s), from the algorithm's own work at the configuration's
shapes, whatever the kernel does to get there: every interacting
unordered pair once, ``FLOPS_PER_PAIR`` operations each, and the
positions, per-atom parameters and forces read or written once.  The
share is (calls x least time) / (the kernel's device time), over all
chips.  The pair math is float32 VPU work read against the chip's bf16
peak: a yardstick every change reads the same way, not a target.
"""
from bench import trace as tr

# The kernels carry no name in the trace yet; the dense nonbonded one is
# the TPU custom call that takes the (Np, Np) exclusion mask.
NAMES = (r"custom-call\(.*f32\[(\d+),\1\]\{.*tpu_custom_call",)
# displacement 3, r^2 5, 1/r^2 1, s6 3, s12 1, LJ coefficient 5,
# 1/r 2, Coulomb coefficient 3, sum 1, force vector 3, +/- onto both
# atoms 6 (each +, -, x, /, sqrt one operation)
FLOPS_PER_PAIR = 33


def interacting_pairs(system: dict) -> int:
    """Unordered pairs at least ``excluded_separation`` apart."""
    n, s = int(system["n_atoms"]), int(system["excluded_separation"])
    return (n - s) * (n - s + 1) // 2 if n > s else 0


def work(system: dict, replicas: int):
    """(flops, bytes) of one call over ``replicas`` replicas."""
    n = int(system["n_atoms"])
    flops = replicas * interacting_pairs(system) * FLOPS_PER_PAIR
    nbytes = 4 * (2 * replicas * n * 3 + 3 * n)
    return flops, nbytes


def read(run):
    return tr.roofline_share(run, NAMES, work)
