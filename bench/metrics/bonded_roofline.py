"""Share of its roofline that the bonded force kernel reaches.

The algorithm's work is O(N): every bond, angle and dihedral once, at
``FLOPS`` operations per term, and positions and forces moved once,
whatever contraction the kernel uses to scatter them.  Least time and
share as in ``nb_dense_roofline``.
"""
from bench import trace as tr

# The kernels carry no name in the trace yet; the bonded one is the TPU
# custom call that takes the bf16 one-hot gather matrix.
NAMES = (r"custom-call\(.*bf16\[\d+,\d+\]\{.*tpu_custom_call",)
# bond: vector 3, r 6, coefficient 4, force 3, onto 2 atoms 6;
# angle: arms 6, dot and norms 15, arccos and sine 5, gradient 27, onto
# 3 atoms 9; dihedral: arms 9, normals 18, |b1| 6, m1 12, atan2 and its
# dots 11, dE/dphi 4, gradient 43, onto 4 atoms 12
FLOPS = {"bond": 22, "angle": 62, "dihedral": 115}


def work(system: dict, replicas: int):
    """(flops, bytes) of one call over ``replicas`` replicas."""
    n = int(system["n_atoms"])
    terms = {"bond": n - 1, "angle": n - 2, "dihedral": n - 3}
    flops = replicas * sum(FLOPS[k] * terms[k] for k in terms)
    nbytes = 4 * (2 * replicas * n * 3 + 2 * sum(terms.values()))
    return flops, nbytes


def read(run):
    return tr.roofline_share(run, NAMES, work)
