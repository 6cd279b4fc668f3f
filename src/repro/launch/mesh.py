"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state.  The single-pod mesh is
16x16 = 256 chips (one TPU v5e pod in this project's hardware model); the
multi-pod mesh adds a leading "pod" axis: 2 x 16 x 16 = 512 chips.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_test_mesh():
    """1-device mesh with production axis names, for CPU tests."""
    return jax.make_mesh((1, 1), ("data", "model"))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    return jax.make_mesh((n_data, n_model), ("data", "model"))


def make_replica_mesh(n_shards: int = 0):
    """1-D ``("replica",)`` mesh for replica-sharded REMD
    (``REMDDriver.run_sharded``).

    Each of the ``n_shards`` devices owns a contiguous block of
    ``R / n_shards`` replicas; ``n_shards = 0`` (the default) uses every
    visible device.  On CPU, export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE jax
    initializes to test multi-shard execution without accelerators —
    this is how CI exercises the path (see docs/SCALING.md).
    """
    n_shards = n_shards or jax.device_count()
    if n_shards > jax.device_count():
        raise ValueError(
            f"make_replica_mesh({n_shards}) needs {n_shards} devices but "
            f"only {jax.device_count()} are visible (on CPU, set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes)")
    # Auto axis: arrays on this mesh carry no sharding in their types, so
    # jitted code outside the shard_map (the start-of-run device_put and
    # cycle fetch, the chunk-boundary bookkeeping) partitions them by
    # propagation
    return jax.make_mesh((n_shards,), ("replica",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def best_replica_shards(n_replicas: int,
                        max_devices: int = 0) -> int:
    """Largest usable shard count for ``n_replicas`` on the CURRENT
    device set: the biggest divisor of the replica count that does not
    exceed the visible (or ``max_devices``-capped) device count.

    This is the elastic-restart resource map (docs/FAULT_TOLERANCE.md):
    a run checkpointed on one mesh calls this on whatever devices
    SURVIVE and reshards onto the answer — losing (or gaining) devices
    changes the mesh shape, never the trajectory."""
    n = jax.device_count()
    if max_devices:
        n = min(n, max_devices)
    n = max(min(n, n_replicas), 1)
    while n_replicas % n:
        n -= 1
    return n


# --- ladder-neighbor permutation tables (halo exchange) --------------------
#
# The replica mesh is a RING in ladder order: shard s holds the contiguous
# replica block [s*B, (s+1)*B) with B = R / n_shards, and — because the
# control grid flattens ROW-MAJOR (dim-major: the last exchange dimension
# is contiguous, earlier dimensions are strided; see
# ``ControlGrid.neighbor_pairs``) — those blocks are also contiguous runs
# of flat ctrl indices at t = 0 and stay the unit of halo locality for
# every dimension's DEO sweep thereafter.  The permutation tables below
# are the static ``lax.ppermute`` edge lists of that ring; the halo
# exchange (``repro.sharding.ring_all_gather``) hops blocks along them.


def ladder_neighbor_perms(n_shards: int, reverse: bool = False):
    """Static ``lax.ppermute`` edge list for the replica-ladder ring.

    ``[(s, s+1 mod S), ...]`` — each shard sends to its upper ladder
    neighbor (``reverse=True``: lower neighbor).  One table per mesh
    shape; both directions together are the full halo stencil of a
    1-D ladder decomposition.
    """
    if n_shards < 2:
        return []
    if reverse:
        return [(s, (s - 1) % n_shards) for s in range(n_shards)]
    return [(s, (s + 1) % n_shards) for s in range(n_shards)]


def ladder_shard_blocks(n_ctrl: int, n_shards: int):
    """The contiguous ``[lo, hi)`` replica block each shard owns, in
    dim-major (row-major flat ctrl) order — the layout contract shared
    by ``ensemble_specs``, ``modes.shard_rows`` and the halo exchange."""
    if n_ctrl % n_shards:
        raise ValueError(f"replica count {n_ctrl} is not divisible by "
                         f"{n_shards} shards")
    b = n_ctrl // n_shards
    return [(s * b, (s + 1) * b) for s in range(n_shards)]
