"""The cross-path conformance matrix pinning the fused BAOAB propagate.

Every propagate implementation of the stock MD engine — the per-replica
vmap oracle (PR 1), the replica-major autodiff path ("batched"), the
analytic per-pass path ("pallas") and the fused force+update path
("fused") — must tell the SAME replica-exchange story.  The contract,
swept here as a matrix over

    force_path x bonded x nonbonded x pattern x scheme x chunk size
    (+ a 1-shard / 8-shard ``run_sharded`` cell),

is two-sided:

  * DISCRETE, bitwise: per-cycle assignment trace, acceptance counters,
    per-dimension history rows and alive masks equal the vmap oracle's
    exactly.  The exchange decision is a threshold on float energies, so
    this only holds because every path folds the identical per-replica
    noise stream (``fold_in(key_r, t)``) and shares one masked update
    graph (``integrators.baoab_fused_iteration``);
  * FLOAT, tolerance-bounded: final positions/velocities track the
    oracle to XLA-fusion rounding (measured ~1e-6 pos / ~2e-5 vel over
    a 6-cycle run; pinned at ~100x margin).

The sparse cells use a full-capture neighbor list (cutoff beyond every
pair, ``k_max = N - 1``) so all cells simulate the same physics and the
oracle stays one dense/dense run.

The second half of the file holds the seeded property pins (the
container has no ``hypothesis``; randomization is explicit via
parametrized seeds): single-iteration bitwise delegation, the in-body
noise stream, OU stationary statistics of the fused loop, and
100-step stability on randomized chain topologies — plus the
feature-interaction pins (kill/resume with the fused+sparse+planes+
relaunch-budget stack live; telemetry observer-effect on the fused
path).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RepExConfig
from repro.core import (REMDDriver, build_grid, control_multiset_ok,
                        ctrl_for_assignment)
from repro.launch.mesh import make_replica_mesh
from repro.md import MDEngine
from repro.md import integrators as I
from repro.md.system import chain_molecule
from repro.obs import Telemetry

N_DEVICES = jax.device_count()

multidevice = pytest.mark.skipif(
    N_DEVICES < 8,
    reason="needs 8 devices — export "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
           "jax initializes (see docs/SCALING.md)")

# TSU grid: exercises the umbrella and salt ctrl reductions on top of
# the temperature ladder (8 replicas)
DIMS = (("temperature", 2), ("umbrella", 2), ("salt", 2))
# sparse legs capture every pair -> identical physics to the dense cells
FULL_CAPTURE = {"cutoff": 1e3, "k_max": 21}
# measured cross-path drift after the 6-cycle run: <=9.6e-7 pos,
# <=2.3e-5 vel — pinned ~100x above
POS_ATOL = 1e-4
VEL_ATOL = 1e-3

FORCE_PATHS = ("vmap", "batched", "pallas", "fused")


def _cfg(pattern="synchronous", scheme="neighbor"):
    return RepExConfig(dimensions=DIMS, md_steps_per_cycle=3, n_cycles=6,
                       pattern=pattern, exchange_scheme=scheme)


def _engine(force_path, **kw):
    if force_path == "vmap":
        return MDEngine(batched=False, **kw)
    return MDEngine(force_path=force_path, **kw)


def _run(force_path, chunk=3, pattern="synchronous", scheme="neighbor",
         **engine_kw):
    d = REMDDriver(_engine(force_path, **engine_kw), _cfg(pattern, scheme))
    ens = d.run_fused(d.init(), chunk_cycles=chunk)
    return d, ens


# one oracle run per (pattern, scheme) — shared across every cell
_ORACLE = {}


def _oracle(pattern="synchronous", scheme="neighbor"):
    key = (pattern, scheme)
    if key not in _ORACLE:
        _ORACLE[key] = _run("vmap", chunk=3, pattern=pattern, scheme=scheme)
    return _ORACLE[key]


def _assert_conforms(d, ens, pattern="synchronous", scheme="neighbor"):
    """The two-sided contract vs the vmap oracle of the same cell."""
    d0, ens0 = _oracle(pattern, scheme)
    # discrete: bitwise
    np.testing.assert_array_equal(np.asarray(ens.assignment),
                                  np.asarray(ens0.assignment))
    np.testing.assert_array_equal(np.asarray(ens.alive),
                                  np.asarray(ens0.alive))
    assert d.acceptance == d0.acceptance
    assert len(d.history) == len(d0.history)
    for h, h0 in zip(d.history, d0.history):
        for key in ("cycle", "dim", "accept", "attempt", "failed"):
            assert h[key] == h0[key], key
        np.testing.assert_array_equal(np.asarray(h["assignment"]),
                                      np.asarray(h0["assignment"]))
    # float: tolerance-bounded
    np.testing.assert_allclose(np.asarray(ens.state["pos"]),
                               np.asarray(ens0.state["pos"]),
                               atol=POS_ATOL)
    np.testing.assert_allclose(np.asarray(ens.state["vel"]),
                               np.asarray(ens0.state["vel"]),
                               atol=VEL_ATOL)


# ---------------------------------------------------------------------------
# The matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [2, 3])
@pytest.mark.parametrize("force_path", FORCE_PATHS)
def test_matrix_force_path_by_chunk(force_path, chunk):
    """Every force path x chunk size vs the vmap/chunk=3 oracle (the
    chunk sweep re-pins the scan-length invariance of the force-sharing
    loop on the new path)."""
    d, ens = _run(force_path, chunk=chunk)
    _assert_conforms(d, ens)


@pytest.mark.parametrize("scheme", ["neighbor", "matrix"])
@pytest.mark.parametrize("pattern", ["synchronous", "asynchronous"])
@pytest.mark.parametrize("force_path", ["batched", "pallas", "fused"])
def test_matrix_force_path_by_pattern_scheme(force_path, pattern, scheme):
    """Every non-oracle path x exchange pattern x scheme, each cell vs
    the vmap oracle of the SAME (pattern, scheme) — the async masking
    (heterogeneous n_steps) and the Gibbs re-pairing must not expose
    path-dependent rounding in the decisions."""
    d, ens = _run(force_path, pattern=pattern, scheme=scheme)
    _assert_conforms(d, ens, pattern, scheme)


@pytest.mark.parametrize("nonbonded", ["dense", "sparse"])
@pytest.mark.parametrize("bonded", ["dense", "sparse"])
@pytest.mark.parametrize("force_path", ["pallas", "fused"])
def test_matrix_force_path_by_bonded_nonbonded(force_path, bonded,
                                               nonbonded):
    """The kernel-capable paths x bonded x nonbonded (sparse cells on
    the full-capture list, so the dense/dense vmap oracle is the
    baseline for all four combinations)."""
    kw = {"bonded": bonded}
    if nonbonded == "sparse":
        kw.update(nonbonded="sparse", **FULL_CAPTURE)
    d, ens = _run(force_path, **kw)
    _assert_conforms(d, ens)


def test_matrix_sharded_cell_one_shard():
    """The fused path under ``run_sharded`` on the degenerate 1-shard
    mesh: same decisions as the unsharded vmap oracle."""
    d = REMDDriver(_engine("fused"), _cfg())
    ens = d.run_sharded(d.init(), mesh=make_replica_mesh(1),
                        chunk_cycles=3)
    _assert_conforms(d, ens)
    assert control_multiset_ok(ens)


@multidevice
def test_matrix_sharded_cell_8shards():
    """The real thing: fused path sharded 1 replica per device — the
    halo exchange + feature all-gather must preserve the oracle's
    decisions bit for bit."""
    d = REMDDriver(_engine("fused"), _cfg())
    ens = d.run_sharded(d.init(), mesh=make_replica_mesh(8),
                        chunk_cycles=3)
    _assert_conforms(d, ens)
    assert control_multiset_ok(ens)


# ---------------------------------------------------------------------------
# Seeded property pins (no hypothesis in the container — randomization
# is explicit, parametrized seeds)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n", [5, 8])
def test_property_in_body_noise_stream_bitwise(seed, n):
    """The fused path's in-loop draw (a traced loop index) is BITWISE
    the pre-drawn stacked stream, per step, for odd and even draw sizes
    — the hinge of cross-path decision equality."""
    rngs = jax.random.split(jax.random.key(seed), 4)
    stacked = I.stacked_step_noise(rngs, 6, (n, 3))

    def in_loop(rngs):
        return jax.lax.fori_loop(
            0, 6, lambda t, acc: acc.at[t].set(I.step_noise(rngs, t, (n, 3))),
            jnp.zeros_like(stacked))

    got = jax.jit(in_loop)(rngs)
    for t in range(6):
        np.testing.assert_array_equal(np.asarray(got[t]),
                                      np.asarray(stacked[t]), err_msg=f"t={t}")


@pytest.mark.parametrize("seed", [0, 3, 11, 42])
def test_property_single_iteration_bitwise_delegation(seed):
    """One fused iteration with hoisted scales == the in-body-scales
    form (``_baoab_apply``), bitwise under jit, across randomized
    stacks, masks and iteration indices — the single-step identity the
    whole matrix leans on."""
    ks = jax.random.split(jax.random.key(seed), 5)
    r, n = 3, 7
    pos = jax.random.normal(ks[0], (r, n, 3))
    vel = jax.random.normal(ks[1], (r, n, 3))
    f = jax.random.normal(ks[2], (r, n, 3)) * 10.0
    noise_i = jax.random.normal(ks[3], (r, n, 3))
    masses = jax.random.uniform(ks[4], (n,), minval=1.0, maxval=16.0)
    temperature = jnp.asarray([250.0, 300.0, 350.0])
    n_steps = jnp.asarray([4, 0, 2], jnp.int32)    # active / idle / short
    dt, gamma, max_steps = 5e-4, 5.0, 4

    @jax.jit
    def in_body(i):
        return I._baoab_apply(i, pos, vel, f, noise_i, masses, temperature,
                              n_steps, max_steps, dt, gamma, 0.0)

    @jax.jit
    def hoisted(i):
        c1, scale = I.baoab_scales(masses, temperature, dt, gamma)
        return I.baoab_fused_iteration(i, pos, vel, f, noise_i, c1, scale,
                                       masses, n_steps, max_steps, dt, 0.0)

    for i in (0, 1, 2, 4):
        p_a, v_a = in_body(jnp.asarray(i))
        p_b, v_b = hoisted(jnp.asarray(i))
        np.testing.assert_array_equal(np.asarray(p_a), np.asarray(p_b),
                                      err_msg=f"pos i={i}")
        np.testing.assert_array_equal(np.asarray(v_a), np.asarray(v_b),
                                      err_msg=f"vel i={i}")


def test_property_ou_stationary_statistics():
    """The fused loop on a harmonic force field is an exact OU process:
    started FROM the stationary distribution it must stay there —
    configurational variance ``KB T / k`` and kinetic temperature ``T``
    within statistical error after 500 steps."""
    r, n = 64, 8
    k_spring, temp = 10.0, 300.0
    dt, gamma, steps = 1e-3, 5.0, 500
    masses = jnp.full((n,), 12.0)
    kp, kv, kr = jax.random.split(jax.random.key(2026), 3)
    var = I.KB * temp / k_spring
    state = {
        "pos": jax.random.normal(kp, (r, n, 3)) * jnp.sqrt(var),
        "vel": I.maxwell_boltzmann(kv, masses, temp, (r, n, 3)),
    }
    rngs = jax.random.split(kr, r)
    temperature = jnp.full((r,), temp)
    n_steps = jnp.full((r,), steps, jnp.int32)

    out, _ = jax.jit(lambda s: I.propagate_replica_major_fused(
        s, lambda p, aux: (-k_spring * p, aux), (), masses, temperature,
        n_steps, rngs, max_steps=steps, dt=dt, gamma=gamma))(state)

    pos = np.asarray(out["pos"])                     # 64*8*3 iid samples
    assert np.var(pos) == pytest.approx(var, rel=0.15)
    assert abs(np.mean(pos)) < 5.0 * np.sqrt(var / pos.size)
    t_kin = np.asarray(I.kinetic_temperature(out["vel"], masses))
    assert np.mean(t_kin) == pytest.approx(temp, rel=0.10)


@pytest.mark.parametrize("n_atoms,seed", [(8, 3), (12, 5), (22, 7)])
def test_property_hundred_step_stability(n_atoms, seed):
    """100 fused-path steps on a randomized chain topology stay sane:
    finite state, no failure detector fires, kinetic energy stays
    BOUNDED.  Randomized topologies start strained, so the thermostat
    transiently runs hot (measured peaks ~3400 K) while the excess
    potential energy drains — the pin is a hard ceiling a diverging
    integrator (exponential KE growth, NaN in tens of steps) blows
    through immediately, not an equilibrium statement."""
    eng = MDEngine(system=chain_molecule(n_atoms=n_atoms, seed=seed),
                   force_path="fused")
    grid = build_grid(RepExConfig(dimensions=(("temperature", 4),)))
    ctrl = ctrl_for_assignment(grid, jnp.arange(4))
    state = eng.init_state(jax.random.key(seed), 4)
    rngs = jax.random.split(jax.random.key(seed + 100), 4)
    n_steps = jnp.full((4,), 100, jnp.int32)
    out = eng.propagate(state, ctrl, n_steps, rngs, max_steps=100)
    for k in ("pos", "vel"):
        assert bool(jnp.all(jnp.isfinite(out[k]))), k
    assert not bool(jnp.any(eng.is_failed(out)))
    t_kin = np.asarray(I.kinetic_temperature(out["vel"], eng.system.masses))
    t_ladder = np.asarray(ctrl["temperature"])
    assert np.all(t_kin > 10.0) and np.all(t_kin < 20.0 * t_ladder)


# ---------------------------------------------------------------------------
# Feature-interaction pins
# ---------------------------------------------------------------------------


def test_interaction_resume_fused_sparse_planes_relaunch(tmp_path):
    """ONE run stacking the features that each have their own suite:
    fused force path + sparse bonded + pair-plane sparse nonbonded +
    live failure injection + relaunch budget + checkpointing.  Killed
    mid-run and resumed, it must stitch bitwise to the uninterrupted
    run — the aux neighbor-list carry, the escalation counters and the
    fused loop's noise stream all survive the boundary together."""
    from tests.test_fault_tolerance import \
        _assert_stitched_equals_uninterrupted

    def driver(**kw):
        eng = MDEngine(force_path="fused", bonded="sparse",
                       nonbonded="sparse", nb_pair_planes=True)
        cfg = RepExConfig(dimensions=(("temperature", 6),),
                          md_steps_per_cycle=3, n_cycles=8,
                          relaunch_budget=2)
        return REMDDriver(eng, cfg, failure_rate=0.3,
                          telemetry=Telemetry(), **kw)

    ref = driver()
    e_ref = ref.run_fused(ref.init(), chunk_cycles=3)

    a = driver(ckpt_dir=str(tmp_path), ckpt_every=1)
    a.run_fused(a.init(), n_cycles=5, chunk_cycles=3)   # ... kill here

    b = driver(ckpt_dir=str(tmp_path), ckpt_every=1)
    e_res = b.resume(via="fused", chunk_cycles=2)       # new chunk size
    assert len(b.history) == 8
    _assert_stitched_equals_uninterrupted(ref, b, e_ref, e_res)


@pytest.mark.parametrize("variant", ["dense", "sparse"])
def test_interaction_telemetry_invariance_fused_path(variant):
    """Observer-effect contract re-asserted on the NEW path: telemetry
    ON leaves the fused-path trajectory bitwise unchanged (dense and
    all-sparse engines)."""
    kw = {"force_path": "fused"}
    if variant == "sparse":
        kw.update(bonded="sparse", nonbonded="sparse")
    cfg = RepExConfig(dimensions=(("temperature", 4),),
                      md_steps_per_cycle=2, n_cycles=4)
    d_on = REMDDriver(MDEngine(**kw), cfg, telemetry=Telemetry())
    d_off = REMDDriver(MDEngine(**kw), cfg)
    d_on.run_fused(d_on.init(), chunk_cycles=2)
    d_off.run_fused(d_off.init(), chunk_cycles=2)
    np.testing.assert_array_equal(
        np.stack([h["assignment"] for h in d_on.history]),
        np.stack([h["assignment"] for h in d_off.history]))
    assert [(h["accept"], h["attempt"], h["failed"]) for h in d_on.history] \
        == [(h["accept"], h["attempt"], h["failed"]) for h in d_off.history]
    assert d_on.acceptance == d_off.acceptance
