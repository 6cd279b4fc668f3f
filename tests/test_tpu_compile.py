"""Compile the MD kernels for a described TPU v5e, without the chip.

Interpret mode runs a Pallas kernel's body on the CPU but never asks
Mosaic, the TPU kernel compiler, to lower it: block shapes that break
the (8, 128) tiling, primitives Mosaic has no lowering for, and blocks
that overflow VMEM all pass there.  These tests compile each kernel of
the MD engine for a v5e chip that JAX describes but does not attach, at
the sizes the engine runs: the main path (``chain_forces`` and the
dense nonbonded kernel) at the paper's 2,881 atoms and R = 64, the
dense, fused and sparse kernels at the largest system the engine admits
on a TPU, and ``exchange_matrix`` at the paper's 1,728 replicas.  Each
kernel's custom call carries its ``name``, and the patterns by which the
benchmark's roofline metrics find the main path's two kernels in a
device trace match them.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU compiler library.
"""
import importlib.util
import re
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax._src.lib import xla_client
from jax.sharding import SingleDeviceSharding

from repro.kernels import pad_to_block
from repro.kernels.chain_forces import kernel as CK
from repro.kernels.exchange_matrix import kernel as XK
from repro.kernels.flash_attention import kernel as AK
from repro.kernels.fused_propagate import kernel as FK
from repro.kernels.lj_forces import kernel as LK
from repro.kernels.lj_forces.ref import COULOMB

N_PAPER = 2881
R_MAIN = 64
LANE = 128
ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without the chip
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, shardings, *shapes, name: str):
    """The compiled module's text with each operand's type printed, as a
    device trace names its ops; the kernel's custom call is named
    ``name``."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=shardings)
            for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    text = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    assert "tpu_custom_call" in text
    assert re.search(rf"%{name}(\.\d+)? = .* custom-call\(", text), name
    return text


def _chain_shapes(n_atoms, n_replicas):
    """The kernel's operand shapes for ``chain_molecule(n_atoms)``:
    n - 1 bonds, n - 2 angles, n - 3 torsions + the two feature quads."""
    n_pad = pad_to_block(n_atoms, LANE)
    n_k = -(-(n_atoms - 1) // LANE)
    rb = CK.replica_block(n_replicas)
    f32 = jnp.float32
    return [((n_replicas // rb, CK.row_pad(rb), n_pad), f32),
            ((n_pad, n_k * CK.N_ROLES * LANE), jnp.bfloat16),
            ((8, n_k * LANE), f32), ((8, n_k * LANE), f32),
            ((8, n_k * LANE), f32), ((n_replicas // rb, rb, 8), f32)]


@pytest.mark.parametrize("bias", [False, True])
def test_chain_forces_compiles_v5e(one_chip, bias):
    """The main path's bonded kernel at the paper's 2,881 atoms."""
    _compile(lambda *a: CK.chain_forces_kernel_batched(
        *a, tb=LANE, bias=bias, interpret=False),
        one_chip, *_chain_shapes(N_PAPER, R_MAIN), name="chain_bonded")


def test_chain_forces_compiles_v5e_at_limit(one_chip):
    """The largest system the engine admits on the bonded kernel."""
    _compile(lambda *a: CK.chain_forces_kernel_batched(
        *a, tb=LANE, bias=True, interpret=False),
        one_chip, *_chain_shapes(CK.MAX_ATOMS, R_MAIN), name="chain_bonded")


@pytest.mark.parametrize("n_replicas", [R_MAIN, 8])
@pytest.mark.parametrize("energies", [False, True])
def test_dense_nonbonded_compiles_v5e(one_chip, n_replicas, energies):
    """The main path's nonbonded kernel at the paper's 2,881 atoms, at
    the 64-replica block per chip and at an 8-replica block, in its
    force-only (propagate) and energy-returning builds."""
    n_pad = pad_to_block(N_PAPER, LANE)
    _compile(lambda c, m: LK.nonbonded_kernel_batched(
        c, m, coulomb=COULOMB, energies=energies, interpret=False),
        one_chip, ((n_replicas, 8, n_pad), jnp.float32),
        ((n_pad, n_pad), jnp.float32), name="lj_nonbonded_dense")


def test_dense_nonbonded_compiles_v5e_at_limit(one_chip):
    """The largest system the engine admits on the dense path (the
    bonded kernel's limit): the replica's j planes and the mask slab
    still fit in VMEM."""
    n_pad = pad_to_block(CK.MAX_ATOMS, LANE)
    _compile(lambda c, m: LK.nonbonded_kernel_batched(
        c, m, coulomb=COULOMB, energies=False, interpret=False),
        one_chip, ((8, 8, n_pad), jnp.float32),
        ((n_pad, n_pad), jnp.float32), name="lj_nonbonded_dense")


def test_sparse_nonbonded_compiles_v5e_at_limit(one_chip):
    """At the limit, with the largest neighbor capacity (every atom)."""
    n_pad = pad_to_block(LK.SPARSE_MAX_ATOMS, LANE)
    _compile(lambda c, i, v: LK.nonbonded_sparse_kernel_batched(
        c, i, v, coulomb=COULOMB, cutoff=9.0, interpret=False),
        one_chip, ((8, 8, n_pad), jnp.float32),
        ((8, n_pad, n_pad), jnp.int32), ((8, n_pad, n_pad), jnp.float32),
        name="lj_nonbonded_sparse")


def test_fused_propagate_compiles_v5e_at_limit(one_chip):
    n_pad = pad_to_block(FK.MAX_ATOMS, LANE)
    shapes = _chain_shapes(FK.MAX_ATOMS, 1)[1:5]
    f32 = jnp.float32
    _compile(lambda c, v, z, st, bi, p, b, a, q, m, ms:
             FK.fused_baoab_kernel_batched(
                 c, v, z, st, bi, p, b, a, q, m, ms, tb=LANE, bias=True,
                 coulomb=COULOMB, c1=0.99, half_kick=0.1, half_dt=2.5e-4,
                 interpret=False),
             one_chip, *([((8, 8, n_pad), f32)] * 3),
             ((8, 1, 8), f32), ((8, 1, 8), f32), *shapes,
             ((n_pad, n_pad), f32), ((8, n_pad), f32),
             name="fused_propagate")


def test_exchange_matrix_compiles_v5e(one_chip):
    """The paper's largest ladder, 1,728 replicas, padded to the tile."""
    r = pad_to_block(1728, LANE)
    _compile(lambda f, g: XK.exchange_matrix_kernel(f, g, interpret=False),
             one_chip, ((8, r), jnp.float32), ((8, r), jnp.float32),
             name="exchange_matrix")


@pytest.mark.parametrize("name,fn,shapes", [
    ("lj_forces", lambda c: LK.lj_forces_kernel_batched(
        c, sigma=1.0, eps=1.0, box=12.0, interpret=False),
     [((4, 8, 256), jnp.float32)]),
    ("flash_attention", lambda q, k, v: AK.flash_attention_kernel(
        q, k, v, interpret=False), [((2, 256, 128), jnp.float32)] * 3),
])
def test_other_kernels_named_v5e(one_chip, name, fn, shapes):
    """Kernels the main path does not run carry their names too
    (``lj_energy`` is left out: Mosaic refuses its scalar store)."""
    _compile(fn, one_chip, *shapes, name=name)


def _roofline_patterns(metric: str):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"roofline_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return [re.compile(p) for p in mod.NAMES]


def test_roofline_patterns_find_the_named_kernels(one_chip):
    """Each roofline metric's pattern matches its own kernel's custom
    call in the main path's compiled program, and not the other's."""
    def main_path(coords, mask, c, gmat, bond, ang, quad, bias):
        nb = LK.nonbonded_kernel_batched(coords, mask, coulomb=COULOMB,
                                         energies=False, interpret=False)
        bonded = CK.chain_forces_kernel_batched(
            c, gmat, bond, ang, quad, bias, tb=LANE, bias=True,
            interpret=False)
        return nb, bonded

    n_pad = pad_to_block(N_PAPER, LANE)
    text = _compile(main_path, one_chip,
                    ((R_MAIN, 8, n_pad), jnp.float32),
                    ((n_pad, n_pad), jnp.float32),
                    *_chain_shapes(N_PAPER, R_MAIN), name="chain_bonded")
    ops = [ln.strip() for ln in text.splitlines() if " = " in ln]
    for metric, kernel in (("nb_dense_roofline", "lj_nonbonded_dense"),
                           ("bonded_roofline", "chain_bonded")):
        pats = _roofline_patterns(metric)
        hits = [op for op in ops if any(p.search(op) for p in pats)]
        assert len(hits) == 1, (metric, hits)
        assert hits[0].startswith(f"%{kernel}"), (metric, hits[0][:80])


@pytest.mark.parametrize("force_path,nonbonded,limit", [
    ("pallas", "dense", CK.MAX_ATOMS),
    ("pallas", "sparse", LK.SPARSE_MAX_ATOMS),
    ("fused", "dense", FK.MAX_ATOMS),
])
def test_engine_refuses_systems_beyond_kernel_limits(
        monkeypatch, force_path, nonbonded, limit):
    """On a TPU the engine refuses, at construction, a system its
    compiled kernels cannot hold, naming the limit — it never falls
    back to the jnp passes.  The backend is steered to "TPU, compiled"
    here; the system is a bare atom count, since the check runs before
    any topology is packed."""
    import repro.md.engine as engine_mod
    from repro.md import MDEngine
    monkeypatch.setattr(engine_mod, "default_use_kernel", lambda: True)
    monkeypatch.setattr(engine_mod, "default_interpret", lambda: False)
    with pytest.raises(ValueError, match=f"at most {limit} atoms"):
        MDEngine(system=types.SimpleNamespace(n_atoms=limit + 1),
                 force_path=force_path, nonbonded=nonbonded)
