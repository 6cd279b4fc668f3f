"""The control at a size a test run holds: the reference in the
program's place, its force field in bfloat16 (the precision step below
the configuration's float32), fails the cell's limits where the
program's own chunk passes them."""
import jax.numpy as jnp
import pytest

from bench import check, spec, workload

kind = spec.kind_module("tremd_chain")
from bench.tests.conftest import TINY


@pytest.fixture(scope="module")
def chunk(tmp_path_factory):
    from bench.tests.conftest import make_tiny_root
    root = make_tiny_root(tmp_path_factory.mktemp("control"))
    cell = spec.cell(TINY, root)
    cell["config"]["system"]["n_atoms"] = 64
    cell["traffic"]["md_steps_per_exchange"] = 20
    prog = workload.build(cell, 2 ** 31 + 3)
    ens = workload.warm_up(prog)
    win = workload.measure(prog, ens, 0.0)
    io = check.chunk_io(win.ens_before_last, win.ens_after,
                        prog.driver.history, prog.chunk_cycles, 0)
    return cell, io


def test_program_passes_and_bf16_control_fails(chunk):
    cell, io = chunk
    conf, traffic, limits = cell["config"], cell["traffic"], cell["limits"]
    sound = kind.compare(conf, traffic, limits, io)
    assert check.passed(sound), sound
    ctrl = kind.control(conf, traffic, io, jnp.bfloat16)
    got = kind.compare(conf, traffic, limits, ctrl)
    assert not check.passed(got)
    assert got["pos_gap_ulp"]["value"] > 2 * limits["pos_gap_ulp"]


def test_float32_reference_in_the_program_place_passes(chunk):
    """The same replay in float32 reads as the reference itself."""
    cell, io = chunk
    conf, traffic, limits = cell["config"], cell["traffic"], cell["limits"]
    same = kind.control(conf, traffic, io, jnp.float32)
    got = kind.compare(conf, traffic, limits, same)
    assert got["pos_gap_ulp"]["value"] == 0.0
    assert got["swap_errors"]["value"] == 0.0
    assert got["rung_errors"]["value"] == 0.0
