"""Compiled-op-count regression probes (thunk-creep guard).

PR 1's floor analysis showed that once cycle fusion amortizes dispatch,
CPU/TPU cycle time tracks the number of executable ops in the compiled
module.  PR 3 collapsed the propagate force subgraph into analytic
passes; these tests pin the compiled op count of the fused-force
propagate step so a refactor that silently re-expands the force graph
(autodiff creeping back in, a fusion-breaking layout change) fails CI
instead of shipping a 2x cycle-time regression.

Budgets are pinned ~25-30% above the measured count (pallas propagate
measured ~115 ops, analytic force fn ~62) to absorb XLA version drift
while still catching structural regressions (the autodiff path sits at
~150 propagate ops — outside the budget — and loses the relative
comparison below).
"""
import jax
import jax.numpy as jnp

from repro.config import RepExConfig
from repro.core import build_grid, ctrl_for_assignment
from repro.launch.hlo_analysis import (compiled_op_count, count_ops,
                                       op_budget_check)
from repro.md import MDEngine

PROPAGATE_OP_BUDGET = 150
FORCE_OP_BUDGET = 80
# the all-sparse propagate (neighbor-list nonbonded + slot-table bonded
# + pair planes in the scan carry) measures ~146 ops — the skin-check
# cond and the list carry cost ~18 ops over the dense path's ~128
SPARSE_PROPAGATE_OP_BUDGET = 185
# the fused jnp propagate measures 111 ops (113 with the slot-table
# bonded contraction) on XLA-CPU, JAX 0.9: hoisted BAOAB scales, and
# the noise drawn in the loop body with jax.random — its rolled hash
# loops now sit inside the body instead of before it.  Pinned ~30%
# above measurement and STRICTLY below the all-sparse ~146 pin.
FUSED_PROPAGATE_OP_BUDGET = 145


def _propagate_args(n=8, steps=10):
    grid = build_grid(RepExConfig(dimensions=(("temperature", n),)))
    ctrl = ctrl_for_assignment(grid, jnp.arange(n))
    rngs = jax.random.split(jax.random.key(7), n)
    n_steps = jnp.full(n, steps, jnp.int32)
    return ctrl, rngs, n_steps, steps


def test_fused_force_propagate_op_budget():
    """The pallas-path propagate step stays under the pinned budget."""
    ctrl, rngs, n_steps, steps = _propagate_args()
    eng = MDEngine()                 # force_path="pallas" default
    assert eng.force_path == "pallas"
    state = eng.init_state(jax.random.key(0), 8)
    total, census = compiled_op_count(
        lambda s: eng.propagate(s, ctrl, n_steps, rngs, max_steps=steps),
        state)
    assert total <= PROPAGATE_OP_BUDGET, (
        f"propagate compiled to {total} ops (> {PROPAGATE_OP_BUDGET}): "
        f"{census}")


def test_analytic_force_fn_op_budget():
    """The analytic force evaluation itself stays small."""
    ctrl, _, _, _ = _propagate_args()
    eng = MDEngine()
    state = eng.init_state(jax.random.key(0), 8)
    total, census = compiled_op_count(eng._analytic_force_fn(ctrl),
                                      state["pos"])
    assert total <= FORCE_OP_BUDGET, (
        f"force fn compiled to {total} ops (> {FORCE_OP_BUDGET}): {census}")


def test_sparse_paths_propagate_op_budget():
    """The linear-in-N propagate paths stay thunk-lean: sparse bonded
    contraction alone must fit the DENSE budget (it swaps two GEMMs for
    two gathers — no structural growth), and the all-sparse engine
    (neighbor list + pair planes + slot-table bonded) stays under its
    own pinned budget."""
    ctrl, rngs, n_steps, steps = _propagate_args()

    def count(**kw):
        eng = MDEngine(**kw)
        state = eng.init_state(jax.random.key(0), 8)
        total, census = compiled_op_count(
            lambda s: eng.propagate(s, ctrl, n_steps, rngs,
                                    max_steps=steps), state)
        return total, census

    total, census = count(bonded="sparse")
    assert total <= PROPAGATE_OP_BUDGET, (
        f"bonded-sparse propagate compiled to {total} ops "
        f"(> {PROPAGATE_OP_BUDGET}): {census}")
    total, census = count(bonded="sparse", nonbonded="sparse")
    assert total <= SPARSE_PROPAGATE_OP_BUDGET, (
        f"all-sparse propagate compiled to {total} ops "
        f"(> {SPARSE_PROPAGATE_OP_BUDGET}): {census}")


def test_sparse_bonded_force_fn_op_budget():
    """The analytic force fn with the slot-table bonded contraction
    stays under the same budget as the dense contraction."""
    ctrl, _, _, _ = _propagate_args()
    eng = MDEngine(bonded="sparse")
    state = eng.init_state(jax.random.key(0), 8)
    total, census = compiled_op_count(eng._analytic_force_fn(ctrl),
                                      state["pos"])
    assert total <= FORCE_OP_BUDGET, (
        f"sparse bonded force fn compiled to {total} ops "
        f"(> {FORCE_OP_BUDGET}): {census}")


def test_fused_propagate_op_budget():
    """The fused-path jnp propagate stays under its own (tighter) pin —
    and that pin sits strictly below the all-sparse budget, so the
    fused body can never quietly regress past the per-pass paths."""
    assert FUSED_PROPAGATE_OP_BUDGET < 146 <= SPARSE_PROPAGATE_OP_BUDGET
    ctrl, rngs, n_steps, steps = _propagate_args()

    def check(**kw):
        eng = MDEngine(force_path="fused", **kw)
        state = eng.init_state(jax.random.key(0), 8)
        return op_budget_check(
            lambda s: eng.propagate(s, ctrl, n_steps, rngs,
                                    max_steps=steps), state,
            budget=FUSED_PROPAGATE_OP_BUDGET)

    ok, total, census = check()
    assert ok, (f"fused propagate compiled to {total} ops "
                f"(> {FUSED_PROPAGATE_OP_BUDGET}): {census}")
    # the sparse-bonded variant swaps GEMMs for gathers — no growth room
    ok, total, census = check(bonded="sparse")
    assert ok, (f"fused bonded-sparse propagate compiled to {total} ops "
                f"(> {FUSED_PROPAGATE_OP_BUDGET}): {census}")


def test_fused_path_beats_pallas_op_count():
    """Relative guard, robust to XLA drift: the fused propagate must
    compile to strictly fewer executable ops than the autodiff oracle
    path, dense and all-sparse alike.  (On XLA-CPU it no longer beats
    the per-pass pallas path: 111 vs 106 ops, dense, once the in-body
    noise is drawn with jax.random; the fusion's launch claim is a
    chip claim — one kernel per iteration — not an op count.)"""
    ctrl, rngs, n_steps, steps = _propagate_args()

    def count(fp, **kw):
        eng = MDEngine(force_path=fp, **kw)
        state = eng.init_state(jax.random.key(0), 8)
        total, _ = compiled_op_count(
            lambda s: eng.propagate(s, ctrl, n_steps, rngs,
                                    max_steps=steps), state)
        return total

    autodiff = count("batched")
    assert count("fused") < autodiff
    assert count("fused", bonded="sparse", nonbonded="sparse") < autodiff


def test_analytic_path_beats_autodiff_op_count():
    """Relative guard, robust to XLA drift: the analytic force path must
    compile to fewer executable ops than the autodiff oracle path."""
    ctrl, rngs, n_steps, steps = _propagate_args()

    def count(fp):
        eng = MDEngine(force_path=fp)
        state = eng.init_state(jax.random.key(0), 8)
        total, _ = compiled_op_count(
            lambda s: eng.propagate(s, ctrl, n_steps, rngs,
                                    max_steps=steps), state)
        return total

    assert count("pallas") < count("batched")


def test_count_ops_fusion_and_trip_semantics():
    """count_ops counts a fusion once, skips bookkeeping ops, and does
    NOT weight by while-loop trip counts (static census)."""
    def f(x):
        def body(_, c):
            return jnp.tanh(c) * 2.0 + 1.0
        return jax.lax.fori_loop(0, 100, body, x)

    x = jnp.ones((8, 8))
    text = jax.jit(f).lower(x).compile().as_text()
    census = count_ops(text)
    total = sum(census.values())
    assert census.get("parameter", 0) == 0
    assert census.get("get-tuple-element", 0) == 0
    # a 100-trip loop over a ~3-op body stays a handful of static ops
    assert 1 <= total < 30, census
