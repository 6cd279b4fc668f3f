"""Observer-effect invariance suite for the telemetry subsystem.

The contract (docs/OBSERVABILITY.md):

  * telemetry ON leaves the discrete trajectory — per-cycle assignment
    trace, acceptance counters, failure totals — BITWISE unchanged,
    across patterns x schemes x force paths x chunk sizes, on all three
    driver paths (run / run_fused / run_sharded);
  * telemetry OFF (``telemetry=None`` or ``Telemetry(enabled=False)``)
    compiles the IDENTICAL program — same HLO text, same op census, op
    budgets of tests/test_op_budget.py intact;
  * the RunReport's counters agree with the driver's own bookkeeping
    (they are observations of it, not a second derivation).

Multi-device cases need forced host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, the sharded CI
job); they skip cleanly otherwise.
"""
import json
import re

import jax
import numpy as np
import pytest

from repro.config import RepExConfig
from repro.core import REMDDriver
from repro.launch.hlo_analysis import count_ops
from repro.launch.mesh import make_replica_mesh
from repro.md import HarmonicEngine, MDEngine
from repro.obs import RunReport, Telemetry, validate_report

N_DEVICES = jax.device_count()

multidevice = pytest.mark.skipif(
    N_DEVICES < 8,
    reason="needs 8 devices — export "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8 before "
           "jax initializes")


def _cfg(pattern="synchronous", scheme="neighbor", n_replicas=6,
         n_cycles=8, md_steps=2):
    return RepExConfig(dimensions=(("temperature", n_replicas),),
                       md_steps_per_cycle=md_steps, n_cycles=n_cycles,
                       pattern=pattern, exchange_scheme=scheme)


def _trajectory(d):
    """The discrete trajectory a run left in the driver's bookkeeping."""
    return (np.stack([h["assignment"] for h in d.history]),
            [(h["accept"], h["attempt"], h["failed"]) for h in d.history],
            d.acceptance)


def _assert_same_trajectory(d_on, d_off):
    a_on, counters_on, acc_on = _trajectory(d_on)
    a_off, counters_off, acc_off = _trajectory(d_off)
    np.testing.assert_array_equal(a_on, a_off)
    assert counters_on == counters_off
    assert acc_on == acc_off


# ---------------------------------------------------------------------------
# Invariance: telemetry on == telemetry off, bitwise
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", ["neighbor", "matrix"])
@pytest.mark.parametrize("pattern", ["synchronous", "asynchronous"])
def test_fused_invariance(pattern, scheme):
    cfg = _cfg(pattern=pattern, scheme=scheme)
    d_on = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(HarmonicEngine(), cfg)
    d_on.run_fused(d_on.init(), chunk_cycles=4)
    d_off.run_fused(d_off.init(), chunk_cycles=4)
    _assert_same_trajectory(d_on, d_off)
    validate_report(d_on.last_report.to_dict())
    validate_report(d_off.last_report.to_dict())


def test_fused_invariance_across_chunk_sizes():
    """Telemetry on at K=2 == telemetry off at K=5 (partial final chunk):
    neither the observation nor the chunking may move the trajectory."""
    cfg = _cfg(n_cycles=7)
    d_on = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(HarmonicEngine(), cfg)
    d_on.run_fused(d_on.init(), chunk_cycles=2)
    d_off.run_fused(d_off.init(), chunk_cycles=5)
    _assert_same_trajectory(d_on, d_off)


@pytest.mark.parametrize("force_path", ["pallas", "batched"])
def test_fused_invariance_force_paths(force_path):
    cfg = _cfg(n_replicas=4, n_cycles=4)
    eng = lambda: MDEngine(force_path=force_path)  # noqa: E731
    d_on = REMDDriver(eng(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(eng(), cfg)
    d_on.run_fused(d_on.init(), chunk_cycles=2)
    d_off.run_fused(d_off.init(), chunk_cycles=2)
    _assert_same_trajectory(d_on, d_off)


def test_fused_invariance_under_failures():
    cfg = _cfg(n_replicas=4, n_cycles=6)
    d_on = REMDDriver(MDEngine(), cfg, failure_rate=0.4,
                      telemetry=Telemetry())
    d_off = REMDDriver(MDEngine(), cfg, failure_rate=0.4)
    d_on.run_fused(d_on.init(), chunk_cycles=3)
    d_off.run_fused(d_off.init(), chunk_cycles=3)
    _assert_same_trajectory(d_on, d_off)
    assert d_on.last_report.failures["total"] > 0
    assert (d_on.last_report.failures["total"]
            == d_off.last_report.failures["total"])


@pytest.mark.parametrize("scheme", ["neighbor", "matrix"])
def test_run_invariance(scheme):
    """The legacy per-cycle path honors the same contract."""
    cfg = _cfg(scheme=scheme, n_cycles=5)
    d_on = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(HarmonicEngine(), cfg)
    d_on.run(d_on.init())
    d_off.run(d_off.init())
    _assert_same_trajectory(d_on, d_off)
    validate_report(d_on.last_report.to_dict())


def test_sharded_invariance_one_shard():
    cfg = _cfg()
    d_on = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(HarmonicEngine(), cfg)
    d_on.run_sharded(d_on.init(), mesh=make_replica_mesh(1), chunk_cycles=4)
    d_off.run_sharded(d_off.init(), mesh=make_replica_mesh(1),
                      chunk_cycles=4)
    _assert_same_trajectory(d_on, d_off)
    validate_report(d_on.last_report.to_dict())


@multidevice
@pytest.mark.parametrize("scheme", ["neighbor", "matrix"])
def test_sharded_invariance_8shards(scheme):
    cfg = _cfg(scheme=scheme, n_replicas=8)
    d_on = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d_off = REMDDriver(HarmonicEngine(), cfg)
    d_on.run_sharded(d_on.init(), mesh=make_replica_mesh(8), chunk_cycles=4)
    d_off.run_sharded(d_off.init(), mesh=make_replica_mesh(8),
                      chunk_cycles=4)
    _assert_same_trajectory(d_on, d_off)


# ---------------------------------------------------------------------------
# Telemetry off is a true no-op: identical HLO, op budgets intact
# ---------------------------------------------------------------------------


_SOURCE_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                  "StackFrames")


def _program_text(text):
    """Compiled HLO text without its source locations: the per-op
    ``metadata={...}`` and the module's file/stack-frame tables name
    the Python lines that traced each op, which differ between two
    drivers even when the programs are identical."""
    keep, in_tables = [], False
    for line in text.splitlines():
        if line in _SOURCE_TABLES:
            in_tables = True
            continue
        if in_tables and line.startswith(("%", "ENTRY")):
            in_tables = False
        if not in_tables:
            keep.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(keep)


def _fused_chunk_text(driver, k=4):
    ens = driver.init()
    fn = driver._fused_chunk_fn(k)
    return _program_text(
        fn.lower(ens, ens.state, jax.random.key(0)).compile().as_text())


def test_telemetry_off_compiles_identical_hlo():
    """telemetry=None, Telemetry(enabled=False) and a driver built with
    no telemetry argument all compile byte-identical fused chunks."""
    eng = HarmonicEngine()
    cfg = _cfg()
    t_none = _fused_chunk_text(REMDDriver(eng, cfg))
    t_off = _fused_chunk_text(
        REMDDriver(eng, cfg, telemetry=Telemetry(enabled=False)))
    assert t_none == t_off
    assert count_ops(t_none) == count_ops(t_off)
    # and telemetry ON compiles a program that differs ONLY by carrying
    # the counter rows out of the scan — op classes, not math: the
    # invariance tests above pin that the trajectory cannot tell
    t_on = _fused_chunk_text(
        REMDDriver(eng, cfg, telemetry=Telemetry()))
    assert t_on != t_none


def test_telemetry_off_legacy_cycle_identical_hlo():
    eng = HarmonicEngine()
    cfg = _cfg()

    def cycle_text(driver):
        ens = driver.init()
        return _program_text(
            driver._cycle_fn(0, 0).lower(ens).compile().as_text())

    assert cycle_text(REMDDriver(eng, cfg)) == cycle_text(
        REMDDriver(eng, cfg, telemetry=Telemetry(enabled=False)))


def test_telemetry_off_op_budgets_hold():
    """The PR-3 op budgets survive the telemetry refactor: the pallas
    propagate step and the analytic force fn still compile under the
    pinned ceilings (the exchange-layer rows must be DCE'd, not lurking
    in the propagate subgraph)."""
    import jax.numpy as jnp

    from repro.core import build_grid, ctrl_for_assignment
    from repro.launch.hlo_analysis import compiled_op_count
    from tests.test_op_budget import FORCE_OP_BUDGET, PROPAGATE_OP_BUDGET

    grid = build_grid(RepExConfig(dimensions=(("temperature", 8),)))
    ctrl = ctrl_for_assignment(grid, jnp.arange(8))
    rngs = jax.random.split(jax.random.key(7), 8)
    n_steps = jnp.full(8, 10, jnp.int32)
    eng = MDEngine()
    state = eng.init_state(jax.random.key(0), 8)
    total, census = compiled_op_count(
        lambda s: eng.propagate(s, ctrl, n_steps, rngs, max_steps=10),
        state)
    assert total <= PROPAGATE_OP_BUDGET, census
    total_f, census_f = compiled_op_count(eng._analytic_force_fn(ctrl),
                                          state["pos"])
    assert total_f <= FORCE_OP_BUDGET, census_f


# ---------------------------------------------------------------------------
# Report contents agree with the driver's own bookkeeping
# ---------------------------------------------------------------------------


def test_report_counters_match_driver_bookkeeping():
    cfg = _cfg(n_cycles=12)
    tel = Telemetry()
    d = REMDDriver(HarmonicEngine(), cfg, telemetry=tel)
    d.run_fused(d.init(), chunk_cycles=4)
    r = d.last_report
    assert isinstance(r, RunReport)
    ex = r.exchange
    # pair counters sum to the driver's global counters
    assert np.asarray(ex["pair_accept"]).sum() == pytest.approx(
        ex["accepted"])
    assert np.asarray(ex["pair_attempt"]).sum() == pytest.approx(
        ex["attempted"])
    np.testing.assert_array_less(
        np.asarray(ex["pair_accept"]) - 1e-9, np.asarray(ex["pair_attempt"]))
    # every replica is on exactly one rung per cycle
    occ = np.asarray(ex["occupancy"])
    np.testing.assert_array_equal(occ.sum(axis=1),
                                  np.full(cfg.n_replicas, 12))
    # host-clock cycle and fetch times, per cycle
    assert r.phases["t_cycle_mean"] > 0.0
    assert 0.0 <= r.phases["t_data_mean"] < r.phases["t_cycle_mean"]
    # json round trip + schema
    validate_report(json.loads(r.to_json()))


def test_report_matrix_scheme_has_no_pair_rows():
    """The Gibbs scheme re-draws pairings per sweep — no static pair-slot
    axis exists, so the report must say so (null), not fake one."""
    cfg = _cfg(scheme="matrix")
    d = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d.run_fused(d.init(), chunk_cycles=4)
    ex = d.last_report.exchange
    assert ex["pair_attempt"] is None and ex["pair_accept"] is None
    # occupancy/round-trips come from the assignment trace — still there
    assert ex["occupancy"] is not None
    validate_report(d.last_report.to_dict())


def test_telemetry_reset_scopes_counters():
    """reset() after warm-up: counters cover only production cycles."""
    cfg = _cfg(n_cycles=12)
    tel = Telemetry()
    d = REMDDriver(HarmonicEngine(), cfg, telemetry=tel)
    ens = d.init()
    ens = d.run_fused(ens, n_cycles=4, chunk_cycles=4)
    tel.reset()
    d.run_fused(ens, n_cycles=8, chunk_cycles=4)
    r = d.last_report
    assert r.cycles["counted"] == 8
    assert r.cycles["total"] == 12
    occ = np.asarray(r.exchange["occupancy"])
    np.testing.assert_array_equal(occ.sum(axis=1),
                                  np.full(cfg.n_replicas, 8))


def test_report_without_telemetry_still_emitted():
    """telemetry=None drivers still emit a (counter-less) RunReport —
    consumers can rely on last_report existing on every path."""
    cfg = _cfg(n_cycles=4)
    d = REMDDriver(HarmonicEngine(), cfg)
    d.run_fused(d.init(), chunk_cycles=2)
    r = d.last_report
    assert r.cycles == {"total": 4, "counted": 0}
    assert r.exchange["pair_attempt"] is None
    assert set(r.phases) == {"t_cycle_mean", "t_data_mean", "t_prep_mean"}
    validate_report(r.to_dict())


# ---------------------------------------------------------------------------
# Wire ledger (run_sharded)
# ---------------------------------------------------------------------------


@multidevice
def test_wire_ledger_scales_with_invocations():
    cfg = _cfg(n_replicas=8, n_cycles=8)
    tel = Telemetry()
    d = REMDDriver(HarmonicEngine(), cfg, telemetry=tel)
    d.run_sharded(d.init(), mesh=make_replica_mesh(8), chunk_cycles=4)
    wire = d.last_report.wire
    assert wire["invocations"]["4"] == 2
    per_chunk = wire["per_chunk"]["4"]
    # the halo protocol's signature: collective-permutes present
    assert "collective-permute" in per_chunk
    for op, tot in wire["totals"].items():
        assert tot["bytes"] == per_chunk[op]["bytes"] * 2
        assert tot["count"] == per_chunk[op]["count"] * 2


def test_wire_ledger_absent_on_fused_path():
    cfg = _cfg(n_cycles=4)
    d = REMDDriver(HarmonicEngine(), cfg, telemetry=Telemetry())
    d.run_fused(d.init(), chunk_cycles=2)
    assert d.last_report.wire == {}


# ---------------------------------------------------------------------------
# CLI --report-out
# ---------------------------------------------------------------------------


def test_cli_report_out(tmp_path, monkeypatch):
    from repro.launch import repex_run
    out = tmp_path / "report.json"
    monkeypatch.setattr("sys.argv", [
        "repex_run", "--engine", "md", "--dims", "temperature:4",
        "--cycles", "4", "--md-steps", "2", "--chunk", "2",
        "--atoms", "8", "--report-out", str(out)])
    repex_run.main()
    with open(out) as f:
        report = json.load(f)
    validate_report(report)
    assert report["path"] == "fused"
    assert report["cycles"]["counted"] == 4
