"""The trace reduction on a small recorded trace: 125 ms of a traced
tremd64.md200 window on one v5e chip, around the boundary between two
chunks (bench/tests/data/trace_tremd64_boundary.json, cut from the
reduced form ``trace.load`` gives)."""
import importlib.util
import json

import numpy as np
import pytest

from bench import trace as tr
from bench.tests.conftest import ROOT

DATA = ROOT / "bench" / "tests" / "data" / "trace_tremd64_boundary.json"
CHUNK_END, NEXT_CHUNK = 5919014943.0, 5924168752.0   # jit_chunk modules


@pytest.fixture(scope="module")
def trace():
    return tr.Trace.from_json(json.loads(DATA.read_text()))


def metric(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Run:
    """The fields of ``harness.Run`` the trace metrics read."""
    def __init__(self, trace, chunks=1, cycles=1):
        self.trace, self.chunks, self.cycles = trace, chunks, cycles
        self.n_replicas, self.n_devices = 64, 1
        self.config = json.loads((ROOT / "bench" / "configs" /
                                  "tremd_chain2881.json").read_text())
        self.peaks = {"peak_flops": 197e12, "peak_bytes_per_s": 819e9}


def test_busy_union_against_sampling(trace):
    busy = tr.busy(trace, 0)
    assert all(b > a for a, b in busy)
    assert all(busy[i][1] < busy[i + 1][0] for i in range(len(busy) - 1))
    ts = np.linspace(*trace.window, 200001)[:-1]
    covered = np.zeros(ts.shape, bool)
    for _, a, b in tr.leaves(trace.ops[0]):
        covered |= (ts >= a) & (ts < b)
    assert tr.total(busy) / trace.window_ns == pytest.approx(
        covered.mean(), abs=2e-4)


def test_idle_share_is_the_uncovered_window(trace):
    idle = tr.gaps(tr.busy(trace, 0), *trace.window)
    share = metric("idle_share").read(Run(trace))
    assert share == pytest.approx(100 * tr.total(idle) / trace.window_ns)
    # the chunk boundary holds nearly all of it: ~5.2 ms of 125 ms
    assert 3.5 < share < 4.5


def test_kernel_matching_by_hand_count(trace):
    nb = metric("nb_dense_roofline").NAMES
    bonded = metric("bonded_roofline").NAMES
    assert len(tr.matching(trace.ops[0], nb)) == 4
    assert len(tr.matching(trace.ops[0], bonded)) == 3
    assert not set(tr.matching(trace.ops[0], nb)) & set(
        tr.matching(trace.ops[0], bonded))


def test_roofline_share_from_work_and_kernel_time(trace):
    m = metric("nb_dense_roofline")
    run = Run(trace)
    hits = tr.matching(trace.ops[0], m.NAMES)
    busy = sum(min(b, trace.window[1]) - max(a, trace.window[0])
               for _, a, b in hits)
    flops, _ = m.work(run.config["system"], 64)
    assert m.read(run) == pytest.approx(
        100 * 4 * flops / 197e12 / (busy / 1e9))
    assert 0 < m.read(run) < 100
    assert 0 < metric("bonded_roofline").read(run) < 100
    assert m.read(Run(None)) is None


def test_gap_attribution_at_the_chunk_boundary(trace):
    gaps = tr.breakdown(trace)["idle_gaps"]
    (n1, s1), (n2, s2) = gaps[:2]
    assert n1 == n2 == "REMDDriver.run_fused (device 0)"
    assert s1 == pytest.approx(3.056076e-3) and s2 == pytest.approx(
        2.102906e-3)
    for a, b in tr.gaps(tr.busy(trace, 0), *trace.window):
        if b - a > 1e6:
            assert CHUNK_END - 1e5 <= a and b <= NEXT_CHUNK + 1e5
    assert tr.span_at(trace, trace.window[0] - 1) == "outside"


def test_host_gap_is_idle_outside_the_chunk_program(trace):
    gap_ms = metric("host_gap_ms_per_chunk").read(Run(trace, chunks=1))
    assert gap_ms == pytest.approx((3.056076 + 2.102906), abs=0.01)


def test_self_times_add_up_to_busy(trace):
    clipped = [(n, max(a, trace.window[0]), min(b, trace.window[1]))
               for n, a, b in trace.ops[0]]
    selfs = tr.self_times(clipped)
    assert all(t >= 0 for _, t in selfs)
    every_op = tr.union((a, b) for _, a, b in clipped if b > a)
    assert sum(t for _, t in selfs) == pytest.approx(
        tr.total(every_op), rel=1e-6)
    # the loop ops' own time (their body's ops less) is not busy time
    assert tr.total(tr.busy(trace, 0)) <= tr.total(every_op)
    top = tr.breakdown(trace)["device_ops"][0][0]
    assert top.startswith("closed_call.52 custom-call")


def test_no_collectives_on_one_chip(trace):
    assert metric("collective_exposed_ms_per_cycle").read(Run(trace)) is None


def test_collective_exposure_by_hand():
    t = tr.Trace(window=(0.0, 100.0), ops={
        0: [("%collective-permute.1 = f32[8]", 10.0, 20.0),
            ("%fusion.1 = f32[8]", 15.0, 30.0),
            ("%collective-permute.2 = f32[8]", 40.0, 46.0)],
        1: [("%collective-permute.1 = f32[8]", 10.0, 12.0)]})
    # device 0: 5 + 6 exposed over 2 cycles -> 5.5 ns a cycle
    got = metric("collective_exposed_ms_per_cycle").read(Run(t, cycles=2))
    assert got == pytest.approx(5.5 / 1e6)


def test_loop_ops_hold_their_body_and_no_gap():
    """A ``while`` op spans its body; the gaps between the body's ops
    are idle, and a collective inside it overlaps no compute."""
    t = tr.Trace(window=(0.0, 100.0), ops={0: [
        ("%while.1 = (s32[]) while(...)", 10.0, 90.0),
        ("%fusion.1 = f32[8]", 10.0, 30.0),
        ("%collective-permute.1 = f32[8]", 40.0, 50.0),
        ("%fusion.2 = f32[8]", 60.0, 90.0),
        ("%copy.1 = f32[8]", 95.0, 95.0)]})
    assert [e[0].split()[0] for e in tr.leaves(t.ops[0])] == [
        "%fusion.1", "%collective-permute.1", "%fusion.2"]
    assert tr.busy(t, 0) == [(10.0, 30.0), (40.0, 50.0), (60.0, 90.0)]
    assert metric("idle_share").read(Run(t)) == pytest.approx(40.0)
    got = metric("collective_exposed_ms_per_cycle").read(Run(t, cycles=1))
    assert got == pytest.approx(10.0 / 1e6)
    assert tr.breakdown(t)["idle_gaps"][0][1] == pytest.approx(10e-9)


def test_intervals_by_hand():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(0, 3), (5, 8)], -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tr.subtract([(0, 10)], [(2, 4), (3, 5)]) == 7
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
