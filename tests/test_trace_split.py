"""``benchmarks/trace_split.py``: the Eq. (1) split of a traced window by
the program's scopes and the driver's host spans.

  * ``split`` on a hand-made window gives hand-computed T_MD, T_EX,
    T_data, T_over, scope coverage, boundary idle and gap names;
  * the tool on the benchmark's own cell cut to 24 atoms (CPU): the join
    table of the compiled chunk names every cycle-body scope, and a
    traced window gives one ``repex.chunk`` per chunk with its args and
    positive T_data and T_over.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from benchmarks import trace_split as ts

ROOT = Path(__file__).resolve().parents[1]

OP_NAMES = {
    "p.1": "jit(chunk)/while/body/propagate/mul",
    "p.2": "jit(chunk)/while/body/propagate/add",
    "f.1": "jit(chunk)/while/body/exchange/features/dot",
    "e.1": "jit(chunk)/while/body/exchange/lt",
    "n.1": "jit(chunk)/while/body/add",
    "d.1": "jit(chunk)/while/body/detect_recover/select",
}
HOST = [
    ("bench:window", 0, 1000, {}),
    ("bench:run", 0, 1000, {}),
    ("repex.start", 0, 40, {}),
    ("repex.chunk", 40, 900, {"chunk": 0, "cycles": 2}),
    ("repex.dispatch", 40, 60, {"first_call": False}),
    ("repex.wait", 60, 800, {}),
    ("repex.fetch", 800, 860, {}),
    ("repex.bookkeep", 860, 880, {}),
    ("repex.report", 900, 950, {}),
]
OPS = {0: [
    ("%s.1 = f32[] add(...)", 10, 20),
    ("%w.1 = (f32[]) while(...)", 45, 795),     # holds the body's ops
    ("%p.1 = f32[] fusion(...)", 50, 450),
    ("%p.2 = f32[] fusion(...)", 400, 600),
    ("%f.1 = f32[] fusion(...)", 600, 650),
    ("%e.1 = f32[] fusion(...)", 650, 660),
    ("%n.1 = f32[] add(...)", 660, 670),
    ("%d.1 = f32[] select(...)", 670, 690),
]}
MODULES = {0: [("jit_chunk", 50, 790), ("jit_small", 10, 20)]}


def test_scope_is_the_innermost_known_component():
    assert ts.scope_of("jit(c)/while/body/exchange/features/dot") == "features"
    assert ts.scope_of("jit(c)/while/body/propagate/closed_call") == \
        "propagate"
    assert ts.scope_of("jit(c)/while/body/add") == "none"
    assert ts.scope_of("") == "none"


def test_op_names_join_instruction_to_metadata():
    text = ('  %fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(c)/while/body/propagate/mul" '
            'source_file="x.py"}\n'
            '  ROOT %tuple.1 = (f32[4]{0}) tuple(%fusion.3)\n'
            '  ROOT %add.2 = f32[] add(%a, %b), '
            'metadata={op_name="jit(c)/exchange/add"}\n')
    assert ts.op_names(text) == {
        "fusion.3": "jit(c)/while/body/propagate/mul",
        "add.2": "jit(c)/exchange/add"}


def test_split_of_a_hand_made_window():
    r = ts.split(OPS, MODULES, HOST, OP_NAMES, chunks=1, cycles=2)
    ns = 1e-6                                   # ms per ns
    # leaf busy: [10, 20] + [50, 690]; propagate [50, 600]
    assert r["t_md_ms_per_cycle"] == pytest.approx(550 * ns / 2)
    assert r["t_ex_ms_per_cycle"] == pytest.approx((50 + 10) * ns / 2)
    assert r["busy_by_scope_s"] == pytest.approx({
        "propagate": 550e-9, "features": 50e-9, "exchange": 10e-9,
        "detect_recover": 20e-9, "none": 20e-9})
    assert r["scope_cover"] == pytest.approx([630 / 650])
    assert r["t_data_ms_per_chunk"] == pytest.approx(60 * ns)
    assert r["t_over_ms_per_chunk"] == pytest.approx((40 + 20 + 20 + 50)
                                                     * ns)
    # idle [0, 10], [20, 50], [690, 1000]; outside the chunk module's
    # run [50, 790]: 10 + 30 + 210; no repex span covers [950, 1000]
    assert r["host_gap_ms_per_chunk"] == pytest.approx(250 * ns)
    assert r["host_gap_in_repex_span_share"] == pytest.approx(1 - 50 / 250)
    assert [n for n, _ in r["idle_gaps"]] == [
        "run/repex.fetch (device 0)", "run/repex.start (device 0)",
        "run/repex.start (device 0)"]
    assert r["idle_by_name_ms_per_chunk"] == pytest.approx({
        "run/repex.fetch": 310 * ns, "run/repex.start": 40 * ns})
    assert r["chunk_args"] == [{"chunk": 0, "cycles": 2}]
    assert r["dispatch_args"] == [{"first_call": False}]


def test_tiny_cell_names_every_scope_and_span(tmp_path):
    """The whole tool on the 24-atom cell, in a process of its own: a
    compilation cache another test turned on would hand the join an
    executable without op metadata."""
    from bench.tests.conftest import TINY, make_tiny_root

    root = make_tiny_root(tmp_path / "root")
    out = tmp_path / "split.json"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    subprocess.run([sys.executable, str(ROOT / "benchmarks" /
                                        "trace_split.py"),
                    TINY, "2147483999", "1", "2", str(out), "--root",
                    str(root)], check=True, env=env, cwd=ROOT, timeout=600)
    r = json.loads(out.read_text())
    assert set(r["joined_instructions"]) >= {
        "propagate", "features", "exchange", "detect_recover"}
    first = r["chunk_args"][0]["chunk"]
    assert r["chunk_args"] == [{"chunk": first + i, "cycles": 1}
                               for i in range(2)]
    assert len(r["traced_chunk_s"]) == 2
    assert r["t_data_ms_per_chunk"] > 0 and r["t_over_ms_per_chunk"] > 0
