"""Whole runs of a small cell on the CPU, with the chip look off: the
last line's schema, and a run whose timed path is broken underneath
coming out not correct."""
import io
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.conftest import ROOT, TINY

SEED = 2 ** 31 + 4099          # above 32 signed bits


def run_tiny(root, traced=False, fault=None, workload=TINY, seconds=0.3):
    buf = io.StringIO()
    rc = harness.run(workload, SEED, seconds, traced, time.perf_counter(),
                     on_chip=False, fault=fault, out=buf, root=root)
    assert rc == 0
    lines = buf.getvalue().strip().splitlines()
    assert len(lines) == 1
    return json.loads(lines[-1])


def test_result_line_schema(tiny_root):
    res = run_tiny(tiny_root)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["attempted"] % 4 == 0
    assert set(res["metrics"]) == {"ns_per_day", "setup_s"}   # no HBM on CPU
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": None}
    for c in res["checks"].values():
        assert set(c) == {"value", "limit"}


def test_traced_result_line(tiny_root):
    res = run_tiny(tiny_root, traced=True)
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    # the CPU has no device plane: nothing to read, nothing reported
    assert "nb_dense_roofline" not in res["metrics"]


def test_a_new_cell_runs_with_two_cycles_a_chunk(tiny_root):
    """Files and entries only; the check follows the program's rungs
    through both cycles of the chunk."""
    from bench.tests.test_spec import test_a_new_cell_is_files_and_entries_only
    test_a_new_cell_is_files_and_entries_only(tiny_root)
    (tiny_root / "bench" / "limits" / "tiny.t9.json").write_text(
        (ROOT / "bench" / "limits" / "tremd64.md200.json").read_text())
    res = run_tiny(tiny_root, traced=True, workload="tiny.t9")
    assert res["correct"] is True
    assert res["attempted"] % 8 == 0


DUMMY_KIND = """
import importlib.util
from pathlib import Path

_path = Path(__file__).with_name("tremd_chain.py")
_spec = importlib.util.spec_from_file_location("dummy_base", _path)
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)
driver, control = base.driver, base.control


def compare(config, traffic, limits, io):
    return {"replicas_seen": {"value": float(io.pos_out.shape[0]),
                              "limit": float(limits["replicas_seen"])}}
"""


def test_a_new_kind_is_a_file(tiny_root):
    """A deployment kind of its own, added as a file: the harness builds
    the program and decides ``correct`` through it."""
    (tiny_root / "bench" / "kinds" / "dummy.py").write_text(DUMMY_KIND)
    conf = tiny_root / "bench" / "configs" / "tiny.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                    kind="dummy")))
    (tiny_root / "bench" / "limits" / f"{TINY}.json").write_text(
        json.dumps({"replicas_seen": 4}))
    res = run_tiny(tiny_root)
    assert res["checks"] == {"replicas_seen": {"value": 4.0, "limit": 4.0}}
    assert res["correct"] is True


def frozen(entry, driver):
    """A step that returns its state unchanged."""
    driver.engine.propagate = lambda state, *a, **k: state
    return entry


def half_batch(entry, driver):
    """Half of the replicas never advance."""
    propagate = driver.engine.propagate

    def half(state, *a, **k):
        out = propagate(state, *a, **k)
        h = state["pos"].shape[0] // 2
        return {key: out[key].at[h:].set(state[key][h:]) for key in out}
    driver.engine.propagate = half
    return entry


def flipped_decisions(entry, driver):
    """Every exchange decision inverted where it is made."""
    import repro.core.exchange as X
    metropolis = X.metropolis
    X.metropolis = lambda delta, rng: ~metropolis(delta, rng)
    return entry


def relabelled(entry, driver):
    """Rungs handed to the next chunk altered between chunks."""
    def step(ens):
        out = entry(ens)
        return out._replace(assignment=out.assignment[::-1])
    return step


@pytest.mark.parametrize("fault", [frozen, half_batch, flipped_decisions,
                                   relabelled])
def test_a_broken_timed_path_is_not_correct(tiny_root, fault):
    import repro.core.exchange as X
    metropolis = X.metropolis
    try:
        res = run_tiny(tiny_root, fault=fault)
    finally:
        X.metropolis = metropolis
    assert res["correct"] is False
    bad = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert bad


def test_no_tpu_exits_nonzero_and_prints_nothing():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "tremd64.md200", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and bench/: no program."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tremd64.md200",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
