"""Cells, configurations, traffic mixes, limits and metrics are found by
name from BENCHMARK.json; a new one needs new files and entries only."""
import json
import re

import pytest

from bench import spec
from bench.tests.conftest import ROOT, write_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_loads_by_name():
    bench = spec.benchmark()
    for work in bench["workloads"]:
        cell = spec.cell(work["name"])
        assert cell["config"]["name"] == work["config"]
        assert cell["traffic"]["name"] == work["traffic"]
        assert int(cell["config"]["replica_shards"]) == work["chips"]
        assert {"pos_gap_ulp", "swap_errors", "rung_errors",
                "failed_replicas"} == set(cell["limits"])
        assert callable(spec.kind_module(cell["config"]["kind"]).compare)
        assert [m["name"] for m in cell["end_to_end"]] == [
            "ns_per_day", "hbm_peak_gib", "setup_s"]
        assert cell["per_layer"]


@pytest.mark.parametrize("section", ["end_to_end", "per_layer"])
def test_every_metric_has_a_reader(section):
    for m in spec.benchmark()[section]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_module(m["name"]).read)


def test_names_and_files_keep_to_the_contract():
    bench = spec.benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    assert all(f.startswith("bench/") for f in files)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_collectives_only_where_chips_exchange():
    mesh = [m for m in spec.benchmark()["per_layer"]
            if m["name"] == "collective_exposed_ms_per_cycle"]
    assert mesh[0]["workloads"] == ["tremd256x4.md100"]
    assert spec.metrics_for(mesh, "tremd64.md200") == []


def test_peaks_by_device_kind():
    p = spec.peaks("TPU v5 lite")
    assert p["peak_flops"] == 197e12 and p["peak_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError, match="not in peaks.json"):
        spec.peaks("TPU v9 imaginary")


def test_missing_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_module("no_such_metric")
    with pytest.raises(spec.SpecError, match="deployment kind"):
        spec.kind_module("no_such_kind")


def test_a_new_cell_is_files_and_entries_only(tiny_root):
    """A dummy configuration, traffic mix, cell and per-layer metric,
    added in a copy of the checkout with no code changed."""
    write_json(tiny_root / "bench" / "traffic" / "t9.json",
               {"name": "t9", "md_steps_per_exchange": 9,
                "cycles_per_sync": 2, "failure_rate": 0.0,
                "checkpoint_every": 0})
    write_json(tiny_root / "bench" / "limits" / "tiny.t9.json",
               {"pos_gap_ulp": 1, "swap_errors": 1, "rung_errors": 0,
                "failed_replicas": 0})
    (tiny_root / "bench" / "metrics" / "chunks_read.py").write_text(
        "def read(run):\n    return run.chunks\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.t9", "config": "tiny",
                               "traffic": "t9", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "chunks_read", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "host chunk loop",
                               "moves": "ns_per_day",
                               "workloads": ["tiny.t9"]})
    write_json(tiny_root / "BENCHMARK.json", bench)
    cell = spec.cell("tiny.t9", tiny_root)
    assert cell["traffic"]["md_steps_per_exchange"] == 9
    assert "chunks_read" in [m["name"] for m in cell["per_layer"]]
    assert "chunks_read" not in [
        m["name"] for m in spec.cell("tiny.t6", tiny_root)["per_layer"]]
    assert spec.metric_module("chunks_read", tiny_root).read(
        type("R", (), {"chunks": 3})) == 3
