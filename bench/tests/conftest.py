import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = "tiny.t6"


def write_json(path: Path, obj) -> None:
    import json
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_tiny_root(root: Path, rungs: int = 4, shards: int = 1) -> Path:
    """A checkout with one small cell of the benchmark's own shapes: the
    chain at 24 atoms, ``rungs`` rungs over ``shards`` devices, 6 steps
    per exchange, one cycle a sync.  The metric readers, kinds, limits and
    peaks are the real ones."""
    import json
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf = json.loads((ROOT / "bench" / "configs" /
                       "tremd_chain2881.json").read_text())
    conf.update(name="tiny", replica_shards=shards)
    conf["system"]["n_atoms"] = 24
    conf["ladder"]["dimensions"] = [["temperature", rungs]]
    write_json(root / "bench" / "configs" / "tiny.json", conf)
    write_json(root / "bench" / "traffic" / "t6.json",
               {"name": "t6", "md_steps_per_exchange": 6,
                "cycles_per_sync": 1, "failure_rate": 0.0,
                "checkpoint_every": 0})
    write_json(root / "bench" / "limits" / f"{TINY}.json", json.loads(
        (ROOT / "bench" / "limits" / "tremd64.md200.json").read_text()))
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny", "traffic": "t6",
                           "chips": shards, "why": "test"}]
    write_json(root / "BENCHMARK.json", bench)
    shutil.copytree(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    shutil.copytree(ROOT / "bench" / "kinds", root / "bench" / "kinds")
    shutil.copy(ROOT / "bench" / "peaks.json", root / "bench")
    return root


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
