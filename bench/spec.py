"""Find everything a benchmark cell needs by name.

``BENCHMARK.json`` at the checkout root names the cells (workloads),
their configuration and traffic mix, and the metrics.  Each of those
lives in a file of its own, found from its name alone:

  configuration   bench/configs/<config>.json   (its ``file`` entry)
  kind            bench/kinds/<kind>.py         (the configuration's
                  ``kind``: how the program is built for it, and the
                  reference comparison that decides ``correct``)
  traffic mix     bench/traffic/<traffic>.json
  limits          bench/limits/<workload>.json  (what ``correct`` allows)
  metric          bench/metrics/<metric>.py     (``read(run) -> float|None``)
  peaks           bench/peaks.json, keyed by JAX's ``device_kind``

so a later cell, configuration, kind, mix or metric is added with new
files and entries only.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]


class SpecError(ValueError):
    """A name or file the benchmark needs is missing or malformed."""


def load_json(path: Path) -> dict:
    try:
        return json.loads(Path(path).read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def benchmark(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def _by_name(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}")


def cell(name: str, root: Path = ROOT) -> dict:
    """Everything one workload needs: its entry, configuration, traffic,
    limits and the metrics it reports, as one dict."""
    root = Path(root)
    bench = benchmark(root)
    work = _by_name(bench["workloads"], name, "workload")
    conf_entry = _by_name(bench["configs"], work["config"], "config")
    config = load_json(root / conf_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{work['traffic']}.json")
    limits = load_json(root / "bench" / "limits" / f"{name}.json")
    return {
        "workload": work,
        "config": config,
        "traffic": traffic,
        "limits": limits,
        "run_seconds": bench["run_seconds"],
        "end_to_end": metrics_for(bench["end_to_end"], name),
        "per_layer": metrics_for(bench["per_layer"], name),
    }


def metrics_for(entries, workload: str) -> list:
    """The metric entries a workload reports: those without a
    ``workloads`` key, and those that list it."""
    return [m for m in entries
            if "workloads" not in m or workload in m["workloads"]]


def _module(path: Path, modname: str, what: str, needs) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for fn in needs:
        if not callable(getattr(mod, fn, None)):
            raise SpecError(f"{what} file {path} defines no {fn}()")
    return mod


def _ident(name: str) -> str:
    return name.replace(".", "_").replace("-", "_")


def metric_module(name: str, root: Path = ROOT) -> ModuleType:
    """Import ``bench/metrics/<name>.py``; it defines ``read(run)``."""
    return _module(Path(root) / "bench" / "metrics" / f"{name}.py",
                   f"bench_metric_{_ident(name)}", f"reader for metric "
                   f"{name!r}", ("read",))


def kind_module(name: str, root: Path = ROOT) -> ModuleType:
    """Import ``bench/kinds/<name>.py``; it defines ``driver``,
    ``compare`` and ``control`` (see ``bench/kinds/tremd_chain.py``)."""
    return _module(Path(root) / "bench" / "kinds" / f"{name}.py",
                   f"bench_kind_{_ident(name)}", f"deployment kind {name!r}",
                   ("driver", "compare", "control"))


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    """Peak FLOP/s and bytes/s of one chip; an unknown kind is an error."""
    table = load_json(Path(root) / "bench" / "peaks.json")
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json "
                        f"({sorted(table['devices'])})") from None
