"""What a profiler trace of a run can name: the cycle body's scopes and
the driver's host spans (docs/OBSERVABILITY.md).

  * the fused chunk (one device), the sharded chunk (four virtual
    devices, a process of its own since the device count is fixed when
    JAX starts) and the legacy per-cycle step carry the scopes
    ``propagate``, ``features``, ``exchange`` and ``detect_recover`` in
    their op metadata, and ``inject`` only where failures are injected;
  * ``run_fused`` under ``jax.profiler.trace`` records one
    ``repex.chunk`` span per chunk, with its ``chunk`` and ``cycles``
    args and its dispatch, wait, fetch, bookkeep and checkpoint spans
    nested inside, between one ``repex.start`` and one ``repex.report``.
"""
import glob
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.config import RepExConfig
from repro.core import REMDDriver
from repro.md import MDEngine
from repro.md.system import chain_molecule

ROOT = Path(__file__).resolve().parents[1]
SCOPES = ("propagate", "features", "exchange", "detect_recover")
CHUNK_CHILDREN = ("dispatch", "wait", "fetch", "bookkeep", "ckpt")


def _cfg(scheme="neighbor", n_replicas=4, **kw):
    return RepExConfig(dimensions=(("temperature", n_replicas),),
                       md_steps_per_cycle=2, n_cycles=6,
                       exchange_scheme=scheme, **kw)


def _engine():
    return MDEngine(system=chain_molecule(12, seed=0))


def scopes_of(lowered) -> set:
    """The scope names in a lowered program's op locations."""
    text = lowered.as_text(debug_info=True)
    names = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        names.update(loc.split("/"))
    return names & set(SCOPES + ("inject",))


@pytest.mark.parametrize("scheme", ["neighbor", "matrix"])
@pytest.mark.parametrize("failure_rate", [0.0, 0.3])
def test_fused_chunk_carries_the_scopes(scheme, failure_rate):
    d = REMDDriver(_engine(), _cfg(scheme), failure_rate=failure_rate)
    ens = d.init()
    low = d._fused_chunk_fn(2).lower(ens, ens.state, jax.random.key(0))
    want = set(SCOPES) | ({"inject"} if failure_rate else set())
    assert scopes_of(low) == want


def test_legacy_cycle_carries_the_scopes():
    d = REMDDriver(_engine(), _cfg())
    ens = d.init()
    assert scopes_of(d._cycle_fn(0, 0).lower(ens)) == {
        "propagate", "features", "exchange"}
    assert scopes_of(d._detect_recover_fn().lower(ens, ens.state)) == {
        "detect_recover"}


SHARDED = r"""
import json, re, sys
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
import jax
from repro.launch.mesh import make_replica_mesh
from tests.test_tracing import REMDDriver, _cfg, _engine, scopes_of

d = REMDDriver(_engine(), _cfg(n_replicas=8, exchange_comm=sys.argv[2]))
mesh = make_replica_mesh(4)
ens = d.init()
fn = d._sharded_chunk_fn(2, mesh, ens)
low = fn.lower(ens, ens.state, jax.random.key(0))
text = low.as_text()
print(json.dumps({"scopes": sorted(scopes_of(low)),
                  "permutes": "collective_permute" in text}))
"""


@pytest.mark.parametrize("comm", ["halo", "gather"])
def test_sharded_chunk_carries_the_scopes(comm):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SHARDED, str(ROOT), comm],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(got["scopes"]) == set(SCOPES)
    assert got["permutes"] is (comm == "halo")


def _host_spans(log_dir: Path):
    """(name, start_ns, end_ns, args) of every ``repex.*`` host event."""
    from jax.profiler import ProfileData
    files = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    data = ProfileData.from_file(files[0])
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns,
                    {k: v for k, v in e.stats})
                   for plane in data.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("repex.")), key=lambda s: s[1])


def test_run_fused_host_spans_nest(tmp_path):
    """Two calls: one chunk that compiles, then three that do not."""
    d = REMDDriver(_engine(), _cfg(), ckpt_dir=str(tmp_path / "ckpt"),
                   ckpt_every=1)
    with jax.profiler.trace(str(tmp_path / "trace")):
        ens = d.run_fused(d.init(), n_cycles=2, chunk_cycles=2)
        d.run_fused(ens, n_cycles=6, chunk_cycles=2)
    spans = _host_spans(tmp_path / "trace")
    calls = [s for s in spans if s[0] in ("repex.start", "repex.report")]
    assert [s[0] for s in calls] == ["repex.start", "repex.report"] * 2
    chunks = [s for s in spans if s[0] == "repex.chunk"]
    assert [(c[3]["chunk"], c[3]["cycles"]) for c in chunks] == [
        (0, 2), (1, 2), (2, 2), (3, 2)]
    # each chunk lies between its call's start and report spans
    assert calls[0][2] <= chunks[0][1] and chunks[0][2] <= calls[1][1]
    assert calls[2][2] <= chunks[1][1] and chunks[-1][2] <= calls[3][1]
    first_calls = []
    for _, lo, hi, _ in chunks:
        inside = [s for s in spans if lo <= s[1] and s[2] <= hi
                  and s[0] != "repex.chunk"]
        assert [s[0] for s in inside] == [f"repex.{c}"
                                          for c in CHUNK_CHILDREN]
        first_calls.append(inside[0][3]["first_call"])
    assert first_calls == [1, 0, 0, 0]
