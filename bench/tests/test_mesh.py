"""The replica-mesh path on four virtual CPU devices: a sound sharded run
is correct, and one whose halo exchange between chips is left out is
not.  Each run is a process of its own, since the device count is fixed
when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests.conftest import ROOT

SCRIPT = r"""
import io, json, sys, time
from pathlib import Path
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1]]
from bench import harness
from bench.tests.conftest import make_tiny_root, TINY

def no_halo(entry, driver):
    # every shard keeps its own block: the ring's hops are left out
    import jax, jax.numpy as jnp
    import repro.core.exchange as X
    def local_only(x, axis_name, n_shards, reverse=False):
        idx = jax.lax.axis_index(axis_name)
        return jnp.zeros((n_shards,) + x.shape, x.dtype).at[idx].set(x)
    X.ring_all_gather = local_only
    return entry

root = make_tiny_root(Path(sys.argv[2]), rungs=8, shards=4)
# a wide ladder on a longer chain, so that swaps between blocks on
# different chips are far from certain and a wrong one shows
conf = root / "bench" / "configs" / "tiny.json"
c = json.loads(conf.read_text())
c["system"]["n_atoms"] = 64
c["ladder"].update(t_min_K=50.0, t_max_K=2000.0)
conf.write_text(json.dumps(c))
buf = io.StringIO()
harness.run(TINY, 2 ** 31 + 11, 0.3, False, time.perf_counter(),
            on_chip=False, fault=no_halo if sys.argv[3] == "1" else None,
            out=buf, root=root)
print(buf.getvalue().strip().splitlines()[-1])
"""


@pytest.mark.parametrize("broken", [False, True])
def test_halo_exchange_between_chips(tmp_path, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(ROOT),
                        str(tmp_path), "1" if broken else "0"],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not broken), res["checks"]
