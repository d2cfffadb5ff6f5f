"""The ring-sharded cell's driver on four CPU devices, in a child process
(the device count is fixed when JAX starts): its counts match the
reference, and leaving out the exchange between chips (the psum of the
sharded partials) comes out not correct."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

CHILD = """
import json, sys
import pytest
from cells import tiny_run
mp = pytest.MonkeyPatch()
if sys.argv[1] == "no_exchange":
    import jax
    mp.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
r = tiny_run(mp, "s18-ring4-sessions", seed=2**31 + 61)
print(json.dumps(r))
"""


def _child(mode):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", CHILD, mode], cwd=HERE, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_ring_driver_counts_match_the_reference():
    r = _child("plain")
    assert r["correct"] is True, r["checks"]
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"edges_per_s", "setup_s"}


def test_ring_without_the_exchange_is_not_correct():
    r = _child("no_exchange")
    assert r["correct"] is False
