"""The exchange of the range-sharded cell: with one shard's results left
out before the cross-shard merge, a whole tiny ``deep96x4.mixed`` run on
four CPU devices reads as not correct.

A fault cannot travel to ``_tiny``'s child process, so this test starts a
child of its own with four devices and installs the fault there."""
import json
import os
import subprocess
import sys

from bench import harness
from bench.tests._tiny import CHILD_TIMEOUT_S

CHILD = """
import json
import jax.numpy as jnp
from bench.tests import _tiny
import repro.search.substrate as sub

real = sub.merge_topk


def shard_1_left_out(ids, dists, k):
    return real(ids.at[1].set(-1), dists.at[1].set(jnp.inf), k)


def fault(index, engine):
    # the mesh programs are traced in warm-up, after this
    sub.merge_topk = shard_1_left_out


out = _tiny._run_here(_tiny.tiny_cell("deep96x4.mixed"), 2**31 + 17, 1.0,
                      False, fault=fault)
print(json.dumps(out))
"""


def test_a_shard_left_out_of_the_merge_is_not_correct():
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append("--xla_force_host_platform_device_count=4")
    path = [str(harness.ROOT), str(harness.ROOT / "src"),
            os.environ.get("PYTHONPATH", "")]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags),
               PYTHONPATH=os.pathsep.join(p for p in path if p))
    p = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, env=env, cwd=harness.ROOT,
                       timeout=CHILD_TIMEOUT_S)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert not out["correct"]
    c = out["checks"]
    assert c["scan_short"]["value"] > 0 \
        or c["beam_recall"]["value"] < c["beam_recall"]["limit"]
