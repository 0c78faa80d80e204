"""Tiny versions of the benchmark's cells, for tests on the CPU.

``run_tiny`` runs one in this process when it has as many devices as the
cell asks for, and otherwise in a child process on that many virtual CPU
devices (``python -m bench.tests._tiny`` reads the job on its standard
input and prints the result line last)."""
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from unittest import mock

from bench import harness

RECORDED = harness.BENCH / "testdata" / "small.xplane.pb"
PEAKS = {"hbm_bytes_per_s": 8.19e11, "bf16_flops_per_s": 1.97e14}
CHILD_TIMEOUT_S = 120


def tiny_cell(name: str, n: int = 2048, d: int = 32, **mix):
    cell = harness.resolve_cell(harness.load_spec(), name)
    cfg = dict(cell.cfg, n=n, d=d)
    cfg["engine"] = dict(cfg["engine"], max_batch=8)
    cell.cfg = cfg
    m = dict(cell.mix, settle_s=0.3)
    if "clients" in m:
        m["clients"] = 8
    if "rate" in m:
        m["rate"] = 40.0
    m.update(mix)
    cell.mix = m
    return cell


@contextlib.contextmanager
def recorded_trace():
    """The window's device trace replaced by the one recorded on a v5e chip
    (the CPU's has no device operations), with that chip's peaks."""
    from bench import trace_reduce
    with mock.patch.object(trace_reduce, "find_trace", lambda d: RECORDED), \
            mock.patch.object(harness.Context, "peaks", lambda self: PEAKS):
        yield


def _run_here(cell, seed, seconds, trace, fault=None):
    with recorded_trace() if trace else contextlib.nullcontext():
        return harness.run_cell(cell.name, seed, seconds, trace,
                                t_process=0.0, require_chip=False, cell=cell,
                                fault=fault)


def run_tiny(cell, seed: int = 20260101, seconds: float = 1.0, fault=None,
             trace: bool = False):
    """The result object of one run of ``cell``.  A cell that asks for more
    chips than this process has devices runs in a child process with that
    many CPU devices; ``fault`` cannot travel there."""
    import jax
    chips = int(cell.entry["chips"])
    if len(jax.devices()) >= chips:
        return _run_here(cell, seed, seconds, trace, fault)
    if fault is not None:
        raise ValueError("a fault runs only in this process")
    job = {"cell": dict(dataclasses.asdict(cell), root=str(cell.root)),
           "seed": seed, "seconds": seconds, "trace": trace}
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={chips}")
    path = [str(harness.ROOT), str(harness.ROOT / "src"),
            os.environ.get("PYTHONPATH", "")]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(flags),
               PYTHONPATH=os.pathsep.join(p for p in path if p))
    p = subprocess.run([sys.executable, "-m", "bench.tests._tiny"],
                       input=json.dumps(job), capture_output=True, text=True,
                       env=env, cwd=harness.ROOT, timeout=CHILD_TIMEOUT_S)
    if p.returncode != 0:
        raise RuntimeError(f"child run exited {p.returncode}:\n"
                           f"{p.stderr[-4000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


if __name__ == "__main__":
    job = json.load(sys.stdin)
    c = harness.Cell(**dict(job["cell"], root=Path(job["cell"]["root"])))
    out = _run_here(c, job["seed"], job["seconds"], job["trace"])
    print(json.dumps(out))
