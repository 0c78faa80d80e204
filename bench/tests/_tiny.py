"""Tiny versions of the benchmark's cells, for tests on the CPU."""
from bench import harness


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


def run_tiny(cell, seed: int = 20260101, seconds: float = 1.0, fault=None):
    return harness.run_cell(cell.name, seed, seconds, False, t_process=0.0,
                            require_chip=False, cell=cell, fault=fault)
