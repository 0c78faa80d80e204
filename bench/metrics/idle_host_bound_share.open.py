"""Device: share of the traced window in which no operation ran on the
device and the engine's dispatcher was not waiting for a batch
(``rnsg.await_batch``), in percent; open loop.  The idle that host work
causes, as opposed to idle for want of requests (``bench/stage_idle.py``,
which also logs the idle seconds by stage)."""
from bench import harness, stage_idle, trace_reduce


def read(ctx):
    if ctx.trace is None or ctx.cell.mix["loop"] != "open":
        return None
    res = stage_idle.read(trace_reduce.find_trace(harness.STATE / "trace"))
    if res is None:
        return None
    stage_idle.log_idle(res)
    return 100.0 * res.host_bound_share
