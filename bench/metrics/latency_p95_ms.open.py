"""95th-percentile query latency over every answered query of the window,
from when it was due to its answer; open loop.  A per-layer reading: a
host stall of a fraction of a second shifts the tail of a 20 s window by
more than any end-to-end bound could hold."""
from bench import stats


def read(ctx):
    if ctx.cell.mix["loop"] != "open":
        return None
    m = ctx.measured
    lat = (m.done - m.due)[m.ok] * 1e3
    return stats.percentile(lat, 95) if len(lat) else None
