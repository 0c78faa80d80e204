"""Median query latency over every answered query of the window, from when
it was due to its answer; open loop."""
from bench import stats


def read(ctx):
    if ctx.cell.mix["loop"] != "open":
        return None
    m = ctx.measured
    lat = (m.done - m.due)[m.ok] * 1e3
    return stats.percentile(lat, 50) if len(lat) else None
