"""95th-percentile query latency over every answered query of the window,
from when it was sent to its answer; closed loop."""
from bench import stats


def read(ctx):
    if ctx.cell.mix["loop"] != "closed":
        return None
    m = ctx.measured
    lat = (m.done - m.due)[m.ok] * 1e3
    return stats.percentile(lat, 95) if len(lat) else None
