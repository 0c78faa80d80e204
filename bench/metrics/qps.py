"""Queries answered per second over the whole window: from the first send
to the last answer of the window's sends (closed loop)."""
from bench import stats


def read(ctx):
    if ctx.cell.mix["loop"] != "closed":
        return None
    m = ctx.measured
    return stats.rate(int(m.ok.sum()), m.start, m.drained)
