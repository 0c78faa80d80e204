"""Device: share of the traced window in which no operation ran on the
device (1 - union of op intervals / window), in percent; closed loop."""


def read(ctx):
    if ctx.trace is None or ctx.cell.mix["loop"] != "closed":
        return None
    return 100.0 * ctx.trace.idle_share
