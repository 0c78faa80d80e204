"""Device: busy milliseconds per chip in the traced window (the union of
the device's op intervals, averaged over the chips) per query answered in
it."""


def read(ctx):
    if ctx.trace is None:
        return None
    n = int(ctx.measured.ok.sum())
    if n == 0:
        return None
    return ctx.trace.busy_s * 1e3 / n
