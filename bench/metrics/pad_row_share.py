"""Planner layer: share of dispatched rows that are padding added to reach a
power-of-two batch (``pad_rows_total`` over real plus pad rows), percent."""


def read(ctx):
    pad, real = ctx.counter("pad_rows_total"), ctx.counter("queries_total")
    if real is None or real <= 0:
        return None
    pad = pad or 0
    return 100.0 * pad / (real + pad)
