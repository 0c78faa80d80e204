"""Planner layer: share of the window's queries routed to the exact scan
(``scan_routed_total`` over scan plus beam), in percent."""


def read(ctx):
    s, b = ctx.counter("scan_routed_total"), ctx.counter("beam_routed_total")
    if s is None or b is None or s + b <= 0:
        return None
    return 100.0 * s / (s + b)
