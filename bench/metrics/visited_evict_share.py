"""Beam layer: share of the visited table's inserts that made it forget an
id, percent (``beam_visited_evictions_total`` over
``beam_visited_inserts_total`` in the window).  A forgotten id is re-scored
when met again, which inflates ``ndist``; the results stay exact."""


def read(ctx):
    ins = ctx.counter("beam_visited_inserts_total")
    if ins is None or ins <= 0:
        return None
    return 100.0 * (ctx.counter("beam_visited_evictions_total") or 0) / ins
