"""Planner layer, mesh path: share of the rows the mesh programs ran that
are padding — group pads and the batch's pad to its row count, counted on
every shard (``mesh_pad_rows_total`` over ``mesh_rows_total``), percent."""


def read(ctx):
    pad, rows = ctx.counter("mesh_pad_rows_total"), \
        ctx.counter("mesh_rows_total")
    if pad is None or rows is None or rows <= 0:
        return None
    return 100.0 * pad / rows
