"""Substrate dispatch layer, mesh path: mean host wall per mesh call of
waiting for the merged result and copying it back (``stage_mesh_block_ms``
sum over count)."""


def read(ctx):
    h = ctx.hist("stage_mesh_block_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
