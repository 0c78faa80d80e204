"""Substrate dispatch layer, mesh path: mean host wall per mesh call of
padding the batch, building every group's per-shard rows and uploading
them (``stage_mesh_prep_ms`` sum over count)."""


def read(ctx):
    h = ctx.hist("stage_mesh_prep_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
