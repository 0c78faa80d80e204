"""Planner layer, mesh path: share of the (query, shard) pairs with a
non-empty shard-local clip that the shard scanned
(``mesh_shard_scan_total`` over scan plus beam pairs), in percent."""


def read(ctx):
    s = ctx.counter("mesh_shard_scan_total")
    b = ctx.counter("mesh_shard_beam_total")
    if s is None or b is None or s + b <= 0:
        return None
    return 100.0 * s / (s + b)
