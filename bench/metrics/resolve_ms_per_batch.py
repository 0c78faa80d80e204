"""Engine layer: mean host wall of the resolver's attribute -> rank
resolve per dynamic batch (``engine_resolve_ms`` sum over count)."""


def read(ctx):
    h = ctx.hist("engine_resolve_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
