"""Engine layer: mean wait of a resolved batch from the resolver's put to
the dispatcher's get (``engine_handoff_wait_ms`` sum over count, one
observation per batch)."""


def read(ctx):
    h = ctx.hist("engine_handoff_wait_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
