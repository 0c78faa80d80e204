"""Substrate dispatch layer: mean wall per beam partition from enqueue to
the host holding its result (``beam_dispatch_ms`` sum over count)."""


def read(ctx):
    h = ctx.hist("beam_dispatch_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
