"""Engine layer: mean wait of a request from ``submit`` to its batch
closing (``engine_queue_wait_ms`` sum over count, one observation per
request)."""


def read(ctx):
    h = ctx.hist("engine_queue_wait_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
