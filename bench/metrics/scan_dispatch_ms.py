"""Substrate dispatch layer: mean wall per scan partition from enqueue to
the host holding its result (``scan_dispatch_ms`` sum over count); it
includes the device time and any wait behind sibling partitions."""


def read(ctx):
    h = ctx.hist("scan_dispatch_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
