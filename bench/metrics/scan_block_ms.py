"""Substrate dispatch layer: mean wall per scan partition of waiting for
the device and copying its outputs back (``stage_scan_block_ms`` sum over
count)."""


def read(ctx):
    h = ctx.hist("stage_scan_block_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
