"""Substrate dispatch layer: mean host wall per scan partition of building
its padded host arrays and device copies (``stage_scan_prep_ms`` sum over
count)."""


def read(ctx):
    h = ctx.hist("stage_scan_prep_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
