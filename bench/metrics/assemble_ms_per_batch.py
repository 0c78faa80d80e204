"""Substrate stitch layer: mean host wall per batch of assembling its
result — request-order scatter of the scan and beam partitions (routed by
one threshold, ``max_scan_frac``), their histograms and counters, id
remap and cache (``stage_assemble_ms`` sum over count)."""


def read(ctx):
    h = ctx.hist("stage_assemble_ms")
    if h is None or h[1] <= 0:
        return None
    return h[0] / h[1]
