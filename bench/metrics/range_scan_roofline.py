"""Kernel layer: the ``range_scan`` Pallas kernel's share of its roofline, in
percent.

Work comes from the workload, not from the program's buckets: every row
inside a scan-routed query's range is read once at d float32 values and
scored with 2d flops.  At 0.5 flop per byte the bound is HBM bandwidth.  Time
is the summed device duration of the kernel's program
(``jit_range_scan_pallas``) in the traced window."""

MODULE = "jit_range_scan_pallas"


def read(ctx):
    if ctx.trace is None or not ctx.scan_rows:
        return None
    t = ctx.trace.kernel_s(MODULE)
    if not t:
        return None
    d = ctx.cell.cfg["d"]
    peaks = ctx.peaks()
    least = max(ctx.scan_rows * d * 4 / peaks["hbm_bytes_per_s"],
                ctx.scan_rows * d * 2 / peaks["bf16_flops_per_s"])
    return 100.0 * least / t
