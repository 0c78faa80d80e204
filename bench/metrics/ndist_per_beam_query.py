"""Beam layer: mean distance evaluations per beam-routed query of the
window, from the per-query ``ndist`` the search returns."""


def read(ctx):
    m = ctx.measured
    beam = m.ok & (m.strategy != 0)
    return float(m.ndist[beam].mean()) if beam.any() else None
