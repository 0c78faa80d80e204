"""Beam program: device milliseconds of the beam search program
(``jit_beam_search_batch``) in the traced window, per beam-routed query
answered in it."""

MODULE = "jit_beam_search_batch"


def read(ctx):
    if ctx.trace is None:
        return None
    t = ctx.trace.kernel_s(MODULE)
    m = ctx.measured
    nq = int((m.ok & (m.strategy != 0)).sum())
    if t is None or nq == 0:
        return None
    return t * 1e3 / nq
