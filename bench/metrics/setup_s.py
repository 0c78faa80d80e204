"""Seconds from process start to the window's first request: data, build,
engine, prefill, warm-up and (first run in a checkout) compilation."""


def read(ctx):
    return ctx.setup_s
