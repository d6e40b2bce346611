"""Median latency of every request issued in the window, client side."""


def read(ctx):
    return ctx.latency_percentile_ms(50)
