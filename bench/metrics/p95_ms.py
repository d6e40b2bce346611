"""95th-percentile latency of every request issued in the window, client
side; a refused or failed request counts as missing every limit."""


def read(ctx):
    return ctx.latency_percentile_ms(95)
