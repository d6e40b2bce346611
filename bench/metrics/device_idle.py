"""One minus the union of device-op intervals over the traced window, mean
over the cell's chips."""


def read(ctx):
    if ctx.trace is None:
        return None
    idle = ctx.trace.idle_share()
    return None if idle is None else 100.0 * idle
