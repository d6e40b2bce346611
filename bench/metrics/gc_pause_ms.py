"""Garbage collection per second of the window: the summed ``gc`` spans (one
per collection, on any thread, from the tracer's ``gc.callbacks`` hook)
that started in the window, in ms per s."""

from rmqbench.spans import named, records_hook, seconds


def read(ctx):
    if not records_hook("gc") or ctx.t1 <= ctx.t0:
        return None
    return 1e3 * seconds(named(ctx, "gc")) / (ctx.t1 - ctx.t0)
