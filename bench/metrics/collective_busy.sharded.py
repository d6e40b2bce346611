"""Device time of collective ops (all-reduce and the like) over the traced
window, mean over the cell's chips."""

import re

COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    t = ctx.trace.op_seconds(lambda module, op: bool(COLLECTIVE.search(op)))
    return 100.0 * t / ctx.trace.window_s if t else None
