"""Share of the HBM roofline reached by the long path (the doubling-table
gathers of ``hybrid.long_query``): the bytes its queries need
(``rmqbench.rooflines.long_bytes``, from the window's own long queries)
over the device time of the jitted ``_long_query`` program's ops times the
chip's HBM bandwidth."""

import numpy as np
from rmqbench.rooflines import long_bytes


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peaks is None:
        return None
    t = ctx.trace.op_seconds(lambda module, op: "_long_query" in module)
    nbytes = 0
    for l, r in ctx.window_queries():
        long = (r.astype(np.int64) - l + 1) > ctx.threshold
        nbytes += long_bytes(l[long], r[long])
    if not t or not nbytes:
        return None
    return 100.0 * nbytes / (t * ctx.peaks["hbm_bytes_per_s"])
