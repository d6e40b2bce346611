"""Share of the HBM roofline reached by the fused kernel over every query
it answered, long ranges included: the bytes those queries need
(``rmqbench.rooflines.fused_bytes``: their one or two rows, the two
interior cells where whole blocks lie between, and their I/O) over the
kernel's own device time (``tpu_custom_call`` events inside the
``fused_query`` program) times the chip's HBM bandwidth. The kernel
answers the queries no longer than the plan's threshold, which is ``n``
where the hybrid has no long-path table."""

import numpy as np
from rmqbench.rooflines import fused_bytes


def is_kernel(module, op):
    return "fused_query" in module and "tpu_custom_call" in op


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peaks is None or ctx.threshold is None:
        return None
    t = ctx.trace.op_seconds(is_kernel)
    nbytes = 0
    for l, r in ctx.window_queries():
        answered = (r.astype(np.int64) - l + 1) <= ctx.threshold
        nbytes += fused_bytes(l[answered], r[answered])
    if not t or not nbytes:
        return None
    return 100.0 * nbytes / (t * ctx.peaks["hbm_bytes_per_s"])
