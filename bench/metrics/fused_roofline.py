"""Share of the HBM roofline reached by the fused short-path kernel: the
bytes its queries need (``rmqbench.rooflines.fused_bytes``, from the
window's own short queries) over its device time times the chip's HBM
bandwidth. The device time is the kernel's own events
(``tpu_custom_call``) inside the short-path program."""

import numpy as np
from rmqbench.rooflines import fused_bytes


def is_kernel(module, op):
    return "fused_query" in module and "tpu_custom_call" in op


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices or ctx.peaks is None:
        return None
    t = ctx.trace.op_seconds(is_kernel)
    nbytes = 0
    for l, r in ctx.window_queries():
        short = (r.astype(np.int64) - l + 1) <= ctx.threshold
        nbytes += fused_bytes(l[short], r[short])
    if not t or not nbytes:
        return None
    return 100.0 * nbytes / (t * ctx.peaks["hbm_bytes_per_s"])
