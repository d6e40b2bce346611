"""Median of the server's ``serve_queue_wait_s`` (submit to flush) over the
requests of the window."""

import numpy as np


def read(ctx):
    waits = ctx.histogram_window("serve_queue_wait_s")
    return float(np.median(waits)) * 1e3 if waits.size else None
