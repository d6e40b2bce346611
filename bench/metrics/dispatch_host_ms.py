"""Host time of the hybrid dispatch per launch: the summed ``prepare``,
``h2d``, ``enqueue`` and ``merge`` spans under each ``launch`` of the
window; the median over launches, in ms."""

import numpy as np
from rmqbench.spans import DISPATCH, children, seconds


def read(ctx):
    per_launch = [
        sum(seconds(spans) for spans in kids.values())
        for kids in children(ctx, DISPATCH).values()
        if "prepare" in kids and "enqueue" in kids
    ]
    return float(np.median(per_launch)) * 1e3 if per_launch else None
