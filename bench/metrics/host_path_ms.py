"""Host time of one launch cycle: per flush of the window, its ``launch``,
``d2h``, ``scatter`` and ``finish`` spans, less every ``wait`` on the
device inside them (the mixed batch's, under ``launch``); the median over
the window's flushes, in ms."""

import numpy as np
from rmqbench.spans import CYCLE, children, records_cycle, seconds

HOST = ("launch", "d2h", "scatter", "finish")


def read(ctx):
    if not records_cycle(ctx):
        return None
    waits = children(ctx, ("wait",))
    per_flush = []
    for kids in children(ctx, CYCLE).values():
        if "launch" not in kids or "finish" not in kids:
            continue  # a cycle the window cut
        host = [s for name in HOST for s in kids.get(name, ())]
        waited = sum(seconds(waits.get(s.span_id, {}).get("wait", ())) for s in host)
        per_flush.append(seconds(host) - waited)
    return float(np.median(per_flush)) * 1e3 if per_flush else None
