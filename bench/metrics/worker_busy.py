"""Share of the window in which the server's worker is in its launch cycle:
the union of its ``launch``, ``wait``, ``d2h``, ``scatter`` and ``finish``
spans over the window. Under 100% the client or the batcher starves it."""

from rmqbench.spans import CYCLE, named, records_cycle
from rmqbench.trace import merge


def read(ctx):
    if not records_cycle(ctx) or ctx.t1 <= ctx.t0:
        return None
    spans = [(max(s.t0, ctx.t0), min(s.t1, ctx.t1)) for s in named(ctx, *CYCLE)]
    busy = sum(b - a for a, b in merge([(a, b) for a, b in spans if b > a]))
    return 100.0 * busy / (ctx.t1 - ctx.t0)
