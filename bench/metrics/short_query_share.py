"""Share of the window's real queries that the hybrid dispatch routed to the
short path (``serve_regime_queries_total`` by regime)."""


def read(ctx):
    short = ctx.counter_delta("serve_regime_queries_total", regime="short")
    long = ctx.counter_delta("serve_regime_queries_total", regime="long")
    return 100.0 * short / (short + long) if short + long else None
