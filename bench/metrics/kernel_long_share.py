"""Share of the window's real queries that were longer than the sqrt(n)
regime boundary and were answered by the fused kernel because the hybrid
was built without its doubling table over the raw array
(``serve_kernel_long_queries_total`` over ``serve_queries_total``, both as
deltas over the window). Silent where the program has no such counter."""

COUNTER = "serve_kernel_long_queries_total"


def read(ctx):
    registry = getattr(ctx, "_registry", None)
    if registry is None or not any(name == COUNTER for name, _ in registry.counters()):
        return None
    total = ctx.counter_delta("serve_queries_total")
    return 100.0 * ctx.counter_delta(COUNTER) / total if total else None
