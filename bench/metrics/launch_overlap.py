"""Share of the window's served launches that were issued while the
worker's previous launch was still computing on the device
(``serve_launches_overlapped_total`` over ``serve_batches_total``, both as
deltas over the window): how often the server's launch pipeline hides one
launch's host path under another's device work. Silent where the program
has no such counter."""

COUNTER = "serve_launches_overlapped_total"


def read(ctx):
    registry = getattr(ctx, "_registry", None)
    if registry is None or not any(name == COUNTER for name, _ in registry.counters()):
        return None
    total = ctx.counter_delta("serve_batches_total")
    return 100.0 * ctx.counter_delta(COUNTER) / total if total else None
