"""Real queries over padded queries, summed over the window's ``flush``
spans (attributes ``n_queries`` and ``padded``)."""


def read(ctx):
    flushes = [s for s in ctx.spans if s.name == "flush" and "padded" in s.attrs]
    padded = sum(s.attrs["padded"] for s in flushes)
    return 100.0 * sum(s.attrs["n_queries"] for s in flushes) / padded if padded else None
