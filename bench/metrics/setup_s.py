"""Process start to the first timed request: data from the seed, the build,
the warm-up of every launch shape, and one request per client."""


def read(ctx):
    return ctx.setup_s
