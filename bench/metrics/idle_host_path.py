"""Share of the device's idle time that the worker's host path explains: the
part of each chip's idle gaps in the window that lies inside the program's
``rmq.launch``, ``rmq.d2h``, ``rmq.scatter``, ``rmq.finish`` or ``rmq.gc``
host events and outside every ``rmq.wait`` (a mixed batch's wait on the
device, inside its launch), on the profiler's clock; mean over the cell's
chips."""

from rmqbench.spans import host_events, minus, overlap

HOST = ("launch", "d2h", "scatter", "finish", "gc")


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.devices:
        return None
    host = minus(host_events(tr, HOST), host_events(tr, ("wait",)))
    if not host:
        return None
    shares = []
    for d in tr.devices:
        gaps = tr.gaps(d)
        idle = sum(b - a for a, b in gaps)
        if idle > 0:
            shares.append(overlap(gaps, host) / idle)
    return 100.0 * sum(shares) / len(shares) if shares else None
