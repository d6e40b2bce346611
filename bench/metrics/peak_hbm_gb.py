"""Peak device memory in use on the fullest of the cell's chips, in GB
(``memory_stats()["peak_bytes_in_use"]``, read after the window)."""


def read(ctx):
    return None if ctx.peak_bytes is None else ctx.peak_bytes / 1e9
