"""Serving's coalescer, in requests per batch: the mean of the
``vss_serve_coalesce_width`` histogram over the window, that is how many
requests each ``read_batch`` that the dispatcher sent held."""


def read(ctx):
    total, batches = getattr(ctx, "coalesce", (0.0, 0))
    if not batches:
        return None
    return total / batches
