"""Serving (``serving/service.py``, ``serving/coalesce.py``), in ms per
request: the mean client time of the window's completed requests (from
when each was due to the last byte of its last segment) less the mean
duration of their ``read`` roots. That is the HTTP exchanges, intake,
the coalescer's queue, parking, and the segments' transfer."""


def read(ctx):
    roots = [r["dur_s"] for r in ctx.spans if r["name"] == "read"]
    if not roots or not getattr(ctx, "client_ms", None):
        return None
    return (sum(ctx.client_ms) / len(ctx.client_ms)
            - 1e3 * sum(roots) / len(roots))
