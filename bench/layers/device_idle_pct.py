"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of device-op intervals / window)."""
from bench import trace_reduce


def read(ctx):
    if ctx.trace is None or ctx.trace["window_s"] <= 0:
        return None
    return trace_reduce.idle_pct(ctx.trace)
