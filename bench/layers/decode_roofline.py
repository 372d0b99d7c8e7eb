"""The decode kernel's share of its roofline, in %: the least HBM time
of the GOP decodes that ran wholly inside the traced window (their least
bytes, ``bench/work.py``, over the chip's bandwidth) over the device's
busy time there. In a scan every device op serves the decode, so
widening, padding and download passes count against it."""
from bench import trace_reduce, work

KERNEL = "delta_decode_pallas"  # the jitted program of one GOP decode


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    n = trace_reduce.count_modules(ctx.trace, KERNEL)
    if n == 0:
        return None
    t, h, w, c = ctx.gop_shape
    least = work.least_seconds(n * work.decode_bytes(t, h, w, c),
                               ctx.device_kind, ctx.peaks)
    return 100.0 * least / ctx.trace["busy_s"]
