"""The codec kernels' share of their roofline, in %: the least HBM time
(``bench/work.py``, at the chip's bandwidth) of the GOP decodes, encodes
and transcodes that ran wholly inside the traced window, over the
device's busy time there.

Each kernel run is read from its own op in the trace: the HLO text of
its custom call (``trace_reduce`` keeps these as ``kernels``) gives the
GOP's frames and its padded planes. A plane's true size is the largest
of the cell's frame sizes (``ctx.frame_sizes``, ``(h, w)``) that fits
in it. Residuals count at their width where the kernel takes or gives
them narrow; where it works on residuals widened outside it, at one
byte, the narrowest any tier stores, so a 16-bit tier reads low. A GOP
of one frame runs no kernel and is not counted. Every device op of the
window counts in the busy time, so the passes around the kernels count
against them."""
import re

from bench import work

KERNELS = ("delta_decode_pallas", "delta_encode_pallas", "transcode_pallas")
SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
WIDTH = {"s8": 1, "u8": 1, "s16": 2, "u16": 2}


def _shapes(text: str):
    return [(t, [int(x) for x in d.split(",") if x])
            for t, d in SHAPE.findall(text)]


def _true_size(hp: int, wp: int, sizes):
    fits = [(h, w) for h, w in sizes if h <= hp and w <= wp]
    return max(fits, key=lambda s: s[0] * s[1]) if fits else None


def gop_bytes(hlo: str, sizes):
    """Least bytes of the codec work of one kernel op, or None where the
    op is not one of the codec's kernels or its planes fit no size."""
    name = hlo.split(" = ", 1)[0].lstrip("%").rsplit(".", 1)[0]
    if name not in KERNELS or " custom-call(" not in hlo:
        return None
    out, args = (_shapes(x) for x in hlo.split(" custom-call(", 1))
    if name == "transcode_pallas":
        (rin, (n, c, hp, wp)), (rout, (_, _, hq, _)) = args[1], out[1]
        t = n + 1
    elif name == "delta_decode_pallas":
        (_, (t, c, hp, wp)), rin = out[0], args[1][0]
    else:
        (_, (t, c, hp, wp)), rin = args[0], out[1][0]
    size = _true_size(hp, wp, sizes)
    if size is None:
        return None
    h, w = size
    if name == "transcode_pallas":
        return work.transcode_bytes(t, h, w, c, factor=hp // hq,
                                    resid_in=WIDTH.get(rin, 1),
                                    resid_out=WIDTH.get(rout, 1))
    nbytes = work.decode_bytes if name == "delta_decode_pallas" \
        else work.encode_bytes
    return nbytes(t, h, w, c, WIDTH.get(rin, 1))


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    total = sum(gop_bytes(hlo, ctx.frame_sizes) or 0
                for hlo in ctx.trace.get("kernels", ()))
    if not total:
        return None
    return 100.0 * work.least_seconds(total, ctx.device_kind,
                                      ctx.peaks) / ctx.trace["busy_s"]
