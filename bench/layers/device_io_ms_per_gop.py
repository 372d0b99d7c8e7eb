"""Host-device boundary (``codec/tvc.py`` ``decode_gop``), in ms per
decoded GOP: the window's ``upload`` (the stored planes handed to JAX)
and ``download`` (from the first device op's dispatch to the uint8
frames on the host, so the device's work and the copy back) over the
number of ``download`` spans, one per GOP that reached the device."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    up = [s["dur_s"] for root in ctx.spans for s in spans(root, "upload")]
    down = [s["dur_s"] for root in ctx.spans for s in spans(root, "download")]
    if not down:
        return None
    return 1e3 * (sum(up) + sum(down)) / len(down)
