"""Codec, host stage (``codec/tvc.py`` ``gop_planes``), in ms per GOP:
the mean duration of the window's ``inflate`` spans, each one GOP's
per-frame zstd (or zlib) chunks inflated on the host, each straight into
its slot of one planes buffer."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    durs = [s["dur_s"] for root in ctx.spans for s in spans(root, "inflate")]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
