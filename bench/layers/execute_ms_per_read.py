"""Read executor (``VSS._execute``, ``VSS._execute_encoded``), in ms per
read: the mean ``decode`` span of the window's reads, which holds the
fetches inside it, host inflate, the codec's device work, crop, stride,
pooling and re-encode."""


def read(ctx):
    durs = [c["dur_s"] for root in ctx.spans if root["name"] == "read"
            for c in root.get("children", ()) if c["name"] == "decode"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
