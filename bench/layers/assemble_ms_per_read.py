"""Read executor (``VSS._extract`` and ``VSS._execute``), in ms per
read: the window's ``assemble`` spans (concatenation, crop, stride and
resample of the decoded frames) over its reads."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    durs = [s["dur_s"] for root in ctx.spans for s in spans(root, "assemble")]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(ctx.spans)
