"""Read executor on a tiled layout (``VSS._stitch``), in ms per read:
the window's ``stitch`` spans (the decoded tile GOPs joined across
columns, rows and GOPs into one region) over its ``read`` roots."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    roots = [r for r in ctx.spans if r["name"] == "read"]
    durs = [s["dur_s"] for r in roots for s in spans(r, "stitch")]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(roots)
