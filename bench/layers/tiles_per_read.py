"""Read executor on a tiled layout (``VSS._load_tiled_frames``), in tile
GOPs per read: the window's ``stitch`` spans' ``tiles`` (the decoded
tile GOPs each read joined into its region) over its ``read`` roots."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    roots = [r for r in ctx.spans if r["name"] == "read"]
    stitches = [s for r in roots for s in spans(r, "stitch")]
    if not stitches:
        return None
    return sum(s.get("attrs", {}).get("tiles", 0)
               for s in stitches) / len(roots)
