"""Storage layer (``_BatchIO`` to the backend), in ms per object: the
wall time of the window's ``fetch`` spans over the objects they
fetched.  A plan group's fetch is one interval recorded on each member
that took part, so each interval counts once."""


def spans(node, name):
    if node["name"] == name:
        yield node
    for child in node.get("children", ()):
        yield from spans(child, name)


def read(ctx):
    fetches = [s for root in ctx.spans for s in spans(root, "fetch")]
    objects = sum(s.get("attrs", {}).get("objects", 0) for s in fetches)
    if not objects:
        return None
    wall = {(s["t_wall"], s["dur_s"]) for s in fetches}
    return 1e3 * sum(dur for _, dur in wall) / objects
