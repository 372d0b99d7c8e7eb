"""Planner (``VSS._plan_group``, ``core/select.py``), in ms per read: the
mean ``plan`` span of the window's reads (a plan group's one interval,
recorded on each of its members)."""


def read(ctx):
    durs = [c["dur_s"] for root in ctx.spans if root["name"] == "read"
            for c in root.get("children", ()) if c["name"] == "plan"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
