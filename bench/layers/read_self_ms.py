"""Read entry (``VSS.read_batch``), in ms per read: the mean, over the
window's ``read`` roots, of the root's duration less the union of its
children's intervals, that is the time of a read that no span names."""


def self_s(root) -> float:
    t0, t1 = root["t_wall"], root["t_wall"] + root["dur_s"]
    covered, end = 0.0, t0
    for a, b in sorted((c["t_wall"], c["t_wall"] + c["dur_s"])
                       for c in root.get("children", ())):
        a, b = max(a, end), min(b, t1)
        if b > a:
            covered += b - a
            end = b
    return max(0.0, root["dur_s"] - covered)


def read(ctx):
    roots = [r for r in ctx.spans if r["name"] == "read"]
    if not roots:
        return None
    return 1e3 * sum(self_s(r) for r in roots) / len(roots)
