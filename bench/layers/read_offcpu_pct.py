"""Host threads, in %: the share of the window's reads that their
threads spent off the CPU, 100 x (1 - sum of the ``read`` roots'
thread CPU time over the sum of their wall time). That is waiting on
the interpreter lock, a lock, the device or I/O, or runnable but not
running."""


def read(ctx):
    roots = [r for r in ctx.spans if r["name"] == "read" and "cpu_s" in r]
    wall = sum(r["dur_s"] for r in roots)
    if wall <= 0:
        return None
    return 100.0 * (1.0 - sum(r["cpu_s"] for r in roots) / wall)
