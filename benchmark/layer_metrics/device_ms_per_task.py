"""device programs: the device's busy time in the traced span over the tasks
the device answered inside it."""

from benchmark import reduce


def read(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    start, end = ctx["trace_span"]
    n, _b = reduce.least_bytes(ctx["log"], ctx["wrong"], ctx["work"], start, end)
    return tr["busy_s"] / n * 1e3 if n else None
