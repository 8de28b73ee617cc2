"""device programs: the least time the chip could take for the tasks the
device answered inside the traced span (each plan's bytes to read, from
benchmark/plans/, over the chip's memory bandwidth from benchmark/peaks.json;
every plan here is bound by bytes, not by operations) over the device's busy
time in that span.  The work is the plan's, whatever program implements it."""

from benchmark import reduce


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    start, end = ctx["trace_span"]
    n, nbytes = reduce.least_bytes(ctx["log"], ctx["wrong"], ctx["work"], start, end)
    if not n:
        return None
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / tr["busy_s"]
