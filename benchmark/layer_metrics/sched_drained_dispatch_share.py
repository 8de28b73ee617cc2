"""read scheduler: dispatcher passes released because no read was on its way
to the queue any more (``why=drained``) over all passes of the window that
took riders off the lanes (tikv_coprocessor_sched_dispatch_total, why drained
over drained, deadline, full and stop, ``copr/scheduler.py:_count_dispatch``).
A program without the counter (the parent of the PR that brought it) moves
nothing, and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_sched_dispatch_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, why="drained") / n
