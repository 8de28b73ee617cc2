"""read scheduler: tasks per micro-batch dispatched in the window
(tikv_coprocessor_sched_batch_occupancy), all kinds."""

from benchmark.counters import moved


def read(ctx):
    n = moved(ctx["before"], ctx["after"], "tikv_coprocessor_sched_batch_occupancy_count")
    if not n:
        return None
    return moved(ctx["before"], ctx["after"],
                 "tikv_coprocessor_sched_batch_occupancy_sum") / n
