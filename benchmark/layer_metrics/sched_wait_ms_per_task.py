"""read scheduler: queue wait before dispatch, all lanes
(tikv_coprocessor_sched_lane_wait_seconds), per task that waited."""

from benchmark.counters import moved


def read(ctx):
    n = moved(ctx["before"], ctx["after"], "tikv_coprocessor_sched_lane_wait_seconds_count")
    if not n:
        return None
    return moved(ctx["before"], ctx["after"],
                 "tikv_coprocessor_sched_lane_wait_seconds_sum") / n * 1e3
