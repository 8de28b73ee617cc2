"""What the eight stage metrics share: the store's stage totals
(``tikv_trace_stage_seconds``, ``tikv_trace_stage_cpu_seconds_total``, emitted
by ``tikv_tpu/util/trace.py``'s ``stage``) between the window's two counter
snapshots, and the count of coprocessor tasks served in it.  A program without
those series (the parent of the PR that brought them) moves nothing, and every
reader here then returns ``None``."""

from benchmark.counters import moved


def tasks(ctx) -> float:
    return moved(ctx["before"], ctx["after"],
                 "tikv_grpc_msg_duration_seconds_count", method="coprocessor")


def stage_ms_per_task(ctx, *stages):
    """Wall time inside ``stages`` over the window, per coprocessor task; None
    where no task was served or none of the stages ran."""
    n = tasks(ctx)
    if not n:
        return None
    runs = sum(moved(ctx["before"], ctx["after"],
                     "tikv_trace_stage_seconds_count", stage=s) for s in stages)
    if not runs:
        return None
    return sum(moved(ctx["before"], ctx["after"],
                     "tikv_trace_stage_seconds_sum", stage=s)
               for s in stages) / n * 1e3
