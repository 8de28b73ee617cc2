"""What the four wait metrics share: the interpreter probe's histograms
(``tikv_process_wake_lag_seconds``, ``tikv_process_interpreter_wait_seconds``)
and the read scheduler's split of each rider's queue wait
(``tikv_coprocessor_sched_wait_behind_seconds_total{behind}``), all emitted
while the store's sample rate is above 0 (``tikv_tpu/util/trace.py``,
``tikv_tpu/copr/scheduler.py``).  A program without those series moves
nothing, and every reader here then returns ``None``."""

from benchmark.counters import moved

BEHIND = "tikv_coprocessor_sched_wait_behind_seconds_total"


def mean_ms(ctx, histogram: str):
    """The mean of a histogram's observations in the window, in ms."""
    n = moved(ctx["before"], ctx["after"], f"{histogram}_count")
    if not n:
        return None
    return moved(ctx["before"], ctx["after"], f"{histogram}_sum") / n * 1e3


def behind_share(ctx, behind: str):
    """The share of the riders' queue wait that the dispatcher spent on
    ``behind`` (``linger``, ``repair``, ``device``, ``serve``), in %."""
    total = moved(ctx["before"], ctx["after"], BEHIND)
    if not total:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], BEHIND, behind=behind) / total
