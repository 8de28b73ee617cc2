"""store process: time inside the interpreter's cyclic collector, all
generations (``tikv_process_gc_pause_seconds_total``, timed by the
``gc.callbacks`` hook that ``server/standalone.py`` installs at store start),
per coprocessor task.  A pause stops every thread of the store."""

from benchmark.counters import moved
from benchmark.layer_metrics._stages import tasks


def read(ctx):
    n = tasks(ctx)
    if not n or "tikv_process_gc_pause_seconds_total" not in ctx["after"]:
        return None
    return moved(ctx["before"], ctx["after"],
                 "tikv_process_gc_pause_seconds_total") / n * 1e3
