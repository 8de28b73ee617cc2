"""region column cache: repairs of a warm image behind the engine that folded
the write-through chain's prefix up to the reader's apply index (``how=fold``)
over all repairs of the window (fold, a walk of the region's CF_WRITE, a
rebuild; ``tikv_coprocessor_region_cache_repair_total{how}``,
``copr/region_cache.py``, counted once per repair).  Where no repair ran, and
in a program without the counter (the parent of the PR that brought it),
nothing moves and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_region_cache_repair_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, how="fold") / n
