"""region column cache: readers whose start_ts lay below an image's
snapshot_ts (another session's task overtook theirs on the way to the region)
that the image served all the same, because it provably held nothing they may
not see, over all such readers of the window
(tikv_coprocessor_region_cache_below_snapshot_total, outcome served over served
and refused, ``copr/region_cache.py:_hit_fresh_locked``).  Where no reader came
below a snapshot, and in a program without the counter (the parent of the PR
that brought it), nothing moves and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_region_cache_below_snapshot_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, outcome="served") / n
