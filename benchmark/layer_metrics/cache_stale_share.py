"""region column cache: lookups turned away because the reader's start_ts lay
below the image's snapshot and the image could not vouch for it, each answered
by a cold scan (tikv_coprocessor_region_cache_total, outcome stale) over all
lookups."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_region_cache_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, outcome="stale") / n
