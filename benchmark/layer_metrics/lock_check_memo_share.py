"""region column cache: warm-hit lock checks answered by the image's lock-free
memo (the snapshot proved CF_LOCK unchanged since a scan that met no lock)
over all lock checks of the window
(tikv_coprocessor_region_cache_lock_check_total, how memo over memo and scan,
``copr/region_cache.py:_check_locks``).  A program without the counter (the
parent of the PR that brought it) moves nothing, and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_region_cache_lock_check_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, how="memo") / n
