"""region column cache: the changed blocks a fold or a fork re-placed on the
device by sending it the rows that changed (``how=edit``) over all it
re-placed, the others shipped whole (``how=block``;
``tikv_coprocessor_region_cache_fold_place_total{how}``,
``copr/cache.py:ColumnBlockCache.successor``, counted once per changed block
whose stacked pins were re-placed).  Where nothing was re-placed, and in a
program without the counter (the parent of the PR that brought it), nothing
moves and the reader gives None."""

from benchmark.counters import moved

SERIES = "tikv_coprocessor_region_cache_fold_place_total"


def read(ctx):
    n = moved(ctx["before"], ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"], SERIES, how="edit") / n
