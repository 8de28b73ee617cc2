"""region column cache: lookups answered by a warm image as it stood
(tikv_coprocessor_region_cache_total, outcome hit) over all lookups."""

from benchmark.counters import moved


def read(ctx):
    n = moved(ctx["before"], ctx["after"], "tikv_coprocessor_region_cache_total")
    if not n:
        return None
    return 100.0 * moved(ctx["before"], ctx["after"],
                         "tikv_coprocessor_region_cache_total", outcome="hit") / n
