"""region column cache: ``RegionColumnCache.serve`` less its lock check and
less any build or delta repair (stage ``cache.lookup``: key, LRU touch, the
wait for the manager lock, the freshness test), wall time per coprocessor
task."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "cache.lookup")
