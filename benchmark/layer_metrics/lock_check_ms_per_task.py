"""region column cache: the CF_LOCK walk over a task's ranges on a cache hit
(stage ``cache.lock_check``, ``copr/region_cache.py:_check_locks``), wall time
per coprocessor task.  One walk a task, under the cache's manager lock."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "cache.lock_check")
