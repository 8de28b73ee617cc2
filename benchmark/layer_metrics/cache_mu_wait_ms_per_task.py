"""region column cache: time readers and write-through notifies waited for the
cache's manager lock (stage ``cache.mu_wait``, ``copr/region_cache.py``),
per coprocessor task.  A program without the stage (the parent of the PR
that brought it) gives None."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "cache.mu_wait")
