"""region column cache: the wall time of repairing warm images behind the
engine (stage ``cache.repair``, ``copr/region_cache.py``: one image's fold,
walk or rebuild, under that image's own lock and off the cache's manager
lock), per coprocessor task.  A program without the stage (the parent of the
PR that brought it) gives None."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "cache.repair")
