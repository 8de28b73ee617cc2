"""device programs: host time inside jitted calls (stage ``device.launch``,
``copr/observatory.py:_TimedJit.__call__``: argument handling, dispatch, and
compile or cache fetch when there is one), per coprocessor task.  A batch's
program is dispatched once, so for batched tasks this is the batch's time
over its riders."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "device.launch")
