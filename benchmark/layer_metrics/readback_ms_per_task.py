"""device programs: the blocking read-back of aggregate states (stage
``device.pull``: ``np.asarray`` of the packed states in ``copr/jax_eval.py``
and ``copr/jax_zone.py``), per coprocessor task.  It holds the device's own
run time, which the host waits out here.  A batch is pulled once, so for
batched tasks this is the batch's time over its riders."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "device.pull")
