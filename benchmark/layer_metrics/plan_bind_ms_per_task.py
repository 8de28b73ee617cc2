"""endpoint and router: taking a task's plan apart into its shape and its
literals and finding the shape's evaluator (stage ``copr.bind``:
``copr/endpoint.py:_bind`` and the split at the read scheduler's admission,
``copr/scheduler.py:_batchable_sig``), wall time per coprocessor task: what
serving a literal the store has not seen costs a warm read.  A program
without the stage (the parent of the PR that brought it) gives None."""

from benchmark.layer_metrics._stages import stage_ms_per_task


def read(ctx):
    return stage_ms_per_task(ctx, "copr.bind")
