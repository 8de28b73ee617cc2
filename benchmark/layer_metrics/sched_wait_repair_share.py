"""read scheduler: the share of the riders' queue wait (stage ``sched.wait``)
that the dispatcher spent repairing region images meanwhile (stages
``cache.repair`` and ``fill.*`` on its thread): ``behind=repair`` over all of
``tikv_coprocessor_sched_wait_behind_seconds_total{behind}``
(``copr/scheduler.py:_observe_wait``, from the dispatcher's wait clock at
enqueue and at pick-up)."""

from benchmark.layer_metrics._waits import behind_share


def read(ctx):
    return behind_share(ctx, "repair")
