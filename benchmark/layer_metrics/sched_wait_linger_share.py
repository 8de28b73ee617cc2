"""read scheduler: the share of the riders' queue wait (stage ``sched.wait``)
that the dispatcher spent in its own linger meanwhile, waiting for riders,
for partners or for a lane's deadline: ``behind=linger`` over all of
``tikv_coprocessor_sched_wait_behind_seconds_total{behind}``
(``copr/scheduler.py:_observe_wait``)."""

from benchmark.layer_metrics._waits import behind_share


def read(ctx):
    return behind_share(ctx, "linger")
