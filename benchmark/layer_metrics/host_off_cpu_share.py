"""store process: the share of the stages' wall time in which the thread that
ran them was not on a CPU (1 - thread CPU time over wall time): waiting for the
interpreter's lock, for another lock, for the device, or descheduled.  Over
every stage that measures its thread's CPU time; the recorded waits
(``sched.wait``, ``wire.route``, ``wire.decode``) and ``host.gc`` have no CPU
series and stay out."""

from benchmark.counters import moved


def read(ctx):
    before, after = ctx["before"], ctx["after"]
    wall = cpu = 0.0
    for key in after.get("tikv_trace_stage_cpu_seconds_total", {}):
        stage = dict(key).get("stage")
        if stage is None:
            continue
        cpu += moved(before, after, "tikv_trace_stage_cpu_seconds_total",
                     stage=stage)
        wall += moved(before, after, "tikv_trace_stage_seconds_sum", stage=stage)
    if not wall:
        return None
    return 100.0 * (1.0 - cpu / wall)
