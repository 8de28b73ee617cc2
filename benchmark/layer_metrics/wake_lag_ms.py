"""store process: how late the OS wakes a sleeping thread of the store, on
average over the window: the interpreter probe's wake time, read by its
native sleep, less the time it asked for (``tikv_process_wake_lag_seconds``,
``util/trace.py``).  A host whose cores are shared or busy shows here, apart
from any wait for the interpreter."""

from benchmark.layer_metrics._waits import mean_ms


def read(ctx):
    return mean_ms(ctx, "tikv_process_wake_lag_seconds")
