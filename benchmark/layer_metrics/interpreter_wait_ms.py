"""store process: how long a thread that the OS has woken waits for the
interpreter's lock, on average over the window.  Read from the interpreter
probe, which sleeps in native code at moments unrelated to the serving
threads and times its wake from the native sleep's own clock to its return
into Python (``tikv_process_interpreter_wait_seconds``, ``util/trace.py``):
what any serving thread pays each time it comes back from a socket, a lock
or a device read-back."""

from benchmark.layer_metrics._waits import mean_ms


def read(ctx):
    return mean_ms(ctx, "tikv_process_interpreter_wait_seconds")
