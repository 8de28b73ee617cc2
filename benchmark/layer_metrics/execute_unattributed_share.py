"""endpoint and router: the share of the coprocessor requests' time, root span
``rpc.coprocessor`` from frame decode to frame sent, that no stage accounts
for (``tikv_trace_request_seconds_total`` against
``tikv_trace_request_attributed_seconds_total``, both summed by the tracer as
each trace finishes).  A rider of a shared batch is attributed all of the
batch's stages, since it waits for all of them."""

from benchmark.counters import moved


def read(ctx):
    total = moved(ctx["before"], ctx["after"],
                  "tikv_trace_request_seconds_total", method="coprocessor")
    if not total:
        return None
    attributed = moved(ctx["before"], ctx["after"],
                       "tikv_trace_request_attributed_seconds_total",
                       method="coprocessor")
    return 100.0 * (1.0 - attributed / total)
