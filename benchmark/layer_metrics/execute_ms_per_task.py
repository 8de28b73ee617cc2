"""endpoint and router: service dispatch to answer of a coprocessor frame
(tikv_grpc_msg_duration_seconds{method=coprocessor}: the execute stage of
tikv_wire_stage_seconds, of the coprocessor frames alone), per task."""

from benchmark.counters import moved


def read(ctx):
    n = moved(ctx["before"], ctx["after"], "tikv_grpc_msg_duration_seconds_count",
              method="coprocessor")
    if not n:
        return None
    return moved(ctx["before"], ctx["after"], "tikv_grpc_msg_duration_seconds_sum",
                 method="coprocessor") / n * 1e3
