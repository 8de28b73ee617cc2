"""socket and wire: request decode plus response encode of every frame the
process served in the window (tikv_wire_stage_seconds, stages decode and
encode; the PD service's timestamp frames are among them), over the
coprocessor tasks served."""

from benchmark.counters import moved


def read(ctx):
    n = moved(ctx["before"], ctx["after"], "tikv_grpc_msg_total", method="coprocessor")
    if not n:
        return None
    s = sum(moved(ctx["before"], ctx["after"], "tikv_wire_stage_seconds_sum", stage=st)
            for st in ("decode", "encode"))
    return s / n * 1e3
