"""95th percentile over all queries of the window, all plans: issue to the
last task's answer, on the client's clock."""

from benchmark import reduce


def read(ctx):
    ms = reduce.query_ms(ctx["log"])
    return reduce.percentile(ms, 95) if ms else None
