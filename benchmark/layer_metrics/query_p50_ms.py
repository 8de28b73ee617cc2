"""client streams: median over all queries of the window, all plans: issue to
the last task's answer, on the client's clock.  Not an end-to-end metric: with
a third of the lookups refused as stale, the median falls between the two
modes of the queries' times (all hits; at least one cold scan) and swings with
their shares (PERF.md)."""

from benchmark import reduce


def read(ctx):
    ms = reduce.query_ms(ctx["log"])
    return reduce.percentile(ms, 50) if ms else None
