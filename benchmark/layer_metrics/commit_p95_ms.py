"""write path: the 95th percentile of the window's acknowledged refresh
transactions, ``kv_prewrite`` sent to ``kv_commit`` acknowledged, on the
writer's clock (``reduce.commit_ms`` over the client's log).  A mix without a
refresh stream has no transactions, and the reader gives None."""

from benchmark.reduce import commit_ms, percentile


def read(ctx):
    ms = commit_ms(ctx["log"]) if "txns" in ctx["log"] else []
    return percentile(ms, 95) if ms else None
