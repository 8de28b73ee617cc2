"""write path: keys of ``kv_prewrite`` and ``kv_commit`` whose action read
the engine through its command's batched reads alone
(``tikv_storage_txn_batched_read_keys_total{how="batch"}``,
``tikv_tpu/storage/txn/commands.py``) over all their keys (``how=walk``: point
reads besides), since the store started: the load lies in the set-up, before
the window's first snapshot, so the total is read and not what moved.  A
program without the counter (the parent of the PR that brought it) has no
such series, and the reader gives None."""

from benchmark.counters import total

SERIES = "tikv_storage_txn_batched_read_keys_total"


def read(ctx):
    n = total(ctx["after"], SERIES)
    if not n:
        return None
    return 100.0 * total(ctx["after"], SERIES, how="batch") / n
