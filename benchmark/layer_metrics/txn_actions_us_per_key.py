"""write path: the wall time of prewrite's and commit's ``process_write``
(``tikv_storage_txn_actions_seconds_total``, ``tikv_tpu/storage/txn/
commands.py``) over the keys through them (``..._actions_keys_total``),
both commands together, in microseconds, since the store started: the load
lies in the set-up.  It is the part of a loaded row's serial work that reads
the snapshot and builds the write batch; the raft proposal after it is not
in it.  A program without the counters gives None."""

from benchmark.counters import total

SECONDS = "tikv_storage_txn_actions_seconds_total"
KEYS = "tikv_storage_txn_actions_keys_total"


def read(ctx):
    n = total(ctx["after"], KEYS)
    if not n:
        return None
    return 1e6 * total(ctx["after"], SECONDS) / n
