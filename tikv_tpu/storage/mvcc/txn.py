"""MVCC write transaction + Percolator actions.

Re-expression of ``src/storage/mvcc/txn.rs:38`` (``MvccTxn``: a buffer of CF
mutations produced by one command) and the reusable actions in
``src/storage/txn/actions/{prewrite,commit,acquire_pessimistic_lock,
check_txn_status,cleanup,gc}.rs``.

Percolator rules enforced here:

* prewrite: write-conflict check (any commit > start_ts), constraint checks
  (Insert/CheckNotExists), lock the key for start_ts with the primary
  recorded; pessimistic prewrite validates the existing pessimistic lock
* commit: the lock at start_ts becomes a Write record at commit_ts
* rollback: remove the lock, write a Rollback marker (protected if needed)
* check_txn_status: TTL expiry / min_commit_ts pushing for the primary
* resolve: commit or roll back secondaries according to the primary's fate
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from ..engine import CF_DEFAULT, CF_LOCK, CF_WRITE, Snapshot, WriteBatch
from ..txn_types import (
    Key,
    Lock,
    LockType,
    MAX_TS,
    Mutation,
    SHORT_VALUE_MAX_LEN,
    Write,
    WriteType,
)
from .reader import KeyIsLockedError, MvccReader, WriteConflictError


class TxnError(Exception):
    pass


class AlreadyExistsError(TxnError):
    def __init__(self, key: bytes):
        self.key = key
        super().__init__(f"key {key!r} already exists")


class TxnLockNotFoundError(TxnError):
    def __init__(self, key: Key, start_ts: int):
        self.key = key
        self.start_ts = start_ts
        super().__init__(f"lock not found for {key!r} at {start_ts}")


class CommitTsExpiredError(TxnError):
    pass


class PessimisticLockNotFoundError(TxnError):
    pass


class MvccTxn:
    """A buffer of CF mutations for one command at one start_ts (txn.rs:38)."""

    def __init__(self, start_ts: int):
        self.start_ts = start_ts
        self.wb = WriteBatch()
        self.locks_put: list[Key] = []
        self.locks_deleted: list[Key] = []

    def put_lock(self, key: Key, lock: Lock) -> None:
        self.wb.put_cf(CF_LOCK, key.encoded, lock.to_bytes())
        self.locks_put.append(key)

    def unlock_key(self, key: Key) -> None:
        self.wb.delete_cf(CF_LOCK, key.encoded)
        self.locks_deleted.append(key)

    def put_value(self, key: Key, ts: int, value: bytes) -> None:
        self.wb.put_cf(CF_DEFAULT, key.append_ts(ts).encoded, value)

    def delete_value(self, key: Key, ts: int) -> None:
        self.wb.delete_cf(CF_DEFAULT, key.append_ts(ts).encoded)

    def put_write(self, key: Key, commit_ts: int, write: Write) -> None:
        self.wb.put_cf(CF_WRITE, key.append_ts(commit_ts).encoded, write.to_bytes())

    def delete_write(self, key: Key, commit_ts: int) -> None:
        self.wb.delete_cf(CF_WRITE, key.append_ts(commit_ts).encoded)

    def is_empty(self) -> bool:
        return self.wb.is_empty()


# ---------------------------------------------------------------------------
# prewrite (actions/prewrite.rs:21)
# ---------------------------------------------------------------------------

@dataclass
class PrewriteContext:
    primary: bytes
    start_ts: int
    lock_ttl: int = 3000
    txn_size: int = 0
    min_commit_ts: int = 0
    use_async_commit: bool = False
    secondaries: list[bytes] = field(default_factory=list)
    is_pessimistic: bool = False


def prewrite_key(
    txn: MvccTxn,
    reader: MvccReader,
    mutation: Mutation,
    ctx: PrewriteContext,
    is_pessimistic_lock: bool = False,
    *,
    lock: Lock | None,
    newest: tuple[int, Write] | None,
) -> int:
    """Prewrite one mutation. Returns min_commit_ts for async commit (0 else).

    ``is_pessimistic_lock``: this key was locked by AcquirePessimisticLock
    earlier in the same txn (pessimistic prewrite path).  ``lock`` and
    ``newest``: the key's ``load_lock`` and ``seek_write(key, MAX_TS)``, which
    the command reads for all its keys at once.
    """
    key = mutation.key
    if lock is not None:
        if lock.ts != ctx.start_ts:
            if ctx.is_pessimistic and is_pessimistic_lock:
                raise PessimisticLockNotFoundError(f"pessimistic lock lost on {key!r}")
            raise KeyIsLockedError(key.to_raw(), lock)
        if lock.lock_type != LockType.PESSIMISTIC:
            # duplicate prewrite: idempotent, keep existing
            return lock.min_commit_ts
        # pessimistic lock exists: will be upgraded below
    elif ctx.is_pessimistic and is_pessimistic_lock:
        raise PessimisticLockNotFoundError(f"pessimistic lock missing on {key!r}")

    skip_conflict_check = ctx.is_pessimistic and is_pessimistic_lock
    if not skip_conflict_check and newest is not None:
        commit_ts, write = newest
        if commit_ts >= ctx.start_ts:
            # a commit above us: write conflict (optimistic) — except a
            # rollback of our own ts, which means we were rolled back
            raise WriteConflictError(key.to_raw(), ctx.start_ts, write.start_ts, commit_ts)
    if mutation.should_not_exists():
        _check_not_exists(reader, key, newest)
    if skip_conflict_check:
        # our own rollback marker ⇒ the txn has been rolled back already
        # (past the conflict check there is none to find: every record is
        # older than start_ts, and this txn's commit or rollback is not;
        # prewrite.rs decides both from the one seek)
        for commit_ts, write in reader.get_txn_commit_record(key, ctx.start_ts):
            if write.write_type == WriteType.ROLLBACK:
                raise WriteConflictError(key.to_raw(), ctx.start_ts, ctx.start_ts, commit_ts)

    if mutation.mutation_type.value == "check_not_exists":
        return 0

    lock = Lock(
        mutation.lock_type(),
        ctx.primary,
        ctx.start_ts,
        ttl=ctx.lock_ttl,
        txn_size=ctx.txn_size,
        min_commit_ts=ctx.min_commit_ts,
        use_async_commit=ctx.use_async_commit,
        secondaries=list(ctx.secondaries) if key.to_raw() == ctx.primary else [],
    )
    value = mutation.value
    if value is not None:
        if len(value) <= SHORT_VALUE_MAX_LEN:
            lock.short_value = value
        else:
            txn.put_value(key, ctx.start_ts, value)
    min_commit_ts = 0
    if ctx.use_async_commit:
        min_commit_ts = max(ctx.min_commit_ts, ctx.start_ts + 1)
        lock.min_commit_ts = min_commit_ts
    txn.put_lock(key, lock)
    return min_commit_ts


def _check_not_exists(reader: MvccReader, key: Key, rec: tuple[int, Write] | None) -> None:
    """AlreadyExists where the key's newest PUT or DELETE, walking down from
    its newest record ``rec``, is a PUT."""
    while rec is not None:
        commit_ts, write = rec
        if write.write_type == WriteType.PUT:
            raise AlreadyExistsError(key.to_raw())
        if write.write_type == WriteType.DELETE:
            return
        rec = reader.seek_write(key, commit_ts - 1)


# ---------------------------------------------------------------------------
# acquire pessimistic lock (actions/acquire_pessimistic_lock.rs)
# ---------------------------------------------------------------------------

def acquire_pessimistic_lock(
    txn: MvccTxn,
    reader: MvccReader,
    key: Key,
    primary: bytes,
    start_ts: int,
    for_update_ts: int,
    ttl: int = 3000,
    should_not_exist: bool = False,
) -> bytes | None:
    """Lock a key for a pessimistic txn; returns the current value if any."""
    lock = reader.load_lock(key)
    if lock is not None:
        if lock.ts != start_ts:
            raise KeyIsLockedError(key.to_raw(), lock)
        # already locked by us: refresh for_update_ts if newer
        if for_update_ts > lock.for_update_ts:
            lock.for_update_ts = for_update_ts
            txn.put_lock(key, lock)
        return None
    rec = reader.seek_write(key, MAX_TS)
    value = None
    if rec is not None:
        commit_ts, write = rec
        if commit_ts > for_update_ts:
            raise WriteConflictError(key.to_raw(), start_ts, write.start_ts, commit_ts)
        # rollback of our own start_ts means we were rolled back
        for cts, w in reader.get_txn_commit_record(key, start_ts):
            if w.write_type == WriteType.ROLLBACK:
                raise WriteConflictError(key.to_raw(), start_ts, start_ts, cts)
        # LOCK/ROLLBACK markers hide the live version — walk to the newest
        # PUT/DELETE (same loop as _check_not_exists)
        while rec is not None and rec[1].write_type not in (WriteType.PUT, WriteType.DELETE):
            rec = reader.seek_write(key, rec[0] - 1)
        if rec is not None and rec[1].write_type == WriteType.PUT:
            value = reader.load_data(key, rec[1])
            if should_not_exist:
                raise AlreadyExistsError(key.to_raw())
    lock = Lock(LockType.PESSIMISTIC, primary, start_ts, ttl=ttl, for_update_ts=for_update_ts)
    txn.put_lock(key, lock)
    return value


# ---------------------------------------------------------------------------
# commit (actions/commit.rs)
# ---------------------------------------------------------------------------

# a read the caller did not make for the action: the action makes it
_READ = object()


def commit_key(txn: MvccTxn, reader: MvccReader, key: Key, start_ts: int,
               commit_ts: int, lock=_READ) -> Lock | None:
    """``lock``: the key's ``load_lock`` where the caller read it for all its
    keys at once."""
    if lock is _READ:
        lock = reader.load_lock(key)
    if lock is None or lock.ts != start_ts:
        # committed already? look for the write record
        for cts, w in reader.get_txn_commit_record(key, start_ts):
            if w.write_type != WriteType.ROLLBACK:
                return None  # idempotent re-commit
        raise TxnLockNotFoundError(key, start_ts)
    if lock.lock_type == LockType.PESSIMISTIC:
        # commit of a pessimistic lock without prewrite: roll it back to a
        # LOCK-type record (commit.rs handles this as lock-type fallthrough)
        lock.lock_type = LockType.LOCK
    if commit_ts < lock.min_commit_ts:
        raise CommitTsExpiredError(
            f"commit_ts {commit_ts} < min_commit_ts {lock.min_commit_ts} for {key!r}"
        )
    wt = {
        LockType.PUT: WriteType.PUT,
        LockType.DELETE: WriteType.DELETE,
        LockType.LOCK: WriteType.LOCK,
    }[lock.lock_type]
    write = Write(wt, start_ts, short_value=lock.short_value)
    txn.put_write(key, commit_ts, write)
    txn.unlock_key(key)
    return lock


# ---------------------------------------------------------------------------
# cleanup / rollback (actions/cleanup.rs, check_txn_status.rs)
# ---------------------------------------------------------------------------

def rollback_key(
    txn: MvccTxn, reader: MvccReader, key: Key, start_ts: int, protect: bool = False
) -> None:
    lock = reader.load_lock(key)
    if lock is not None and lock.ts == start_ts:
        if lock.short_value is None and lock.lock_type == LockType.PUT:
            txn.delete_value(key, start_ts)
        txn.unlock_key(key)
        txn.put_write(key, start_ts, Write.new_rollback(start_ts, protect))
        return
    # no lock: check commit record
    for commit_ts, w in reader.get_txn_commit_record(key, start_ts):
        if w.write_type == WriteType.ROLLBACK:
            return  # already rolled back
        raise TxnError(f"txn {start_ts} already committed at {commit_ts} on {key!r}")
    # neither lock nor record: leave a protected rollback tombstone
    txn.put_write(key, start_ts, Write.new_rollback(start_ts, protect))


class TxnStatusKind(enum.Enum):
    COMMITTED = "committed"
    ROLLED_BACK = "rolled_back"
    LOCKED = "locked"
    TTL_EXPIRED = "ttl_expired"
    MIN_COMMIT_PUSHED = "min_commit_pushed"
    NOT_FOUND = "not_found"


@dataclass
class TxnStatus:
    kind: TxnStatusKind
    commit_ts: int = 0
    lock_ttl: int = 0
    min_commit_ts: int = 0
    # Set on LOCKED results for async-commit locks so the client knows to
    # resolve via check_secondary_locks / force_sync_commit instead of
    # retrying check_txn_status forever (the reference returns the full
    # LockInfo in TxnStatus::uncommitted for this purpose).
    use_async_commit: bool = False


def check_txn_status(
    txn: MvccTxn,
    reader: MvccReader,
    primary_key: Key,
    lock_ts: int,
    caller_start_ts: int,
    current_ts: int,
    rollback_if_not_exist: bool = False,
    now_ms: int | None = None,
    force_sync_commit: bool = False,
) -> TxnStatus:
    """Primary-key liveness check (actions/check_txn_status.rs).

    Async-commit locks are never rolled back or pushed here, regardless of
    TTL: the transaction may already be decided committed through its
    secondaries, so resolution is CheckSecondaryLocks/ResolveLock's job
    (actions/check_txn_status.rs:26 returns uncommitted for
    use_async_commit locks unless the client set force_sync_commit).
    """
    from ..txn_types import ts_physical

    lock = reader.load_lock(primary_key)
    if lock is not None and lock.ts == lock_ts:
        if lock.use_async_commit and not force_sync_commit:
            return TxnStatus(
                TxnStatusKind.LOCKED, lock_ttl=lock.ttl,
                min_commit_ts=lock.min_commit_ts, use_async_commit=True,
            )
        lock_elapsed = ts_physical(current_ts) - ts_physical(lock_ts)
        if lock_elapsed >= lock.ttl:
            rollback_key(txn, reader, primary_key, lock_ts, protect=True)
            return TxnStatus(TxnStatusKind.TTL_EXPIRED)
        # push min_commit_ts so readers above caller_start_ts can proceed
        if caller_start_ts >= lock.min_commit_ts:
            lock.min_commit_ts = caller_start_ts + 1
            txn.put_lock(primary_key, lock)
            return TxnStatus(
                TxnStatusKind.MIN_COMMIT_PUSHED, lock_ttl=lock.ttl, min_commit_ts=lock.min_commit_ts
            )
        return TxnStatus(TxnStatusKind.LOCKED, lock_ttl=lock.ttl, min_commit_ts=lock.min_commit_ts)
    for commit_ts, w in reader.get_txn_commit_record(primary_key, lock_ts):
        if w.write_type == WriteType.ROLLBACK:
            return TxnStatus(TxnStatusKind.ROLLED_BACK)
        return TxnStatus(TxnStatusKind.COMMITTED, commit_ts=commit_ts)
    if rollback_if_not_exist:
        rollback_key(txn, reader, primary_key, lock_ts, protect=True)
        return TxnStatus(TxnStatusKind.ROLLED_BACK)
    return TxnStatus(TxnStatusKind.NOT_FOUND)
