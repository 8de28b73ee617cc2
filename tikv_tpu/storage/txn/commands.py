"""Transaction commands — one class per scheduler command.

Re-expression of ``src/storage/txn/commands/`` (one file per command there:
prewrite, commit, acquire_pessimistic_lock, check_txn_status,
check_secondary_locks, cleanup, rollback, pessimistic_rollback, resolve_lock,
txn_heart_beat, mvcc_by_key/start_ts, compare_and_swap, atomic_store).

Each command declares the keys it must latch and a ``process_write(snapshot)``
producing (WriteBatch, result) — executed by the Scheduler under latches.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from ...util.metrics import REGISTRY
from ..engine import Snapshot
from ..mvcc.reader import IsolationLevel, KeyIsLockedError, MvccReader, WriteConflictError
from ..mvcc.txn import (
    MvccTxn,
    PrewriteContext,
    TxnError,
    TxnStatus,
    TxnStatusKind,
    acquire_pessimistic_lock,
    check_txn_status,
    commit_key,
    prewrite_key,
    rollback_key,
)
from ..txn_types import MAX_TS, Key, Lock, Mutation, WriteType

_ACTIONS_SECONDS = REGISTRY.counter(
    "tikv_storage_txn_actions_seconds_total",
    "Wall time of prewrite's and commit's process_write, by command")
_ACTIONS_KEYS = REGISTRY.counter(
    "tikv_storage_txn_actions_keys_total",
    "Keys through prewrite's and commit's process_write, by command")
_BATCHED_READ_KEYS = REGISTRY.counter(
    "tikv_storage_txn_batched_read_keys_total",
    "Keys of prewrite and commit by how their action read the engine: "
    "batch = its command's batched reads alone, walk = point reads besides")


class _ActionReads:
    """What a prewrite's or commit's keys cost: the wall time of the
    command's process_write, and how many of its keys needed point reads of
    their own beyond the command's batched ones (a key whose action moved
    the reader's lock gets or write seeks)."""

    def __init__(self, cmd: str, reader: MvccReader):
        self.cmd = cmd
        self.stats = reader.stats
        self.t0 = time.perf_counter()
        self.keys = self.walked = self.seen = 0

    def _reads(self) -> int:
        return self.stats.lock.get + self.stats.write.seek

    def next_key(self) -> None:
        """Called before each key's action, after the batched reads: the
        point reads since the last call were the previous key's."""
        seen = self._reads()
        if self.keys and seen != self.seen:
            self.walked += 1
        self.seen = seen
        self.keys += 1

    def done(self) -> None:
        if self.keys and self._reads() != self.seen:
            self.walked += 1
        _ACTIONS_SECONDS.inc(time.perf_counter() - self.t0, cmd=self.cmd)
        _ACTIONS_KEYS.inc(self.keys, cmd=self.cmd)
        _BATCHED_READ_KEYS.inc(self.keys - self.walked, cmd=self.cmd, how="batch")
        _BATCHED_READ_KEYS.inc(self.walked, cmd=self.cmd, how="walk")


class Command:
    # group commit eligibility (scheduler._collect_group_locked): a
    # groupable command reads/writes ONLY its own latched keys, so any set
    # of queued (latch-granted, hence key-disjoint) groupable commands
    # composes into one snapshot + one engine WriteBatch with effects
    # identical to back-to-back execution.  Range/scan commands
    # (ResolveLock-without-keys, Flashback) must stay non-groupable.
    groupable = False

    def latch_keys(self) -> list[bytes]:
        raise NotImplementedError

    def process_write(self, snapshot: Snapshot):
        """Returns (MvccTxn, result)."""
        raise NotImplementedError


@dataclass
class Prewrite(Command):
    mutations: list[Mutation]
    primary: bytes
    start_ts: int
    lock_ttl: int = 3000
    txn_size: int = 0
    min_commit_ts: int = 0
    use_async_commit: bool = False
    secondaries: list[bytes] = field(default_factory=list)
    # pessimistic variant: per-mutation flags, aligned with mutations
    is_pessimistic: bool = False
    pessimistic_flags: list[bool] = field(default_factory=list)
    for_update_ts: int = 0

    groupable = True  # touches only its latched keys (group commit)

    def latch_keys(self) -> list[bytes]:
        return [m.key.encoded for m in self.mutations]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        acts = _ActionReads("prewrite", reader)
        ctx = PrewriteContext(
            primary=self.primary,
            start_ts=self.start_ts,
            lock_ttl=self.lock_ttl,
            txn_size=self.txn_size,
            min_commit_ts=self.min_commit_ts,
            use_async_commit=self.use_async_commit,
            secondaries=self.secondaries,
            is_pessimistic=self.is_pessimistic,
        )
        min_commit_ts = 0
        errors: list[Exception] = []
        keys = [m.key for m in self.mutations]
        try:
            locks = reader.load_locks(keys)
            newest = reader.seek_writes(keys, MAX_TS)
            for i, m in enumerate(self.mutations):
                flag = self.pessimistic_flags[i] if i < len(self.pessimistic_flags) else False
                acts.next_key()
                try:
                    ts = prewrite_key(txn, reader, m, ctx, is_pessimistic_lock=flag,
                                      lock=locks[i], newest=newest[i])
                    min_commit_ts = max(min_commit_ts, ts)
                except (KeyIsLockedError, WriteConflictError, TxnError) as e:
                    errors.append(e)
        finally:
            acts.done()
        if errors:
            # keys that prewrote fine stay locked (the reference persists the
            # successful locks alongside the KeyError vec; the client retries
            # or resolves them) — so the txn buffer is NOT discarded
            return txn, {"errors": errors, "min_commit_ts": min_commit_ts}
        return txn, {"min_commit_ts": min_commit_ts}


@dataclass
class Commit(Command):
    keys: list[Key]
    start_ts: int
    commit_ts: int

    groupable = True  # touches only its latched keys (group commit)

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k in self.keys]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        acts = _ActionReads("commit", reader)
        try:
            locks = reader.load_locks(self.keys)
            for k, lock in zip(self.keys, locks):
                acts.next_key()
                commit_key(txn, reader, k, self.start_ts, self.commit_ts, lock=lock)
        finally:
            acts.done()
        return txn, {"commit_ts": self.commit_ts}


@dataclass
class Rollback(Command):
    keys: list[Key]
    start_ts: int

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k in self.keys]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        for k in self.keys:
            rollback_key(txn, reader, k, self.start_ts)
        return txn, {}


@dataclass
class Cleanup(Command):
    """Rollback the primary if its TTL expired (or unconditionally when
    current_ts == 0) — commands/cleanup.rs.

    Deliberately rolls back async-commit locks too, matching the reference
    (actions/cleanup.rs calls rollback_lock with no use_async_commit check):
    Cleanup is the txn owner's own path, unlike CheckTxnStatus which other
    txns invoke and which must not roll back async-commit primaries."""

    key: Key
    start_ts: int
    current_ts: int

    def latch_keys(self) -> list[bytes]:
        return [self.key.encoded]

    def process_write(self, snapshot: Snapshot):
        from ..txn_types import ts_physical

        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        lock = reader.load_lock(self.key)
        if lock is not None and lock.ts == self.start_ts and self.current_ts:
            if ts_physical(self.current_ts) - ts_physical(self.start_ts) < lock.ttl:
                raise KeyIsLockedError(self.key.to_raw(), lock)
        rollback_key(txn, reader, self.key, self.start_ts, protect=True)
        return txn, {}


@dataclass
class AcquirePessimisticLock(Command):
    keys: list[tuple[Key, bool]]  # (key, should_not_exist)
    primary: bytes
    start_ts: int
    for_update_ts: int
    lock_ttl: int = 3000
    return_values: bool = False

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k, _ in self.keys]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        values = []
        for k, sne in self.keys:
            v = acquire_pessimistic_lock(
                txn, reader, k, self.primary, self.start_ts, self.for_update_ts,
                ttl=self.lock_ttl, should_not_exist=sne,
            )
            values.append(v)
        return txn, {"values": values if self.return_values else None}


@dataclass
class PessimisticRollback(Command):
    keys: list[Key]
    start_ts: int
    for_update_ts: int

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k in self.keys]

    def process_write(self, snapshot: Snapshot):
        from ..txn_types import LockType

        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        for k in self.keys:
            lock = reader.load_lock(k)
            if (
                lock is not None
                and lock.lock_type == LockType.PESSIMISTIC
                and lock.ts == self.start_ts
                and lock.for_update_ts <= self.for_update_ts
            ):
                txn.unlock_key(k)
        return txn, {}


@dataclass
class TxnHeartBeat(Command):
    primary_key: Key
    start_ts: int
    advise_ttl: int

    def latch_keys(self) -> list[bytes]:
        return [self.primary_key.encoded]

    def process_write(self, snapshot: Snapshot):
        from ..mvcc.txn import TxnLockNotFoundError

        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        lock = reader.load_lock(self.primary_key)
        if lock is None or lock.ts != self.start_ts:
            raise TxnLockNotFoundError(self.primary_key, self.start_ts)
        if self.advise_ttl > lock.ttl:
            lock.ttl = self.advise_ttl
            txn.put_lock(self.primary_key, lock)
        return txn, {"lock_ttl": lock.ttl}


@dataclass
class CheckTxnStatus(Command):
    primary_key: Key
    lock_ts: int
    caller_start_ts: int
    current_ts: int
    rollback_if_not_exist: bool = False
    force_sync_commit: bool = False

    def latch_keys(self) -> list[bytes]:
        return [self.primary_key.encoded]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.lock_ts)
        reader = MvccReader(snapshot)
        status = check_txn_status(
            txn, reader, self.primary_key, self.lock_ts,
            self.caller_start_ts, self.current_ts, self.rollback_if_not_exist,
            force_sync_commit=self.force_sync_commit,
        )
        return txn, {"status": status}


@dataclass
class CheckSecondaryLocks(Command):
    """Async-commit: determine secondaries' fate (commands/check_secondary_locks.rs)."""

    keys: list[Key]
    start_ts: int

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k in self.keys]

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        locks: list[Lock] = []
        commit_ts = 0
        for k in self.keys:
            lock = reader.load_lock(k)
            if lock is not None and lock.ts == self.start_ts:
                if lock.lock_type.name == "PESSIMISTIC":
                    # pessimistic lock can't decide a commit: roll it back
                    rollback_key(txn, reader, k, self.start_ts, protect=True)
                else:
                    locks.append(lock)
                continue
            found = False
            for cts, w in reader.get_txn_commit_record(k, self.start_ts):
                found = True
                if w.write_type != WriteType.ROLLBACK:
                    commit_ts = max(commit_ts, cts)
            if not found:
                rollback_key(txn, reader, k, self.start_ts, protect=True)
                return txn, {"locks": [], "commit_ts": 0}
        return txn, {"locks": locks, "commit_ts": commit_ts}


@dataclass
class ResolveLock(Command):
    """Commit or roll back all keys of txn start_ts per the primary's fate
    (commands/resolve_lock.rs; the lite variant takes explicit keys)."""

    start_ts: int
    commit_ts: int  # 0 = roll back
    keys: list[Key] | None = None  # None = scan all locks of this txn

    def latch_keys(self) -> list[bytes]:
        return [k.encoded for k in self.keys] if self.keys else []

    def process_write(self, snapshot: Snapshot):
        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        keys = self.keys
        if keys is None:
            keys = [
                k for k, lock in reader.scan_locks(None, None, lambda l: l.ts == self.start_ts)
            ]
        for k in keys:
            if self.commit_ts:
                commit_key(txn, reader, k, self.start_ts, self.commit_ts)
            else:
                rollback_key(txn, reader, k, self.start_ts)
        return txn, {"resolved": len(keys)}


@dataclass
class FlashbackToVersion(Command):
    """Restore a key range to its state as of ``version``
    (commands/flashback_to_version.rs + flashback_to_version_read_phase.rs,
    folded into one command for the in-process scheduler): every key whose
    newest write landed after ``version`` gets a NEW record at ``commit_ts``
    reinstating the old value (or a DELETE if the key didn't exist) — MVCC
    history below ``commit_ts`` stays intact, so this is an append-only,
    replayable operation.  All locks in the range are cleared first, exactly
    like the reference's prepare phase."""

    version: int
    start_ts: int
    commit_ts: int
    start_key: Key | None = None
    end_key: Key | None = None

    # flashback's correctness depends on its snapshot being the write-time
    # state: take every latch slot (the reference serializes via an
    # exclusive prepare phase)
    exclusive = True

    def latch_keys(self) -> list[bytes]:
        return []

    def process_write(self, snapshot: Snapshot):
        from ..engine import CF_WRITE
        from ..txn_types import SHORT_VALUE_MAX_LEN, Write, split_ts

        txn = MvccTxn(self.start_ts)
        reader = MvccReader(snapshot)
        # 1. ROLL BACK every lock in range (flashback supersedes in-flight
        # txns): rollback_key also removes orphaned CF_DEFAULT prewrite
        # values and leaves a protected rollback marker so the superseded
        # txn cannot re-prewrite + commit after the flashback
        for k, lock in reader.scan_locks(self.start_key, self.end_key):
            rollback_key(txn, reader, k, lock.ts, protect=True)
        # 2. every user key with any write newer than `version` gets reset
        start_enc = self.start_key.encoded if self.start_key else b""
        end_enc = self.end_key.encoded if self.end_key else None
        changed = 0
        last_user: bytes | None = None
        for wkey, _wval in snapshot.scan_cf(CF_WRITE, start_enc, end_enc):
            user_enc, commit_ts = split_ts(wkey)
            if user_enc == last_user:
                continue  # CF_WRITE is newest-first per key
            last_user = user_enc
            if commit_ts >= self.commit_ts:
                # a write committed after our TSOs were fetched: the restore
                # record would be silently shadowed — fail loudly so the
                # client retries with fresh timestamps (the reference closes
                # this window with its blocking prepare phase)
                raise WriteConflictError(
                    Key.from_encoded(user_enc).to_raw(), self.start_ts, 0, commit_ts
                )
            if commit_ts <= self.version:
                continue  # newest write predates the flashback point: keep
            key = Key.from_encoded(user_enc)
            # RC isolation: in-range locks are being rolled back in this very
            # batch, so the snapshot's lock records must not abort the reads
            old_value = reader.get(key, self.version, isolation=IsolationLevel.RC)
            current = reader.get(key, self.start_ts, isolation=IsolationLevel.RC)
            if old_value == current:
                continue
            if old_value is None:
                txn.put_write(key, self.commit_ts, Write(WriteType.DELETE, self.start_ts))
            else:
                w = Write(WriteType.PUT, self.start_ts)
                if len(old_value) <= SHORT_VALUE_MAX_LEN:
                    w.short_value = old_value
                else:
                    txn.put_value(key, self.start_ts, old_value)
                txn.put_write(key, self.commit_ts, w)
            changed += 1
        return txn, {"flashback_keys": changed}
