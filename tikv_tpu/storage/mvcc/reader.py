"""MVCC read path: point gets and range scanners over a snapshot.

Re-expression of the reference's ``src/storage/mvcc/reader/{reader,
point_getter.rs:136, scanner/forward.rs:114, scanner/backward.rs:28}``.

Semantics (Percolator/SI):

* A read at ``ts`` must first consult CF_LOCK — a PUT/DELETE lock from a txn
  with ``lock.ts <= ts`` blocks the read (the writing txn may commit below our
  read ts) unless bypassed or pushed via ``min_commit_ts``.
* The visible version is the newest CF_WRITE entry with ``commit_ts <= ts``,
  skipping LOCK/ROLLBACK records; PUT yields a value (inline short value or
  CF_DEFAULT at ``start_ts``), DELETE yields nothing.
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from ...util import codec
from ..engine import CF_DEFAULT, CF_LOCK, CF_WRITE, Cursor, Snapshot
from ..txn_types import MAX_TS, Key, Lock, Write, WriteType, append_ts, split_ts


class IsolationLevel(enum.Enum):
    SI = "si"
    RC = "rc"


class KeyIsLockedError(Exception):
    def __init__(self, key: bytes, lock: Lock):
        self.key = key
        self.lock = lock
        super().__init__(f"key {key!r} is locked by txn {lock.ts} (primary {lock.primary!r})")


class WriteConflictError(Exception):
    def __init__(self, key: bytes, start_ts: int, conflict_start_ts: int, conflict_commit_ts: int):
        self.key = key
        self.start_ts = start_ts
        self.conflict_start_ts = conflict_start_ts
        self.conflict_commit_ts = conflict_commit_ts
        super().__init__(
            f"write conflict on {key!r}: txn {start_ts} vs committed "
            f"[{conflict_start_ts}, {conflict_commit_ts}]"
        )


@dataclass
class CfStatistics:
    get: int = 0
    next: int = 0
    prev: int = 0
    seek: int = 0
    seek_for_prev: int = 0
    processed_keys: int = 0

    def add(self, other: "CfStatistics") -> None:
        self.get += other.get
        self.next += other.next
        self.prev += other.prev
        self.seek += other.seek
        self.seek_for_prev += other.seek_for_prev
        self.processed_keys += other.processed_keys


@dataclass
class Statistics:
    """Per-CF cursor operation counts (tikv_kv/src/stats.rs)."""

    lock: CfStatistics = field(default_factory=CfStatistics)
    write: CfStatistics = field(default_factory=CfStatistics)
    data: CfStatistics = field(default_factory=CfStatistics)

    def add(self, other: "Statistics") -> None:
        self.lock.add(other.lock)
        self.write.add(other.write)
        self.data.add(other.data)

    def total_ops(self) -> int:
        return sum(
            s.get + s.next + s.prev + s.seek + s.seek_for_prev
            for s in (self.lock, self.write, self.data)
        )


# the largest possible ts suffix: appending desc(0) sorts after every real version
_LAST_VERSION_SUFFIX = codec.encode_u64_desc(0)


def _check_lock(
    lock_bytes: bytes,
    key_raw: bytes,
    ts: int,
    bypass_locks: frozenset[int],
) -> int:
    """Raise KeyIsLockedError if the lock blocks a read at ``ts``.

    Returns the ts to actually read at (committing-lock reads see through at
    the same ts; mirrors Lock::check_ts_conflict lock.rs:192).
    """
    lock = Lock.from_bytes(lock_bytes)
    if not lock.is_visible_to(ts, bypass_locks):
        raise KeyIsLockedError(key_raw, lock)
    return ts


class MvccReader:
    """Low-level MVCC access over a snapshot (reader.rs:90)."""

    def __init__(self, snapshot: Snapshot, statistics: Statistics | None = None):
        self.snap = snapshot
        self.stats = statistics or Statistics()

    # -- locks ------------------------------------------------------------

    def load_lock(self, key: Key) -> Lock | None:
        self.stats.lock.get += 1
        raw = self.snap.get_cf(CF_LOCK, key.encoded)
        return Lock.from_bytes(raw) if raw is not None else None

    def load_locks(self, keys: list[Key]) -> list[Lock | None]:
        """``load_lock`` of every key, in one read of the snapshot."""
        self.stats.lock.get += len(keys)
        raws = self.snap.multi_get_cf(CF_LOCK, [k.encoded for k in keys])
        return [Lock.from_bytes(r) if r is not None else None for r in raws]

    def scan_locks(
        self,
        start: Key | None,
        end: Key | None,
        predicate=None,
        limit: int | None = None,
    ) -> list[tuple[Key, Lock]]:
        out: list[tuple[Key, Lock]] = []
        start_enc = start.encoded if start else b""
        end_enc = end.encoded if end else None
        for k, v in self.snap.scan_cf(CF_LOCK, start_enc, end_enc):
            self.stats.lock.next += 1
            lock = Lock.from_bytes(v)
            if predicate is None or predicate(lock):
                out.append((Key.from_encoded(k), lock))
                if limit is not None and len(out) >= limit:
                    break
        return out

    # -- write records ----------------------------------------------------

    def seek_write(self, key: Key, ts: int) -> tuple[int, Write] | None:
        """Newest write with commit_ts <= ts for exactly this key."""
        cur = self.snap.cursor_cf(CF_WRITE)
        self.stats.write.seek += 1
        if not cur.seek(append_ts(key.encoded, ts)):
            return None
        user_key, commit_ts = split_ts(cur.key())
        if user_key != key.encoded:
            return None
        return commit_ts, Write.from_bytes(cur.value())

    def seek_writes(self, keys: list[Key], ts: int) -> list[tuple[int, Write] | None]:
        """``seek_write(key, ts)`` of every key, in one read of the snapshot."""
        self.stats.write.seek += len(keys)
        found = self.snap.newest_versions_cf(CF_WRITE, [k.encoded for k in keys], ts)
        return [None if f is None else (split_ts(f[0])[1], Write.from_bytes(f[1]))
                for f in found]

    def get_txn_commit_record(self, key: Key, start_ts: int) -> list[tuple[int, Write]]:
        """All writes of txn ``start_ts`` on ``key`` (commit/rollback search)."""
        out = []
        cur = self.snap.cursor_cf(CF_WRITE)
        self.stats.write.seek += 1
        ok = cur.seek(append_ts(key.encoded, MAX_TS))
        while ok:
            user_key, commit_ts = split_ts(cur.key())
            if user_key != key.encoded:
                break
            w = Write.from_bytes(cur.value())
            if w.start_ts == start_ts:
                out.append((commit_ts, w))
            if commit_ts < start_ts and w.start_ts < start_ts:
                # writes are commit_ts-descending; nothing older can belong to us
                break
            self.stats.write.next += 1
            ok = cur.next()
        return out

    # -- values -----------------------------------------------------------

    def load_data(self, key: Key, write: Write) -> bytes:
        if write.short_value is not None:
            return write.short_value
        self.stats.data.get += 1
        v = self.snap.get_cf(CF_DEFAULT, append_ts(key.encoded, write.start_ts))
        if v is None:
            raise ValueError(f"default value missing for {key!r} @ {write.start_ts}")
        return v

    def get(
        self,
        key: Key,
        ts: int,
        isolation: IsolationLevel = IsolationLevel.SI,
        bypass_locks: frozenset[int] = frozenset(),
    ) -> bytes | None:
        return PointGetter(self.snap, ts, isolation, bypass_locks, self.stats).get(key)


class PointGetter:
    """Single-key visible-version lookup (point_getter.rs:136)."""

    def __init__(
        self,
        snapshot: Snapshot,
        ts: int,
        isolation: IsolationLevel = IsolationLevel.SI,
        bypass_locks: frozenset[int] = frozenset(),
        statistics: Statistics | None = None,
    ):
        self.snap = snapshot
        self.ts = ts
        self.isolation = isolation
        self.bypass_locks = bypass_locks
        self.stats = statistics or Statistics()

    def get(self, key: Key) -> bytes | None:
        if self.isolation == IsolationLevel.SI:
            self.stats.lock.get += 1
            lock_bytes = self.snap.get_cf(CF_LOCK, key.encoded)
            if lock_bytes is not None:
                _check_lock(lock_bytes, key.to_raw(), self.ts, self.bypass_locks)

        cur = self.snap.cursor_cf(CF_WRITE)
        self.stats.write.seek += 1
        ok = cur.seek(append_ts(key.encoded, self.ts))
        while ok:
            user_key, commit_ts = split_ts(cur.key())
            if user_key != key.encoded:
                return None
            write = Write.from_bytes(cur.value())
            if write.write_type == WriteType.PUT:
                self.stats.write.processed_keys += 1
                if write.short_value is not None:
                    return write.short_value
                self.stats.data.get += 1
                v = self.snap.get_cf(CF_DEFAULT, append_ts(key.encoded, write.start_ts))
                if v is None:
                    raise ValueError(f"default value missing for {key!r} @ {write.start_ts}")
                return v
            if write.write_type == WriteType.DELETE:
                return None
            # LOCK / ROLLBACK: look at the next (older) version
            self.stats.write.next += 1
            ok = cur.next()
        return None


class _ScannerBase:
    def __init__(
        self,
        snapshot: Snapshot,
        ts: int,
        start: Key | None,
        end: Key | None,
        isolation: IsolationLevel = IsolationLevel.SI,
        bypass_locks: frozenset[int] = frozenset(),
        key_only: bool = False,
        statistics: Statistics | None = None,
    ):
        self.snap = snapshot
        self.ts = ts
        self.start = start.encoded if start else b""
        self.end = end.encoded if end else None
        self.isolation = isolation
        self.bypass_locks = bypass_locks
        self.key_only = key_only
        self.stats = statistics or Statistics()

    def _check_range_locks(self) -> None:
        """Every lock in the scanned range must permit a read at ``ts`` —
        including locks on keys with no CF_WRITE entries yet (a prewritten
        brand-new key MUST block the scan, same as PointGetter; the reference
        walks a parallel lock cursor in forward.rs for exactly this)."""
        if self.isolation != IsolationLevel.SI:
            return
        for k, v in self.snap.scan_cf(CF_LOCK, self.start, self.end):
            self.stats.lock.next += 1
            _check_lock(v, Key.from_encoded(k).to_raw(), self.ts, self.bypass_locks)

    def _resolve_version(self, cur: Cursor, user_key: bytes) -> bytes | None:
        """From a cursor positioned at the newest candidate version of
        ``user_key`` with commit_ts <= ts, find the visible value."""
        ok = True
        while ok:
            k, _ = split_ts(cur.key())
            if k != user_key:
                return None
            write = Write.from_bytes(cur.value())
            if write.write_type == WriteType.PUT:
                self.stats.write.processed_keys += 1
                if self.key_only:
                    return b""
                if write.short_value is not None:
                    return write.short_value
                self.stats.data.get += 1
                v = self.snap.get_cf(CF_DEFAULT, append_ts(user_key, write.start_ts))
                if v is None:
                    raise ValueError(f"default value missing for {user_key!r}")
                return v
            if write.write_type == WriteType.DELETE:
                return None
            self.stats.write.next += 1
            ok = cur.next()
        return None


class ForwardScanner(_ScannerBase):
    """Ascending scan emitting (raw_key, value) of visible versions
    (scanner/forward.rs:114, latest-KV policy)."""

    def __iter__(self):
        self._check_range_locks()
        cur = self.snap.cursor_cf(CF_WRITE, upper=self.end)
        self.stats.write.seek += 1
        ok = cur.seek(self.start)
        while ok:
            user_key, commit_ts = split_ts(cur.key())
            if self.end is not None and user_key >= self.end:
                return
            if commit_ts > self.ts:
                # newer than the read point: hop to (user_key, ts)
                self.stats.write.seek += 1
                ok = cur.seek(append_ts(user_key, self.ts))
                if ok:
                    k2, _ = split_ts(cur.key())
                    if k2 == user_key:
                        value = self._resolve_version(cur, user_key)
                        if value is not None:
                            yield Key.from_encoded(user_key).to_raw(), value
                ok = self._skip_to_next_key(cur, user_key)
                continue
            value = self._resolve_version(cur, user_key)
            if value is not None:
                yield Key.from_encoded(user_key).to_raw(), value
            ok = self._skip_to_next_key(cur, user_key)

    def _skip_to_next_key(self, cur: Cursor, user_key: bytes) -> bool:
        self.stats.write.seek += 1
        ok = cur.seek(user_key + _LAST_VERSION_SUFFIX)
        while ok:
            k, _ = split_ts(cur.key())
            if k != user_key:
                return True
            self.stats.write.next += 1
            ok = cur.next()
        return False


class BackwardScanner(_ScannerBase):
    """Descending scan in (start, end] reversed order (scanner/backward.rs:28)."""

    def __iter__(self):
        self._check_range_locks()
        cur = self.snap.cursor_cf(CF_WRITE)
        # position at the last entry below `end`
        if self.end is not None:
            self.stats.write.seek_for_prev += 1
            ok = cur.seek_for_prev(self.end)
            if ok and cur.key() >= self.end:
                ok = cur.prev()
        else:
            self.stats.write.seek_for_prev += 1
            ok = cur.seek_to_last()
        while ok:
            user_key, _ = split_ts(cur.key())
            if user_key < self.start:
                return
            # move to the newest version <= ts of this key
            self.stats.write.seek += 1
            if cur.seek(append_ts(user_key, self.ts)):
                k2, _ = split_ts(cur.key())
                if k2 == user_key:
                    value = self._resolve_version(cur, user_key)
                    if value is not None:
                        yield Key.from_encoded(user_key).to_raw(), value
            # hop to just before the first version of this key
            self.stats.write.seek_for_prev += 1
            ok = cur.seek_for_prev(user_key)
            if ok and split_ts(cur.key())[0] >= user_key:
                # seek_for_prev landed on a version of user_key (its suffix
                # sorts above the bare key) — walk below it
                while ok and split_ts(cur.key())[0] >= user_key:
                    self.stats.write.prev += 1
                    ok = cur.prev()
