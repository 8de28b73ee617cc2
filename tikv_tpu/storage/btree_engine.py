"""Ordered in-memory multi-CF engine with O(log n) seeks and cheap snapshots.

Plays the role of the reference's ``tikv_kv/src/btree_engine.rs`` (the in-memory
test engine) *and* stands in for RocksDB until the native C++ engine is wired
in.  Each CF is a sorted key list + value dict; snapshots freeze the current
state and the engine clones a CF's state lazily on the first write after a
snapshot (copy-on-write at CF granularity), so read-heavy workloads never copy.

``bulk_load`` ingests a pre-sorted batch without per-key list insertion — the
coprocessor benchmarks load millions of MVCC rows through it.
"""

from __future__ import annotations

import bisect
import threading
from typing import Iterator

from .engine import ALL_CFS, Cursor, KvEngine, Snapshot, WriteBatch


class _CfState:
    """Immutable-once-frozen sorted state of one column family."""

    __slots__ = ("keys", "vals", "frozen")

    def __init__(self, keys: list[bytes] | None = None, vals: dict[bytes, bytes] | None = None):
        self.keys: list[bytes] = keys if keys is not None else []
        self.vals: dict[bytes, bytes] = vals if vals is not None else {}
        self.frozen = False

    def clone(self) -> "_CfState":
        return _CfState(list(self.keys), dict(self.vals))


class _ListCursor(Cursor):
    __slots__ = ("_keys", "_vals", "_lo", "_hi", "_pos")

    def __init__(self, state: _CfState, lower: bytes | None, upper: bytes | None):
        self._keys = state.keys
        self._vals = state.vals
        self._lo = 0 if lower is None else bisect.bisect_left(self._keys, lower)
        self._hi = len(self._keys) if upper is None else bisect.bisect_left(self._keys, upper)
        self._pos = -1

    def seek(self, key: bytes) -> bool:
        self._pos = max(bisect.bisect_left(self._keys, key), self._lo)
        return self.valid()

    def seek_for_prev(self, key: bytes) -> bool:
        self._pos = min(bisect.bisect_right(self._keys, key), self._hi) - 1
        return self.valid()

    def seek_to_first(self) -> bool:
        self._pos = self._lo
        return self.valid()

    def seek_to_last(self) -> bool:
        self._pos = self._hi - 1
        return self.valid()

    def next(self) -> bool:
        self._pos += 1
        return self.valid()

    def prev(self) -> bool:
        self._pos -= 1
        return self.valid()

    def valid(self) -> bool:
        return self._lo <= self._pos < self._hi

    def key(self) -> bytes:
        return self._keys[self._pos]

    def value(self) -> bytes:
        return self._vals[self._keys[self._pos]]


class BTreeSnapshot(Snapshot):
    __slots__ = ("_states", "_seq", "_engine")

    def __init__(self, states: dict[str, _CfState], seq: int, engine: "BTreeEngine"):
        self._states = states
        self._seq = seq
        self._engine = engine

    def sequence(self) -> int:
        return self._seq

    def cf_touched_seq(self, cf: str) -> int:
        return self._engine.cf_touched_seq(cf)

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        return self._states[cf].vals.get(key)

    def cursor_cf(self, cf: str, lower: bytes | None = None, upper: bytes | None = None) -> Cursor:
        return _ListCursor(self._states[cf], lower, upper)


class BTreeEngine(KvEngine):
    def __init__(self, cfs: tuple[str, ...] = ALL_CFS):
        self._lock = threading.RLock()
        self._cfs: dict[str, _CfState] = {cf: _CfState() for cf in cfs}
        # one number per batch, and per CF the number of the newest batch
        # that touched it (Snapshot.sequence / cf_touched_seq), both moved
        # under _lock with the batch itself
        self._seq = 0
        self._cf_seq: dict[str, int] = {cf: 0 for cf in cfs}

    def _writable(self, cf: str) -> _CfState:
        state = self._cfs[cf]
        if state.frozen:
            state = state.clone()
            self._cfs[cf] = state
        self._cf_seq[cf] = self._seq
        return state

    def seq(self) -> int:
        with self._lock:
            return self._seq

    def cf_touched_seq(self, cf: str) -> int:
        with self._lock:
            return self._cf_seq[cf]

    def write(self, batch: WriteBatch) -> None:
        with self._lock:
            self._seq += 1
            for op, cf, key, val in batch.ops:
                state = self._writable(cf)
                if op == "put":
                    if key not in state.vals:
                        bisect.insort(state.keys, key)
                    state.vals[key] = val
                elif op == "delete":
                    if key in state.vals:
                        del state.vals[key]
                        i = bisect.bisect_left(state.keys, key)
                        del state.keys[i]
                elif op == "delete_range":
                    lo = bisect.bisect_left(state.keys, key)
                    hi = bisect.bisect_left(state.keys, val)
                    for k in state.keys[lo:hi]:
                        del state.vals[k]
                    del state.keys[lo:hi]
                else:
                    raise ValueError(f"unknown op {op}")

    def bulk_load(self, cf: str, items: list[tuple[bytes, bytes]]) -> None:
        """Merge a batch of (key, value) pairs in one sort — O((n+m) log(n+m))."""
        with self._lock:
            self._seq += 1
            state = self._writable(cf)
            state.vals.update(items)
            state.keys = sorted(state.vals)

    def snapshot(self) -> BTreeSnapshot:
        with self._lock:
            for state in self._cfs.values():
                state.frozen = True
            return BTreeSnapshot(dict(self._cfs), self._seq, self)

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        with self._lock:
            return self._cfs[cf].vals.get(key)

    def snapshot_cf(self, cf: str) -> BTreeSnapshot:
        """Snapshot freezing only one CF — scans shouldn't tax writes to other CFs."""
        with self._lock:
            state = self._cfs[cf]
            state.frozen = True
            return BTreeSnapshot({cf: state}, self._seq, self)

    def scan_cf(
        self,
        cf: str,
        start: bytes,
        end: bytes | None,
        limit: int | None = None,
        reverse: bool = False,
    ) -> Iterator[tuple[bytes, bytes]]:
        # Materialize the range under the lock rather than snapshotting: a
        # snapshot freezes the CF and forces the next write to clone it (O(n)).
        with self._lock:
            state = self._cfs[cf]
            lo = bisect.bisect_left(state.keys, start)
            hi = len(state.keys) if end is None else bisect.bisect_left(state.keys, end)
            keys = state.keys[lo:hi]
            if reverse:
                keys = keys[::-1]
            if limit is not None:
                keys = keys[:limit]
            items = [(k, state.vals[k]) for k in keys]
        return iter(items)
