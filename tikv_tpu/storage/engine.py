"""Storage-engine trait layer.

Re-expression of ``components/engine_traits`` (``engine.rs:13``,
``peekable.rs:11``, ``iterable.rs:130``, ``write_batch.rs:33,82``,
``snapshot.rs:11``, ``cf_defs.rs``): a small set of abstract interfaces that
decouple everything above (MVCC, txn, raftstore, coprocessor) from the concrete
storage medium.  Implementations in this package:

* ``btree_engine.BTreeEngine`` — ordered in-memory engine (tests + default)
* ``native`` C++ engine (ctypes) — drop-in once built

Column families mirror ``cf_defs.rs``: default / lock / write / raft.
"""

from __future__ import annotations

import abc
from typing import Iterator

from ..util.codec import encode_u64_desc

CF_DEFAULT = "default"
CF_LOCK = "lock"
CF_WRITE = "write"
CF_RAFT = "raft"
ALL_CFS = (CF_DEFAULT, CF_LOCK, CF_WRITE, CF_RAFT)
DATA_CFS = (CF_DEFAULT, CF_LOCK, CF_WRITE)


class Cursor(abc.ABC):
    """A bidirectional iterator over one CF of a snapshot.

    Semantics follow ``engine_traits::Iterator`` (iterable.rs:33-127): the
    cursor is either valid (positioned on an entry) or invalid; seeks position
    it at the first entry >= key (``seek``) or last entry <= key
    (``seek_for_prev``).
    """

    @abc.abstractmethod
    def seek(self, key: bytes) -> bool: ...

    @abc.abstractmethod
    def seek_for_prev(self, key: bytes) -> bool: ...

    @abc.abstractmethod
    def seek_to_first(self) -> bool: ...

    @abc.abstractmethod
    def seek_to_last(self) -> bool: ...

    @abc.abstractmethod
    def next(self) -> bool: ...

    @abc.abstractmethod
    def prev(self) -> bool: ...

    @abc.abstractmethod
    def valid(self) -> bool: ...

    @abc.abstractmethod
    def key(self) -> bytes: ...

    @abc.abstractmethod
    def value(self) -> bytes: ...


class Snapshot(abc.ABC):
    """A consistent, immutable view of the engine (snapshot.rs:11)."""

    @abc.abstractmethod
    def get_cf(self, cf: str, key: bytes) -> bytes | None: ...

    @abc.abstractmethod
    def cursor_cf(self, cf: str, lower: bytes | None = None, upper: bytes | None = None) -> Cursor: ...

    def get(self, key: bytes) -> bytes | None:
        return self.get_cf(CF_DEFAULT, key)

    def cursor(self, lower: bytes | None = None, upper: bytes | None = None) -> Cursor:
        return self.cursor_cf(CF_DEFAULT, lower, upper)

    def sequence(self) -> int | None:
        """The engine sequence number this snapshot reads at: it holds every
        batch up to it and none after.  None where the engine keeps none."""
        return None

    def cf_touched_seq(self, cf: str) -> int | None:
        """Sequence number of the newest batch that put, deleted or
        range-deleted in ``cf``, read from the engine NOW, so after this
        snapshot was taken: it may name a batch the snapshot does not hold,
        never miss one it does.  Read after snapshots ``a`` and ``b`` of one
        engine were taken, ``cf_touched_seq(cf) <= min(a.sequence(),
        b.sequence())`` therefore proves that both read the same ``cf``
        (docs/write_path.md).  None where the engine cannot say."""
        return None

    def multi_get_cf(self, cf: str, keys: list[bytes]) -> list[bytes | None]:
        """``get_cf`` of every key, in order.  An engine that can answer them
        in one call overrides this default, which asks once a key."""
        return [self.get_cf(cf, k) for k in keys]

    def newest_versions_cf(
        self,
        cf: str,
        user_keys: list[bytes],
        ts: int,
        lower: bytes | None = None,
        upper: bytes | None = None,
    ) -> list[tuple[bytes, bytes] | None]:
        """For each user key, in order: the first (key, value) of ``cf`` at or
        after ``user_key ++ desc(ts)`` within [lower, upper) if that key is
        the user key and an 8-byte version suffix, else None; for CF_WRITE
        that is the key's newest version at or below ``ts``
        (``MvccReader.seek_write``).  An engine that can answer them in one
        call overrides this default, which seeks a cursor once a key."""
        suffix = encode_u64_desc(ts)
        cur = self.cursor_cf(cf, lower, upper)
        out: list[tuple[bytes, bytes] | None] = []
        for uk in user_keys:
            found = None
            if cur.seek(uk + suffix):
                k = cur.key()
                if len(k) == len(uk) + 8 and k.startswith(uk):
                    found = (k, cur.value())
            out.append(found)
        return out

    def scan_cf(
        self,
        cf: str,
        start: bytes,
        end: bytes | None,
        limit: int | None = None,
        reverse: bool = False,
    ) -> Iterator[tuple[bytes, bytes]]:
        """Yield (key, value) in [start, end) — convenience over cursors."""
        cur = self.cursor_cf(cf, lower=None if reverse else start, upper=end)
        n = 0
        if reverse:
            ok = cur.seek_for_prev(end) if end is not None else cur.seek_to_last()
            # end is exclusive
            if ok and end is not None and cur.key() >= end:
                ok = cur.prev()
            while ok and (limit is None or n < limit):
                if cur.key() < start:
                    break
                yield cur.key(), cur.value()
                n += 1
                ok = cur.prev()
        else:
            ok = cur.seek(start)
            while ok and (limit is None or n < limit):
                if end is not None and cur.key() >= end:
                    break
                yield cur.key(), cur.value()
                n += 1
                ok = cur.next()


class WriteBatch:
    """Ordered list of mutations applied atomically (write_batch.rs:33,82)."""

    __slots__ = ("ops",)

    def __init__(self):
        # (op, cf, key, value_or_end_key)
        self.ops: list[tuple[str, str, bytes, bytes | None]] = []

    def put(self, key: bytes, value: bytes) -> None:
        self.put_cf(CF_DEFAULT, key, value)

    def put_cf(self, cf: str, key: bytes, value: bytes) -> None:
        self.ops.append(("put", cf, key, value))

    def delete(self, key: bytes) -> None:
        self.delete_cf(CF_DEFAULT, key)

    def delete_cf(self, cf: str, key: bytes) -> None:
        self.ops.append(("delete", cf, key, None))

    def delete_range_cf(self, cf: str, start: bytes, end: bytes) -> None:
        self.ops.append(("delete_range", cf, start, end))

    def is_empty(self) -> bool:
        return not self.ops

    def count(self) -> int:
        return len(self.ops)

    def clear(self) -> None:
        self.ops.clear()

    def merge(self, other: "WriteBatch") -> None:
        self.ops.extend(other.ops)


class KvEngine(abc.ABC):
    """The full engine interface (engine.rs:13): point ops + batches + snapshots."""

    @abc.abstractmethod
    def write(self, batch: WriteBatch) -> None: ...

    @abc.abstractmethod
    def snapshot(self) -> Snapshot: ...

    @abc.abstractmethod
    def get_cf(self, cf: str, key: bytes) -> bytes | None: ...

    def get(self, key: bytes) -> bytes | None:
        return self.get_cf(CF_DEFAULT, key)

    def put_cf(self, cf: str, key: bytes, value: bytes) -> None:
        wb = WriteBatch()
        wb.put_cf(cf, key, value)
        self.write(wb)

    def delete_cf(self, cf: str, key: bytes) -> None:
        wb = WriteBatch()
        wb.delete_cf(cf, key)
        self.write(wb)

    @abc.abstractmethod
    def scan_cf(
        self,
        cf: str,
        start: bytes,
        end: bytes | None,
        limit: int | None = None,
        reverse: bool = False,
    ) -> Iterator[tuple[bytes, bytes]]: ...

    def flush(self) -> None:  # durability hook; in-memory engines no-op
        pass
