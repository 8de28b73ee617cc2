"""A block of byte strings as one flat buffer and where each lies in it.

The cold fill's currency (``mvcc_batch`` → ``integrity.row_checksums`` →
``rowv2``): the engine's scan hands back one buffer, and the record keys, the
write records and the row values in it are each a ``ByteRows`` over that same
buffer, so no step cuts a ``bytes`` object a row to pass it on.  Whoever still
walks the rows reads it like a sequence of ``bytes``.
"""

from __future__ import annotations

import numpy as np


class ByteRows:
    """``raw[at[i] : at[i] + lens[i]]`` for each row ``i``; ``flat`` is ``raw``
    as a uint8 array.  Rows may lie anywhere in the buffer, in any order."""

    __slots__ = ("raw", "flat", "at", "lens")

    def __init__(self, raw: bytes, at: np.ndarray, lens: np.ndarray):
        self.raw = raw
        self.flat = np.frombuffer(raw, dtype=np.uint8)
        self.at = at
        self.lens = lens

    @classmethod
    def of(cls, rows) -> "ByteRows":
        """``rows`` itself, or a list of ``bytes`` joined into one buffer."""
        if isinstance(rows, ByteRows):
            return rows
        lens = np.fromiter(map(len, rows), dtype=np.int64, count=len(rows))
        return cls(b"".join(rows), np.cumsum(lens) - lens, lens)

    @classmethod
    def from_matrix(cls, mat: np.ndarray) -> "ByteRows":
        """The rows of an ``(n, width)`` byte matrix."""
        n, w = mat.shape
        return cls(mat.tobytes(), np.arange(n, dtype=np.int64) * w,
                   np.full(n, w, dtype=np.int64))

    def __len__(self) -> int:
        return len(self.at)

    def __getitem__(self, i):
        """An int gives the row's ``bytes``; a slice or an index array the
        rows it selects, over the same buffer."""
        if isinstance(i, (int, np.integer)):
            a = int(self.at[i])
            return self.raw[a : a + int(self.lens[i])]
        return ByteRows(self.raw, self.at[i], self.lens[i])

    def __iter__(self):
        raw = self.raw
        return (raw[a : a + n] for a, n in zip(self.at.tolist(), self.lens.tolist()))

    def matrix(self) -> np.ndarray | None:
        """The rows as an ``(n, width)`` byte matrix where they all share one
        width, else None.  A view (read-only) where the rows lie at one
        stride, as a fixed-width scan's do; a gathered copy otherwise."""
        n = len(self.at)
        if n == 0 or (self.lens != self.lens[0]).any():
            return None
        w = int(self.lens[0])
        at0 = int(self.at[0])
        stride = int(self.at[1]) - at0 if n > 1 else w
        end = at0 + (n - 1) * stride + w
        if (stride >= w and 0 <= at0 and end <= len(self.flat)
                and (n < 3 or (np.diff(self.at) == stride).all())):
            span = self.flat[at0:end]
            return np.lib.stride_tricks.as_strided(
                span, shape=(n, w), strides=(stride, 1), writeable=False)
        return self.flat[self.at[:, None] + np.arange(w)]
