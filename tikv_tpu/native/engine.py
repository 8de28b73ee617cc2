"""ctypes binding for the native C++ engine.

Implements the ``KvEngine``/``Snapshot``/``Cursor`` trait surface over
``engine.cc`` (the RocksDB role from components/engine_rocks, as a versioned
ordered memtable with O(1) sequence-number snapshots).  The shared library is
built on first use with the baked-in g++ (no pip deps; pybind11 unavailable —
plain C ABI via ctypes).
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading
from typing import Iterator

import numpy as np

from . import CLOSED, U64_CLOSED, ensure_built, refused
from ..storage.engine import ALL_CFS, Cursor, KvEngine, Snapshot, WriteBatch
from ..util.io_limiter import IoType

_CF_IDS = {cf: i for i, cf in enumerate(ALL_CFS)}

# background compaction folds a CF's sorted runs once this many accumulate
MERGE_FANIN = 4

def _serialize_ops(ops) -> bytes:
    """The native wire format (op u8 | cf u8 | klen u32 | key | vlen u32 |
    val) has exactly ONE encoder — write() and bulk_load() both come here.
    Join-based with precomputed 2-byte prefixes: this loop is the Python
    side of the ingestion hot path."""
    parts = []
    ap = parts.append
    pack = _U32.pack
    for op, cf, key, val in ops:
        v = val if val is not None else b""
        ap(_OP_CF_PREFIX[op, cf])
        ap(pack(len(key)))
        ap(key)
        ap(pack(len(v)))
        ap(v)
    return b"".join(parts)


_OP_CF_PREFIX = {
    (op, cf): bytes([opc, cfc])
    for op, opc in (("put", 1), ("delete", 2), ("delete_range", 3))
    for cf, cfc in _CF_IDS.items()
}
_U32 = struct.Struct("<I")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "engine.cc")
_SO = os.path.join(_HERE, "libtikv_engine.so")

_lib = None
_lib_err: str | None = None
_build_mu = threading.Lock()


def _load():
    global _lib, _lib_err
    with _build_mu:
        if _lib is not None or _lib_err is not None:
            return _lib
        try:
            ensure_built(_SO, _SRC, os.path.join(_HERE, "crypt.h"),
                         os.path.join(_HERE, "guard.h"))
            lib = ctypes.CDLL(_SO)
        except (OSError, subprocess.CalledProcessError) as e:
            _lib_err = str(e)
            return None
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.eng_open.restype = ctypes.c_void_p
        lib.eng_close.argtypes = [ctypes.c_void_p]
        lib.eng_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.eng_write.restype = ctypes.c_int
        lib.eng_snapshot.argtypes = [ctypes.c_void_p]
        lib.eng_snapshot.restype = ctypes.c_uint64
        lib.eng_release_snapshot.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.eng_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_get.restype = ctypes.c_int
        lib.eng_scan.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_scan.restype = ctypes.c_long
        lib.eng_seek.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_seek.restype = ctypes.c_int
        lib.eng_multi_get.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_multi_get.restype = ctypes.c_long
        lib.eng_multi_seek_newest.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int,
            ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_multi_seek_newest.restype = ctypes.c_long
        lib.eng_free.argtypes = [u8p]
        lib.eng_stats_keys.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.eng_stats_keys.restype = ctypes.c_uint64
        lib.eng_open_at.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.eng_open_at.restype = ctypes.c_void_p
        lib.eng_open_at_enc.argtypes = [
            ctypes.c_char_p, ctypes.c_int, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_char_p, ctypes.c_int,
        ]
        lib.eng_open_at_enc.restype = ctypes.c_void_p
        lib.eng_set_encryption.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32), ctypes.c_char_p, ctypes.c_int,
        ]
        lib.eng_set_encryption.restype = ctypes.c_int
        lib.eng_checkpoint.argtypes = [ctypes.c_void_p]
        lib.eng_checkpoint.restype = ctypes.c_int
        lib.eng_set_wal_limit.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.eng_set_wal_limit.restype = ctypes.c_int
        lib.eng_set_sync.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.eng_set_sync.restype = ctypes.c_int
        for fn in (lib.eng_seq, lib.eng_mem_bytes, lib.eng_wal_bytes):
            fn.argtypes = [ctypes.c_void_p]
            fn.restype = ctypes.c_uint64
        lib.eng_cf_touched_seq.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.eng_cf_touched_seq.restype = ctypes.c_uint64
        lib.eng_compact_step.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int),
        ]
        lib.eng_compact_step.restype = ctypes.c_long
        lib.eng_mvcc_props.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_char_p, ctypes.c_uint64,
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_int, ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.eng_mvcc_props.restype = ctypes.c_int
        lib.eng_build_sst.argtypes = [ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.eng_build_sst.restype = ctypes.c_int
        lib.eng_ingest_sst.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.eng_ingest_sst.restype = ctypes.c_int
        lib.eng_flush.argtypes = [ctypes.c_void_p]
        lib.eng_flush.restype = ctypes.c_int
        lib.eng_set_mem_limit.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.eng_set_mem_limit.restype = ctypes.c_int
        lib.eng_run_count.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.eng_run_count.restype = ctypes.c_int
        lib.eng_merge_runs.argtypes = [ctypes.c_void_p, ctypes.c_int]
        lib.eng_merge_runs.restype = ctypes.c_int
        lib.eng_perf.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64)]
        lib.eng_perf.restype = ctypes.c_int
        _lib = lib
        return _lib


def build_sst(path: str, entries) -> None:
    """Write an immutable SST file: ``entries`` = iterable of
    (cf_name, key, value), sorted by (cf, key).  The native side frames it
    (magic + CRC footer) and re-validates sortedness before the atomic
    tmp+rename publish."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native engine unavailable: {_lib_err}")
    parts = []
    for cf, key, val in entries:
        parts.append(bytes([_CF_IDS[cf]]))
        parts.append(_U32.pack(len(key)))
        parts.append(key)
        parts.append(_U32.pack(len(val)))
        parts.append(val)
    body = b"".join(parts)
    r = lib.eng_build_sst(os.fsencode(path), body, len(body))
    if r != 0:
        raise RuntimeError(f"eng_build_sst failed: {r} (entries must be sorted)")


def native_available() -> bool:
    return _load() is not None


def parse_frames(buf: bytes, n: int):
    """Iterate (key, value) pairs of the native scan frame format
    (klen u32le | key | vlen u32le | val) — THE decoder for this layout."""
    off = 0
    for _ in range(n):
        (klen,) = _U32.unpack_from(buf, off)
        off += 4
        k = buf[off : off + klen]
        off += klen
        (vlen,) = _U32.unpack_from(buf, off)
        off += 4
        v = buf[off : off + vlen]
        off += vlen
        yield k, v


# what a batched read's frame holds in place of a length for a key it did not
# find (engine.cc: kAbsent)
_ABSENT = 0xFFFFFFFF


def _frame_keys(keys) -> bytes:
    """The keys of a batched read as the native side takes them: repeated
    (klen u32le | key)."""
    pack = _U32.pack
    return b"".join([part for k in keys for part in (pack(len(k)), k)])


def _parse_batch(buf: bytes, n: int, pairs: bool) -> list:
    """The answers of a batched read, one a key in order: its value (``pairs``
    false: eng_multi_get's frames, vlen u32le | val) or its (key, value)
    (``pairs`` true: eng_multi_seek_newest's, klen | key | vlen | val), or
    None where the frame's first length is ``_ABSENT``."""
    unpack = _U32.unpack_from
    out = []
    ap = out.append
    off = 0
    for _ in range(n):
        (ln,) = unpack(buf, off)
        off += 4
        if ln == _ABSENT:
            ap(None)
            continue
        first = buf[off : off + ln]
        off += ln
        if pairs:
            (ln,) = unpack(buf, off)
            off += 4
            ap((first, buf[off : off + ln]))
            off += ln
        else:
            ap(first)
    return out


def frame_spans(buf: bytes, n: int) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Where the keys and the values of ``n`` scan frames lie in ``buf``:
    ``(key_at, key_len, value_at, value_len)``, nothing cut.  Where every
    frame has the first one's two lengths (checked as one byte matrix) they
    lie at one stride; else the walk ``parse_frames`` makes, reading each
    frame's lengths."""
    if n == 0:
        return (np.empty(0, dtype=np.int64),) * 4
    unpack = _U32.unpack_from
    klen = unpack(buf, 0)[0]
    if len(buf) >= 8 + klen:
        vlen = unpack(buf, 4 + klen)[0]
        stride = 8 + klen + vlen
        if len(buf) == n * stride:
            mat = np.frombuffer(buf, dtype=np.uint8).reshape(n, stride)
            if (mat[:, :4] == mat[0, :4]).all() and (
                    mat[:, 4 + klen : 8 + klen] == mat[0, 4 + klen : 8 + klen]).all():
                at = np.arange(n, dtype=np.int64) * stride
                return (at + 4, np.full(n, klen, dtype=np.int64),
                        at + 8 + klen, np.full(n, vlen, dtype=np.int64))
    k_at, v_at = [0] * n, [0] * n
    off = 0
    for i in range(n):
        k_at[i] = off = off + 4
        v_at[i] = off = off + unpack(buf, off - 4)[0] + 4
        off += unpack(buf, off - 4)[0]
    k_at = np.array(k_at, dtype=np.int64)
    v_at = np.array(v_at, dtype=np.int64)
    return k_at, v_at - 4 - k_at, v_at, np.append(k_at[1:] - 4, off) - v_at


def _failed(what: str, r: int) -> RuntimeError:
    """The exception for a native call's error code: ``EngineClosed`` where
    the call came after ``close()`` (guard.h)."""
    if r == CLOSED:
        return refused("kv", what)
    return RuntimeError(f"{what} failed: {r}")


def _u64(what: str, r: int) -> int:
    """An unsigned result, or ``EngineClosed``."""
    if r == U64_CLOSED:
        raise refused("kv", what)
    return r


def _take(lib, ptr, length) -> bytes:
    try:
        return ctypes.string_at(ptr, length)
    finally:
        lib.eng_free(ptr)


class _NativeCursor(Cursor):
    """Cursor via repeated bounded seeks (each seek resolves MVCC versions
    natively; next/prev re-seek from the current key)."""

    def __init__(self, snap: "NativeSnapshot", cf: int, lower: bytes | None, upper: bytes | None):
        self._snap = snap
        self._cf = cf
        self._lower = lower or b""
        self._upper = upper
        self._key: bytes | None = None
        self._value: bytes | None = None

    def _do_seek(self, target: bytes, for_prev: bool) -> bool:
        lib = self._snap._lib
        kout = ctypes.POINTER(ctypes.c_uint8)()
        klen = ctypes.c_uint64()
        vout = ctypes.POINTER(ctypes.c_uint8)()
        vlen = ctypes.c_uint64()
        upper = self._upper
        r = lib.eng_seek(
            self._snap._handle, self._cf, self._snap._seq,
            target, len(target),
            self._lower, len(self._lower),
            upper or b"", len(upper or b""), 1 if upper is not None else 0,
            1 if for_prev else 0,
            ctypes.byref(kout), ctypes.byref(klen),
            ctypes.byref(vout), ctypes.byref(vlen),
        )
        if r == 1:
            self._key = _take(lib, kout, klen.value)
            self._value = _take(lib, vout, vlen.value)
            return True
        self._key = self._value = None
        if r < 0:
            raise _failed("eng_seek", r)
        return False

    def seek(self, key: bytes) -> bool:
        return self._do_seek(key, False)

    def seek_for_prev(self, key: bytes) -> bool:
        return self._do_seek(key, True)

    def seek_to_first(self) -> bool:
        return self._do_seek(self._lower, False)

    def seek_to_last(self) -> bool:
        if self._upper is not None:
            # upper is exclusive; for_prev at upper then step below it
            if self._do_seek(self._upper, True) and self._key < self._upper:
                return True
            return self.prev() if self._key is not None else False
        return self._do_seek(b"\xff" * 64, True)

    def next(self) -> bool:
        if self._key is None:
            return False
        return self._do_seek(self._key + b"\x00", False)

    def prev(self) -> bool:
        """Step to the largest visible key strictly below the current one.

        Byte-string order has no exact predecessor, so seek_for_prev targets
        the tightest constructible bound: for ...X00 the prefix itself, else
        decrement the last byte and pad with 0xff (safe for keys shorter than
        the pad — true for all key layouts in this system).
        """
        if self._key is None:
            return False
        k = self._key
        if len(k) == 0:
            self._key = self._value = None
            return False
        if k.endswith(b"\x00"):
            target = k[:-1]
        else:
            target = k[:-1] + bytes([k[-1] - 1]) + b"\xff" * 64
        ok = self._do_seek(target, True)
        if ok and self._key >= k:
            self._key = self._value = None
            return False
        return ok

    def valid(self) -> bool:
        return self._key is not None

    def key(self) -> bytes:
        return self._key

    def value(self) -> bytes:
        return self._value


class NativeSnapshot(Snapshot):
    """Holds its engine, so the engine is not collected under it.  Its copy
    of the handle stays good after ``close()``: the handle is never freed
    (guard.h), and a read through it then raises ``EngineClosed``."""

    def __init__(self, engine: "NativeEngine"):
        self._lib = engine._lib
        self._handle = engine._handle
        self._engine = engine
        self._released = True  # until there is a sequence to release
        self._seq = _u64("eng_snapshot", self._lib.eng_snapshot(self._handle))
        self._released = False

    def __del__(self):
        try:
            self.release()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass

    def release(self) -> None:
        if not self._released:
            # a closed engine has no snapshots left to release: a no-op there
            self._lib.eng_release_snapshot(self._handle, self._seq)
            self._released = True

    def sequence(self) -> int:
        return self._seq

    def cf_touched_seq(self, cf: str) -> int:
        return self._engine.cf_touched_seq(cf)

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        r = self._lib.eng_get(
            self._handle, _CF_IDS[cf], key, len(key), self._seq,
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if r == 1:
            val = _take(self._lib, out, out_len.value)
            self._engine._io(IoType.FOREGROUND_READ, len(val))
            return val
        if r < 0:
            raise _failed("eng_get", r)
        return None

    def multi_get_cf(self, cf: str, keys: list[bytes]) -> list[bytes | None]:
        """``get_cf`` of every key in one crossing (eng_multi_get)."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        frames = _frame_keys(keys)
        n = self._lib.eng_multi_get(
            self._handle, _CF_IDS[cf], self._seq, frames, len(frames),
            len(keys), ctypes.byref(out), ctypes.byref(out_len),
        )
        if n < 0:
            raise _failed("eng_multi_get", n)
        buf = _take(self._lib, out, out_len.value)
        # the values' bytes: every frame but its length
        self._engine._io(IoType.FOREGROUND_READ, len(buf) - 4 * n)
        return _parse_batch(buf, n, pairs=False)

    def newest_versions_cf(self, cf: str, user_keys: list[bytes], ts: int,
                           lower: bytes | None = None,
                           upper: bytes | None = None) -> list[tuple[bytes, bytes] | None]:
        """The trait's answer in one crossing (eng_multi_seek_newest)."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        frames = _frame_keys(user_keys)
        lower = lower or b""
        n = self._lib.eng_multi_seek_newest(
            self._handle, _CF_IDS[cf], self._seq, frames, len(frames),
            len(user_keys), ts, lower, len(lower),
            upper or b"", len(upper or b""), 1 if upper is not None else 0,
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if n < 0:
            raise _failed("eng_multi_seek_newest", n)
        return _parse_batch(_take(self._lib, out, out_len.value), n, pairs=True)

    def cursor_cf(self, cf: str, lower: bytes | None = None, upper: bytes | None = None) -> Cursor:
        return _NativeCursor(self, _CF_IDS[cf], lower, upper)

    def scan_raw(self, cf: str, start: bytes, end: bytes | None, limit=None, reverse=False) -> tuple[int, bytes]:
        """One FFI crossing for a whole range: (n_pairs, framed buffer).
        Frame: repeated (klen u32le | key | vlen u32le | val)."""
        out = ctypes.POINTER(ctypes.c_uint8)()
        out_len = ctypes.c_uint64()
        n = self._lib.eng_scan(
            self._handle, _CF_IDS[cf], self._seq,
            start, len(start), end or b"", len(end or b""), 1 if end is not None else 0,
            limit or 0, 1 if reverse else 0,
            ctypes.byref(out), ctypes.byref(out_len),
        )
        if n < 0:
            raise _failed("eng_scan", n)
        buf = _take(self._lib, out, out_len.value)
        self._engine._io(IoType.FOREGROUND_READ, len(buf))
        return n, buf

    def scan_spans(self, cf: str, start: bytes, end: bytes | None):
        """``scan_raw``'s buffer and where its frames' keys and values lie in
        it: ``(buf, key_at, key_len, value_at, value_len)``."""
        n, buf = self.scan_raw(cf, start, end)
        return (buf, *frame_spans(buf, n))

    def scan_cf(self, cf, start, end, limit=None, reverse=False) -> Iterator[tuple[bytes, bytes]]:
        n, buf = self.scan_raw(cf, start, end, limit, reverse)
        yield from parse_frames(buf, n)


def _key_registry(keys_mgr):
    """(ids_array, keys_blob, current_id) for the FFI from a DataKeyManager."""
    items = sorted(keys_mgr.all_keys().items())
    ids = (ctypes.c_uint32 * len(items))(*[i for i, _k in items])
    keys = b"".join(k for _i, k in items)
    current, _ = keys_mgr.current()
    return ids, keys, current


class NativeEngine(KvEngine):
    """In-memory by default; pass ``path`` for a durable LSM engine: every
    committed WriteBatch is WAL-appended + fdatasync'd before the write
    returns (``sync=False`` keeps OS-buffered appends); memtable flushes
    write immutable block-indexed, bloom-filtered sorted runs and truncate
    the WAL; reads merge memtable + runs; background merges fold runs and
    drop bottom-level tombstones (engine_rocks over rocksdb: WAL + memtable
    flush + SST levels + compaction + perf context, re-derived)."""

    def __init__(self, path: str | None = None, sync: bool = True,
                 wal_limit: int | None = None, mem_limit: int | None = None,
                 io_limiter=None, keys_mgr=None):
        lib = _load()
        if lib is None:
            raise RuntimeError(f"native engine unavailable: {_lib_err}")
        self._lib = lib
        self.path = path
        # per-IO-type classification + throttling (components/file_system:
        # every engine IO is tagged foreground_write / flush / compaction /
        # foreground_read and, when a limiter is attached, pays its byte
        # budget — background compaction is the one the budget exists for)
        self._io_limiter = io_limiter
        self._io_bytes = {t: 0 for t in IoType}
        self._io_mu = threading.Lock()
        # encryption at rest (manager/mod.rs:398 + engine_rocks/src/
        # encryption.rs:30 role): the DataKeyManager's raw keys cross the FFI
        # once; every file written from here on is ChaCha20-encrypted with a
        # per-file sidecar naming its key id, so master/data-key rotation
        # never rewrites data files
        self._keys_mgr = keys_mgr
        if path is None:
            self._handle = lib.eng_open()
        else:
            if keys_mgr is not None:
                ids, keys, current = _key_registry(keys_mgr)
                self._handle = lib.eng_open_at_enc(
                    os.fsencode(path), 1 if sync else 0, current, ids, keys,
                    len(ids),
                )
            else:
                self._handle = lib.eng_open_at(
                    os.fsencode(path), 1 if sync else 0
                )
            if not self._handle:
                raise RuntimeError(f"cannot open engine dir {path!r}")
        if wal_limit is not None:
            lib.eng_set_wal_limit(self._handle, wal_limit)
        if mem_limit is not None:
            lib.eng_set_mem_limit(self._handle, mem_limit)

    def refresh_encryption(self) -> None:
        """Re-read the key registry from the DataKeyManager (after an
        external rotate): files written from now on use the new current key
        while existing files keep their sidecar key."""
        if self._keys_mgr is None:
            raise RuntimeError("engine opened without encryption")
        ids, keys, current = _key_registry(self._keys_mgr)
        r = self._lib.eng_set_encryption(self._handle, current, ids, keys, len(ids))
        if r != 0:
            raise _failed("eng_set_encryption", r)

    def rotate_data_key(self) -> int:
        """Mint a new data key and refresh the engine registry."""
        if self._keys_mgr is None:
            raise RuntimeError("engine opened without encryption")
        new_id = self._keys_mgr.rotate()
        self.refresh_encryption()
        return new_id

    def _io(self, io_type, nbytes: int) -> None:
        if nbytes <= 0 or self.path is None:
            return  # in-memory engines do no file IO: nothing to classify
        with self._io_mu:
            self._io_bytes[io_type] += nbytes
        if self._io_limiter is not None:
            self._io_limiter.request(nbytes, io_type)

    def io_stats(self) -> dict:
        """Bytes moved per IO type (file_system IOStats role)."""
        with self._io_mu:
            return {t.value: n for t, n in self._io_bytes.items() if n}

    def checkpoint(self) -> None:
        """Flush the memtable to sorted runs; truncates the WAL.  O(memtable),
        never O(database) — the incremental successor of the full spill."""
        nbytes = self.mem_bytes() if self.path is not None else 0
        r = self._lib.eng_checkpoint(self._handle)
        if r != 0:
            raise _failed("eng_checkpoint", r)
        self._io(IoType.FLUSH, nbytes)

    flush = checkpoint

    def set_mem_limit(self, limit: int) -> None:
        """Memtable flush threshold in bytes (0 = manual flush only)."""
        r = self._lib.eng_set_mem_limit(self._handle, limit)
        if r != 0:
            raise _failed("eng_set_mem_limit", r)

    def run_count(self, cf: str = "default") -> int:
        """On-disk sorted runs for one CF."""
        r = self._lib.eng_run_count(self._handle, _CF_IDS[cf])
        if r < 0:
            raise _failed("eng_run_count", r)
        return r

    def merge_runs(self, cf: str) -> int:
        """Merge every run of a CF into one (background compaction step);
        returns 1 if a merge happened."""
        nbytes = 0
        if self.path is not None and self.run_count(cf) >= 2:
            # compaction reads every input run and writes one output of
            # roughly the same size: charge the run bytes on disk (skip
            # in-flight .tmp files; a file unlinked mid-scan just drops out)
            prefix = f"run{_CF_IDS[cf]}-"
            try:
                names = os.listdir(self.path)
            except OSError:
                names = []
            for f in names:
                if f.startswith(prefix) and not f.endswith(".tmp"):
                    try:
                        nbytes += os.path.getsize(os.path.join(self.path, f))
                    except OSError:
                        pass
        r = self._lib.eng_merge_runs(self._handle, _CF_IDS[cf])
        if r < 0:
            raise _failed("eng_merge_runs", r)
        if r:
            self._io(IoType.COMPACTION, nbytes)
        return r

    def perf_context(self) -> dict:
        """Per-read statistics (engine_rocks perf_context.rs role)."""
        import ctypes

        out = (ctypes.c_uint64 * 7)()
        r = self._lib.eng_perf(self._handle, out)
        if r != 0:
            raise _failed("eng_perf", r)
        names = ("gets", "memtable_hits", "run_probes", "bloom_skips",
                 "blocks_read", "flushes", "run_merges")
        return dict(zip(names, out))

    def set_sync(self, sync: bool) -> None:
        """Import-mode tuning (import_mode.rs): buffered WAL during bulk
        load, fdatasync restored (and the window closed) when done."""
        r = self._lib.eng_set_sync(self._handle, 1 if sync else 0)
        if r != 0:
            # the flush closing the unsynced window failed: the buffered tail
            # is not durable and the engine has latched into refuse-writes
            raise _failed("eng_set_sync", r)

    def seq(self) -> int:
        return _u64("eng_seq", self._lib.eng_seq(self._handle))

    def cf_touched_seq(self, cf: str) -> int:
        """Sequence number of the newest batch that put, deleted or
        range-deleted in ``cf`` (``Snapshot.cf_touched_seq``)."""
        return _u64("eng_cf_touched_seq",
                    self._lib.eng_cf_touched_seq(self._handle, _CF_IDS[cf]))

    def mem_bytes(self) -> int:
        """Approximate resident key+value bytes (tikv_alloc-style accounting)."""
        return _u64("eng_mem_bytes", self._lib.eng_mem_bytes(self._handle))

    def wal_bytes(self) -> int:
        return _u64("eng_wal_bytes", self._lib.eng_wal_bytes(self._handle))

    # -- compaction ---------------------------------------------------------

    def compact_cf(self, cf: str, slice_keys: int = 4096) -> int:
        """One full compaction pass over a CF in bounded slices; returns
        versions dropped.  Each slice holds the engine's write lock for at
        most ``slice_keys`` keys, so reads/writes interleave between slices
        (the rocksdb background-compaction property, with the scheduling
        living here and the work in native code — ctypes releases the GIL
        for the duration of each step)."""
        import ctypes

        total = 0
        cursor = b""
        while True:
            resume = ctypes.POINTER(ctypes.c_uint8)()
            resume_len = ctypes.c_uint64(0)
            done = ctypes.c_int(0)
            r = self._lib.eng_compact_step(
                self._handle, _CF_IDS[cf], cursor, len(cursor), slice_keys,
                ctypes.byref(resume), ctypes.byref(resume_len), ctypes.byref(done),
            )
            if r < 0:
                raise _failed("eng_compact_step", r)
            total += r
            if done.value:
                return total
            cursor = _take(self._lib, resume, resume_len.value)

    def compact(self, slice_keys: int = 4096) -> int:
        """Compact every CF; returns total versions dropped."""
        return sum(self.compact_cf(cf, slice_keys) for cf in _CF_IDS)

    def start_auto_compaction(self, interval_s: float = 10.0) -> None:
        """Background compaction loop (rocksdb's background job threads)."""
        import threading

        if getattr(self, "_compactor", None) is not None:
            return
        self._compact_stop = threading.Event()

        def loop():
            while not self._compact_stop.wait(interval_s):
                try:
                    self.compact()
                    # fold accumulated runs (leveled-compaction role): merge
                    # whenever a CF's run count reaches the fan-in
                    if self.path is not None:
                        for cf in _CF_IDS:
                            if self.run_count(cf) >= MERGE_FANIN:
                                self.merge_runs(cf)
                except RuntimeError:
                    return

        self._compactor = threading.Thread(
            target=loop, name="native-compaction", daemon=True
        )
        self._compactor.start()

    def stop_auto_compaction(self) -> None:
        if getattr(self, "_compactor", None) is not None:
            self._compact_stop.set()
            self._compactor.join(timeout=5.0)
            self._compactor = None

    # -- SST ingest ---------------------------------------------------------

    def ingest_sst(self, path: str) -> None:
        """Ingest an immutable SST file (sst_importer ingest:158): validated,
        copied into the engine dir, WAL-referenced (manifest-style), loaded.
        Survives crash/reopen; folded into the next checkpoint."""
        r = self._lib.eng_ingest_sst(self._handle, os.fsencode(path))
        if r != 0:
            raise _failed("eng_ingest_sst", r)

    # -- MVCC properties ----------------------------------------------------

    def mvcc_properties(self, start: bytes = b"", end: bytes | None = None,
                        cf: str = "write") -> dict:
        """Range statistics steering GC (engine_rocks properties.rs
        MvccProperties): whether a sweep over this range can collect
        anything at all."""
        import ctypes

        out = (ctypes.c_uint64 * 8)()
        r = self._lib.eng_mvcc_props(
            self._handle, _CF_IDS[cf], start, len(start),
            end or b"", len(end or b""), 0 if end is None else 1,
            self.seq(), out,
        )
        if r != 0:
            raise _failed("eng_mvcc_props", r)
        return {
            "num_entries": out[0],
            "num_rows": out[1],
            "num_puts": out[2],
            "num_deletes": out[3],
            "num_locks_rollbacks": out[4],
            "min_commit_ts": out[5],
            "max_commit_ts": out[6],
            "max_row_versions": out[7],
        }

    def need_gc(self, safe_point: int, ratio_threshold: float = 1.1,
                start: bytes = b"", end: bytes | None = None) -> bool:
        """The compaction-filter gate (gc_worker check_need_gc): skip ranges
        where versions/rows is below the threshold and nothing is deleted."""
        p = self.mvcc_properties(start, end)
        if p["num_rows"] == 0:
            return False
        if p["min_commit_ts"] > safe_point:
            return False  # every version still visible above the safe point
        if p["num_deletes"] > 0 or p["num_locks_rollbacks"] > 0:
            return True
        return p["num_entries"] >= p["num_rows"] * ratio_threshold

    def close(self) -> None:
        """Free the native engine.  A call in flight on another thread
        finishes normally first; every call after this one, through the
        engine or through a snapshot or cursor taken before it, raises
        ``EngineClosed``; a second close does nothing (guard.h)."""
        self.stop_auto_compaction()
        self._lib.eng_close(self._handle)

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter shutdown
            pass

    def _write_buf(self, out: bytes) -> None:
        self._io(IoType.FOREGROUND_WRITE, len(out))
        r = self._lib.eng_write(self._handle, out, len(out))
        if r != 0:
            raise _failed("eng_write", r)

    def write(self, batch: WriteBatch) -> None:
        self._write_buf(_serialize_ops(batch.ops))

    def bulk_load(self, cf: str, items: list[tuple[bytes, bytes]]) -> None:
        # chunked so the parts list and joined buffer stay allocator-friendly
        CH = 32768
        for off in range(0, len(items), CH):
            self._write_buf(
                _serialize_ops(
                    ("put", cf, k, v) for k, v in items[off : off + CH]
                )
            )

    def snapshot(self) -> NativeSnapshot:
        return NativeSnapshot(self)

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        snap = self.snapshot()
        try:
            return snap.get_cf(cf, key)
        finally:
            snap.release()

    def scan_cf(self, cf, start, end, limit=None, reverse=False):
        snap = self.snapshot()
        try:
            return list(snap.scan_cf(cf, start, end, limit, reverse))
        finally:
            snap.release()
