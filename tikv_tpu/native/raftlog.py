"""ctypes binding for the native raft log engine (raftlog.cc).

The RaftEngine role from the reference (components/raft_log_engine/src/
engine.rs:25, selected per-store at components/server/src/server.rs:153-157):
raft log entries + hard-state blobs in segmented append-only files with
group-commit fdatasync, logical purge, and live-record rewrite — instead of
riding CF_RAFT of the general-purpose LSM.  Built on first use with the
baked-in g++ (plain C ABI via ctypes; pybind11 unavailable in this image).
"""

from __future__ import annotations

import ctypes
import os
import struct
import subprocess
import threading

from . import CLOSED, ensure_built, refused

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "raftlog.cc")
_SO = os.path.join(_HERE, "libtikv_raftlog.so")

_lib = None
_lib_err: str | None = None
_build_mu = threading.Lock()

_U32 = struct.Struct("<I")
_FRAME = struct.Struct("<QI")  # idx u64 | len u32


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
        c = ctypes
        lib.rl_open.argtypes = [c.c_char_p, c.c_uint64, c.c_int, c.c_uint32, c.c_char_p, c.c_int]
        lib.rl_open.restype = c.c_void_p
        lib.rl_open_enc.argtypes = [
            c.c_char_p, c.c_uint64, c.c_int, c.c_uint32, c.c_uint32,
            c.POINTER(c.c_uint32), c.c_char_p, c.c_int, c.c_char_p, c.c_int,
        ]
        lib.rl_open_enc.restype = c.c_void_p
        lib.rl_set_encryption.argtypes = [
            c.c_void_p, c.c_uint32, c.POINTER(c.c_uint32), c.c_char_p, c.c_int,
        ]
        lib.rl_set_encryption.restype = c.c_int
        lib.rl_close.argtypes = [c.c_void_p]
        lib.rl_append.argtypes = [
            c.c_void_p, c.c_uint64, c.c_uint64, c.c_uint32,
            c.c_char_p, c.POINTER(c.c_uint32), c.c_char_p, c.c_uint32, c.c_int,
        ]
        lib.rl_append.restype = c.c_int
        lib.rl_put_state.argtypes = [c.c_void_p, c.c_uint64, c.c_char_p, c.c_uint32, c.c_int]
        lib.rl_put_state.restype = c.c_int
        for fn in (lib.rl_first_index, lib.rl_last_index):
            fn.argtypes = [c.c_void_p, c.c_uint64]
            fn.restype = c.c_int64
        lib.rl_fetch_size.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64, c.c_uint64]
        lib.rl_fetch_size.restype = c.c_int64
        lib.rl_fetch.argtypes = [
            c.c_void_p, c.c_uint64, c.c_uint64, c.c_uint64, c.c_char_p, c.c_uint64
        ]
        lib.rl_fetch.restype = c.c_int64
        lib.rl_state.argtypes = [c.c_void_p, c.c_uint64, c.c_char_p, c.c_uint32]
        lib.rl_state.restype = c.c_int
        lib.rl_purge.argtypes = [c.c_void_p, c.c_uint64, c.c_uint64]
        lib.rl_purge.restype = c.c_int
        lib.rl_clean.argtypes = [c.c_void_p, c.c_uint64]
        lib.rl_clean.restype = c.c_int
        lib.rl_regions.argtypes = [c.c_void_p, c.POINTER(c.c_uint64), c.c_uint32]
        lib.rl_regions.restype = c.c_int64
        lib.rl_sync.argtypes = [c.c_void_p]
        lib.rl_sync.restype = c.c_int
        lib.rl_stats.argtypes = [c.c_void_p, c.POINTER(c.c_uint64)]
        lib.rl_stats.restype = c.c_int
        _lib = lib
        return lib


def raftlog_available() -> bool:
    return _load() is not None


def _live(what: str, r: int) -> int:
    """A native call's result, or ``EngineClosed`` where the call came after
    ``close()`` (guard.h)."""
    if r == CLOSED:
        raise refused("raftlog", what)
    return r


def _key_registry(keys_mgr):
    """(ids_array, keys_blob, current_id) for the FFI (engine.py twin)."""
    items = sorted(keys_mgr.all_keys().items())
    ids = (ctypes.c_uint32 * len(items))(*[i for i, _k in items])
    keys = b"".join(k for _i, k in items)
    current, _ = keys_mgr.current()
    return ids, keys, current


class NativeRaftLog:
    """One store's raft log: entries + hard-state blobs keyed by region id.

    Thread-safe; the entry blob format is opaque to this layer (the store's
    ``_encode_entry`` bytes go in and come back verbatim).  After ``close()``
    every call raises ``EngineClosed``; one in flight finishes first.
    """

    def __init__(self, path: str, segment_bytes: int = 64 << 20,
                 sync: bool = True, rewrite_max: int = 4096, keys_mgr=None):
        lib = _load()
        if lib is None:
            raise ImportError(f"native raftlog unavailable: {_lib_err}")
        self._lib = lib
        self._keys_mgr = keys_mgr
        err = ctypes.create_string_buffer(256)
        if keys_mgr is not None:
            ids, keys, current = _key_registry(keys_mgr)
            self._h = lib.rl_open_enc(
                os.fsencode(path), segment_bytes, 1 if sync else 0,
                rewrite_max, current, ids, keys, len(ids), err, 256,
            )
        else:
            self._h = lib.rl_open(
                os.fsencode(path), segment_bytes, 1 if sync else 0, rewrite_max, err, 256
            )
        if not self._h:
            raise RuntimeError(f"raftlog open failed: {err.value.decode()}")
        self.path = path

    def refresh_encryption(self) -> None:
        """Re-read the key registry after an external rotate."""
        if self._keys_mgr is None:
            raise RuntimeError("raftlog opened without encryption")
        ids, keys, current = _key_registry(self._keys_mgr)
        if _live("rl_set_encryption", self._lib.rl_set_encryption(
                self._h, current, ids, keys, len(ids))) != 0:
            raise RuntimeError("rl_set_encryption failed")

    def rotate_data_key(self) -> int:
        """Mint a new data key and refresh the registry; new segments
        encrypt under it."""
        if self._keys_mgr is None:
            raise RuntimeError("raftlog opened without encryption")
        new_id = self._keys_mgr.rotate()
        self.refresh_encryption()
        return new_id

    # -- write path ---------------------------------------------------------

    def append(self, region_id: int, first_index: int, blobs: list[bytes],
               state: bytes | None = None, sync: int = -1) -> None:
        """Append ``blobs`` as entries [first_index, ...) — truncating any
        conflicting indexed suffix — plus an optional hard-state blob, as one
        durable batch (sync -1 = engine default, grouped fdatasync)."""
        n = len(blobs)
        lens = (ctypes.c_uint32 * n)(*[len(b) for b in blobs]) if n else None
        buf = b"".join(blobs)
        st = state if state is not None else b""
        r = self._lib.rl_append(
            self._h, region_id, first_index, n, buf, lens, st, len(st), sync
        )
        if _live("rl_append", r) != 0:
            raise OSError("raftlog append failed")

    def put_state(self, region_id: int, state: bytes, sync: int = -1) -> None:
        if _live("rl_put_state", self._lib.rl_put_state(
                self._h, region_id, state, len(state), sync)) != 0:
            raise OSError("raftlog put_state failed")

    def purge(self, region_id: int, to_index: int) -> None:
        """Logically drop entries <= to_index; dead segments are unlinked and
        nearly-dead ones rewritten (engine.rs purge_expired_files role)."""
        if _live("rl_purge", self._lib.rl_purge(self._h, region_id, to_index)) != 0:
            raise OSError("raftlog purge failed")

    def clean(self, region_id: int) -> None:
        if _live("rl_clean", self._lib.rl_clean(self._h, region_id)) != 0:
            raise OSError("raftlog clean failed")

    def sync(self) -> None:
        _live("rl_sync", self._lib.rl_sync(self._h))

    # -- read path ----------------------------------------------------------

    def first_index(self, region_id: int) -> int:
        return _live("rl_first_index", self._lib.rl_first_index(self._h, region_id))

    def last_index(self, region_id: int) -> int:
        return _live("rl_last_index", self._lib.rl_last_index(self._h, region_id))

    def state(self, region_id: int) -> bytes | None:
        cap = 512
        while True:
            buf = ctypes.create_string_buffer(cap)
            r = _live("rl_state", self._lib.rl_state(self._h, region_id, buf, cap))
            if r == -2:
                return None
            if r == -1:
                cap *= 4
                continue
            return buf.raw[:r]

    def entries(self, region_id: int, lo: int = 0, hi: int = 1 << 62) -> list[tuple[int, bytes]]:
        """(index, blob) pairs for [lo, hi), ascending."""
        need = _live("rl_fetch_size",
                     self._lib.rl_fetch_size(self._h, region_id, lo, hi))
        if need <= 0:
            return []
        while True:
            buf = ctypes.create_string_buffer(int(need))
            n = _live("rl_fetch",
                      self._lib.rl_fetch(self._h, region_id, lo, hi, buf, need))
            if n == -1:  # raced with an append that grew the range
                need = _live("rl_fetch_size",
                             self._lib.rl_fetch_size(self._h, region_id, lo, hi))
                continue
            if n == -2:
                raise OSError("raftlog fetch IO error")
            out = []
            pos = 0
            raw = buf.raw
            for _ in range(n):
                idx, ln = _FRAME.unpack_from(raw, pos)
                pos += 12
                out.append((idx, raw[pos:pos + ln]))
                pos += ln
            return out

    def regions(self) -> list[int]:
        cap = 1024
        while True:
            arr = (ctypes.c_uint64 * cap)()
            n = _live("rl_regions", self._lib.rl_regions(self._h, arr, cap))
            if n <= cap:
                return [arr[i] for i in range(n)]
            cap = int(n) + 64

    def stats(self) -> dict:
        out = (ctypes.c_uint64 * 6)()
        _live("rl_stats", self._lib.rl_stats(self._h, out))
        return {
            "segments": out[0],
            "active_size": out[1],
            "live_entries": out[2],
            "rewrites": out[3],
            "purged_entries": out[4],
            "appends": out[5],
        }

    def close(self) -> None:
        """Free the native log: after the calls in flight, once (guard.h)."""
        self._lib.rl_close(self._h)

    def __del__(self):  # pragma: no cover - interpreter shutdown ordering
        try:
            self.close()
        except Exception:
            pass
