"""Encryption at rest: AES-GCM, two-level keys, encrypting engine wrapper.

Re-expression of ``components/encryption`` (master_key/{file,mem}.rs,
manager/, crypter.rs, file_dict_file.rs): a master key seals rotating *data
keys*; every value is encrypted under the current data key with a random
per-value nonce; the key dictionary itself is persisted sealed under the
master key, so rotating the MASTER key only re-seals the dictionary — data
written under old data keys stays readable without rewriting a byte.  The
cipher is AES-256-GCM (the reference's crypter.rs AEAD choice) via the
``cryptography`` package, with a keyed-BLAKE2b AEAD fallback when that
package is absent (same architecture, honest about the primitive).
"""

from __future__ import annotations

import hashlib
import hmac
import os
import threading

from ..util import codec
from .engine import Cursor, KvEngine, Snapshot, WriteBatch

try:
    from cryptography.hazmat.primitives.ciphers.aead import AESGCM
except ImportError:  # pragma: no cover - baked into this image
    AESGCM = None

_METHOD_BLAKE2 = 0  # keyed-keystream + MAC fallback
_METHOD_AESGCM = 1  # AES-256-GCM (crypter.rs EncryptionMethod::Aes256Gcm)

_BLOCK = 64  # blake2b digest size


def _keystream(key: bytes, iv: bytes, n: int) -> bytes:
    out = bytearray()
    counter = 0
    while len(out) < n:
        out += hashlib.blake2b(
            iv + counter.to_bytes(8, "big"), key=key, digest_size=_BLOCK
        ).digest()
        counter += 1
    return bytes(out[:n])


def _xor(data: bytes, stream: bytes) -> bytes:
    # big-int XOR: ~50x faster than a per-byte generator on large values
    n = len(data)
    return (
        int.from_bytes(data, "little") ^ int.from_bytes(stream[:n], "little")
    ).to_bytes(n, "little")


def seal(key: bytes, plaintext: bytes) -> bytes:
    """method(1) | nonce | ciphertext+tag — AEAD under the given 32-byte key."""
    if AESGCM is not None:
        nonce = os.urandom(12)
        ct = AESGCM(key).encrypt(nonce, plaintext, None)
        return bytes([_METHOD_AESGCM]) + nonce + ct
    iv = os.urandom(16)
    ct = _xor(plaintext, _keystream(key, iv, len(plaintext)))
    mac = hmac.new(key, iv + ct, hashlib.blake2b).digest()[:16]
    return bytes([_METHOD_BLAKE2]) + iv + ct + mac


def unseal(key: bytes, sealed: bytes) -> bytes:
    """Inverse of :func:`seal`.

    Format note: the leading method byte was introduced before any release
    shipped; there is no deployed data in the legacy headerless ``iv|ct|mac``
    layout, so no fallback parse is attempted for it.
    """
    if not sealed:
        raise ValueError("empty sealed blob")
    method, body = sealed[0], sealed[1:]
    if method == _METHOD_AESGCM:
        if AESGCM is None:
            raise ValueError("AES-GCM sealed data but no cipher library")
        if len(body) < 12 + 16:
            raise ValueError("sealed blob too short")
        from cryptography.exceptions import InvalidTag

        try:
            return AESGCM(key).decrypt(body[:12], body[12:], None)
        except InvalidTag as e:
            raise ValueError("AEAD tag mismatch: wrong key or corrupted data") from e
    if method == _METHOD_BLAKE2:
        if len(body) < 32:
            raise ValueError("sealed blob too short")
        iv, ct, mac = body[:16], body[16:-16], body[-16:]
        want = hmac.new(key, iv + ct, hashlib.blake2b).digest()[:16]
        if not hmac.compare_digest(mac, want):
            raise ValueError("MAC mismatch: wrong key or corrupted data")
        return _xor(ct, _keystream(key, iv, len(ct)))
    raise ValueError(f"unknown seal method {method}")


class MasterKey:
    """Master key backends (master_key/{file,mem}.rs)."""

    def __init__(self, key: bytes):
        if len(key) < 16:
            raise ValueError("master key must be at least 16 bytes")
        self.key = hashlib.blake2b(key, digest_size=32).digest()

    @classmethod
    def from_file(cls, path: str) -> "MasterKey":
        """Hex text (master_key/file.rs format) or raw key bytes.

        The reference's file backend holds exactly one 256-bit key as 64 hex
        chars, so ONLY that shape takes the hex interpretation — an all-hex
        file of any other length is deliberate raw key material (e.g. a
        16-byte binary key that happens to decode as ASCII hex) and must not
        be silently re-encoded into a different key.  A 64-char near-hex
        file is a corrupted hex key, not raw bytes: error loudly."""
        with open(path, "rb") as f:
            raw = f.read()
        try:
            text = raw.decode("ascii")
        except UnicodeDecodeError:
            return cls(raw)  # binary key material
        stripped = text.strip()
        hexish = sum(c in "0123456789abcdefABCDEF" for c in stripped)
        if len(stripped) == 64:
            if hexish == 64:
                return cls(bytes.fromhex(stripped))  # exactly 32 key bytes
            if hexish >= 0.9 * 64:
                # almost-hex at the exact key length: a corrupted hex key
                # file, not deliberate raw bytes
                raise ValueError(f"{path}: looks like hex but fails to parse")
        return cls(raw)

    @classmethod
    def mem(cls, seed: bytes = b"test-master-key-0000") -> "MasterKey":
        return cls(seed)


class DataKeyManager:
    """Rotating data keys sealed under the master key (manager/), with the
    key dictionary persisted to disk (file_dict_file.rs role: atomic
    tmp+rename snapshots of the sealed dict)."""

    def __init__(self, master: MasterKey, dict_path: str | None = None):
        self.master = master
        self._mu = threading.Lock()
        self._persist_mu = threading.Lock()
        self.keys: dict[int, bytes] = {}
        self.current_id = 0
        self.dict_path = dict_path
        self.rotate()

    def rotate(self) -> int:
        """Mint a new data key; new writes use it, old keys stay for reads."""
        with self._mu:
            self.current_id += 1
            self.keys[self.current_id] = os.urandom(32)
            kid = self.current_id
        self._persist()
        return kid

    def rotate_master(self, new_master: MasterKey) -> None:
        """Master-key rotation (master_key/file.rs:10-47 semantics): the data
        keys are unchanged — only the dictionary is re-sealed — so every file
        written under an old data key stays readable without rewriting."""
        with self._mu:
            self.master = new_master
        self._persist()

    def _persist(self) -> None:
        if self.dict_path is None:
            return
        # one persist at a time, export INSIDE the persist lock: two
        # concurrent rotations must not race a stale dict over a newer one
        # (or interleave bytes in the shared tmp file)
        with self._persist_mu:
            blob = self.export_dict()
            tmp = self.dict_path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self.dict_path)
            # the rename itself must survive a crash (file_dict_file.rs
            # guarantee): fsync the containing directory
            dfd = os.open(os.path.dirname(os.path.abspath(self.dict_path)), os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)

    @classmethod
    def open(cls, master: MasterKey, dict_path: str) -> "DataKeyManager":
        """Load the persisted dictionary, or create a fresh manager when the
        path does not exist yet.  A wrong master key fails loudly here — the
        reference likewise refuses to start with an undecryptable dict."""
        if os.path.exists(dict_path):
            with open(dict_path, "rb") as f:
                mgr = cls.import_dict(master, f.read())
            mgr.dict_path = dict_path
            return mgr
        return cls(master, dict_path=dict_path)

    def current(self) -> tuple[int, bytes]:
        with self._mu:
            return self.current_id, self.keys[self.current_id]

    def by_id(self, key_id: int) -> bytes:
        with self._mu:
            k = self.keys.get(key_id)
        if k is None:
            raise ValueError(f"unknown data key {key_id}")
        return k

    def all_keys(self) -> dict[int, bytes]:
        """Snapshot of every data key, for handing the registry to a native
        engine over the FFI (old keys keep old files readable)."""
        with self._mu:
            return dict(self.keys)

    def export_dict(self) -> bytes:
        """The encrypted key dictionary (file_dict_file.rs)."""
        with self._mu:
            out = bytearray()
            out += codec.encode_var_u64(self.current_id)
            out += codec.encode_var_u64(len(self.keys))
            for kid, key in sorted(self.keys.items()):
                out += codec.encode_var_u64(kid)
                out += codec.encode_compact_bytes(key)
        return seal(self.master.key, bytes(out))

    @classmethod
    def import_dict(cls, master: MasterKey, sealed: bytes) -> "DataKeyManager":
        raw = unseal(master.key, sealed)
        mgr = cls.__new__(cls)
        mgr.master = master
        mgr._mu = threading.Lock()
        mgr._persist_mu = threading.Lock()
        mgr.keys = {}
        mgr.dict_path = None
        cur, off = codec.decode_var_u64(raw, 0)
        n, off = codec.decode_var_u64(raw, off)
        for _ in range(n):
            kid, off = codec.decode_var_u64(raw, off)
            key, off = codec.decode_compact_bytes(raw, off)
            mgr.keys[kid] = key
        mgr.current_id = cur
        return mgr


class EncryptedEngine(KvEngine):
    """Engine wrapper encrypting every VALUE at rest (keys stay plaintext for
    ordering, like the reference's file-level encryption leaves RocksDB key
    order intact).  Stored value = varint key_id | sealed(value)."""

    def __init__(self, inner: KvEngine, keys_mgr: DataKeyManager):
        self.inner = inner
        self.keys = keys_mgr

    def _enc(self, value: bytes, cur: tuple[int, bytes] | None = None) -> bytes:
        kid, key = cur if cur is not None else self.keys.current()
        return codec.encode_var_u64(kid) + seal(key, value)

    def _dec(self, stored: bytes) -> bytes:
        kid, off = codec.decode_var_u64(stored, 0)
        return unseal(self.keys.by_id(kid), stored[off:])

    def write(self, batch: WriteBatch) -> None:
        # one key fetch per batch: cheaper, and a batch racing a rotation
        # never straddles two data keys
        cur = self.keys.current()
        enc = WriteBatch()
        for op, cf, key, val in batch.ops:
            if op == "put":
                enc.put_cf(cf, key, self._enc(val, cur))
            elif op == "delete":
                enc.delete_cf(cf, key)
            else:
                enc.delete_range_cf(cf, key, val)
        self.inner.write(enc)

    def snapshot(self) -> "EncryptedSnapshot":
        return EncryptedSnapshot(self.inner.snapshot(), self)

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        v = self.inner.get_cf(cf, key)
        return None if v is None else self._dec(v)

    def scan_cf(self, cf, start, end, limit=None, reverse=False):
        for k, v in self.inner.scan_cf(cf, start, end, limit, reverse):
            yield k, self._dec(v)

    def bulk_load(self, cf: str, items):
        cur = self.keys.current()
        self.inner.bulk_load(cf, [(k, self._enc(v, cur)) for k, v in items])


class _DecCursor(Cursor):
    def __init__(self, inner: Cursor, eng: EncryptedEngine):
        self._c = inner
        self._e = eng

    def seek(self, key):
        return self._c.seek(key)

    def seek_for_prev(self, key):
        return self._c.seek_for_prev(key)

    def seek_to_first(self):
        return self._c.seek_to_first()

    def seek_to_last(self):
        return self._c.seek_to_last()

    def next(self):
        return self._c.next()

    def prev(self):
        return self._c.prev()

    def valid(self):
        return self._c.valid()

    def key(self):
        return self._c.key()

    def value(self):
        return self._e._dec(self._c.value())


class EncryptedSnapshot(Snapshot):
    def __init__(self, inner: Snapshot, eng: EncryptedEngine):
        self._snap = inner
        self._e = eng

    def get_cf(self, cf, key):
        v = self._snap.get_cf(cf, key)
        return None if v is None else self._e._dec(v)

    def cursor_cf(self, cf, lower=None, upper=None):
        return _DecCursor(self._snap.cursor_cf(cf, lower, upper), self._e)

    def sequence(self):
        return self._snap.sequence()

    def cf_touched_seq(self, cf):
        return self._snap.cf_touched_seq(cf)
