"""Device-resident per-region column cache with incremental delta apply.

The coprocessor's existing block cache (``cache.py``) is keyed by
``(region, ranges, start_ts, data version)`` — ANY write produces a new key
and the whole region re-decodes from KV bytes.  That leaves scan/selection
DAGs (cost-dominated by rowv2 decode + MVCC resolution) on the 1.0× floor:
the device never helps because every request rebuilds the columns on host.

This module keeps ONE decoded image per ``(region, ranges, schema)``, keyed
for freshness by ``(region_epoch, apply_index)`` — the TCR/Taurus near-data
shape: base data stays resident in the accelerator-friendly format and only
deltas move.

* build: vectorized MVCC range resolve (``MvccBatchScanSource``) + the
  NumPy-batched row decoder materialize the region's visible rows into
  fixed-width column blocks; the evaluators pin them on device on first use.
* hit: same ``apply_index`` ⇒ the engine cannot have changed; serve the
  resident blocks as-is (zero scan, zero decode, zero transfer).
* delta: a newer ``apply_index`` folds the write-through chain's prefix
  (below); where the chain cannot say, ``mvcc_batch.scan_delta`` makes one
  vectorized pass over the CF_WRITE *keys* and finds rows whose version
  fingerprint moved; only those rows re-resolve and re-decode.  Either way
  the rows go into a NEW version of the image, block by block: the blocks
  they touch are copied, the rest shared, pins and all
  (``RegionImage.apply_delta``).
* below the snapshot: a reader another session overtook (its ``start_ts``
  lies below the ``snapshot_ts`` a later reader raised) is a hit whenever the
  image provably holds nothing it may not see — same ``apply_index`` and
  ``start_ts >= max_commit_ts`` (``_hit_fresh_locked``).
* fallback: any other read below the image's snapshot ts, a non-vectorizable
  range, or an over-budget region serves through the existing per-request
  path — the cache only ever degrades to current behavior.

Follower stale serving (docs/stale_reads.md): images built off STALE-read
snapshots need no special handling — a stale snapshot's ``apply_index`` is
guaranteed at/above the RegionReadProgress pair's required index and its
reads sit at/below the paired watermark (``raftkv`` refuses otherwise, and
``endpoint._region_cache_for`` asserts the pairing), so the
``(region_id, epoch, apply_index)`` key already identifies exactly the data
version the watermark covers.  Leader and follower images of one region
therefore never alias to different bytes under one key.

Invalidation: ``raft/store.py`` calls :func:`notify_region_epoch_change` on
split / merge / conf change; the epoch in the key catches anything missed.
Memory: LRU over images + a byte budget bound host AND device residency (a
device pin costs about one host copy per pinned plan signature).

Write-through deltas: the raft apply path
(``raft/store.py`` ``_apply_run`` / ``_exec_data_cmd``) calls
:func:`notify_region_write` with every committed data batch's ops and the
entry's apply index.  The parsed batch (changed handles/values/commit_ts,
deleted handles, lock changes) is appended to the image's PENDING chain; a
warm read folds the chain's prefix up to its own apply index — the writes
committed at or below its ``start_ts`` — and serves WITHOUT any CF_WRITE
scan, leaving later writes pending.  ``scan_delta`` stays as the fallback
whenever emission is off (``apply_emit_write_delta`` failpoint, config), an
op is not vectorizable, or the chain has a gap (detected via the per-region
notify watermark; see docs/write_path.md for the contract).

Concurrency (docs/region_column_cache.md, "Concurrency"): the manager lock
guards the image table and its bookkeeping and is held only briefly; a
repair runs under the image's own lock, so it holds up neither readers of
other images nor the apply path's notifies.  A repair never writes into a
version a reader was handed — it makes a new one — so the evaluator may
read a version's blocks after ``serve`` returns.  ``apply_index`` is
propagated end-to-end: ``RegionSnapshot``
carries the peer's applied index, and the endpoint reads region identity,
epoch and apply index straight off the snapshot — raft-backed deployments
need no context plumbing (explicit context still wins for tests and
embedded engines).
"""

from __future__ import annotations

import contextlib
import copy
import time
import weakref

import numpy as np

from ..analysis import bufsan as _bufsan
from ..analysis.sanitizer import make_rlock
from ..storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE
from ..storage.mvcc import Statistics
from ..storage.mvcc.reader import KeyIsLockedError, _check_lock
from ..storage.txn_types import Key, Lock, Write, WriteType, append_ts, split_ts
from ..util import trace
from . import encoding as _encoding
from . import integrity as _integrity
from .byterows import ByteRows
from .cache import BlockEdit, ColumnBlockCache, _Block
from .datatypes import Column, EvalType
from .mvcc_batch import MvccBatchScanSource, scan_delta
from .table import RowBatchDecoder, decode_record_handles, decode_record_key, record_key

DEFAULT_BYTE_BUDGET = 256 << 20
DEFAULT_MAX_REGIONS = 64
_REBUILD_FRACTION = 0.25  # delta bigger than this fraction of rows ⇒ rebuild
_TOKEN_UNSET = object()  # cache not yet bound to an engine's data_token

_CACHES: "weakref.WeakSet[RegionColumnCache]" = weakref.WeakSet()


@contextlib.contextmanager
def _fold_part(part: str):
    """Time one part of a fold into
    ``tikv_coprocessor_region_cache_fold_part_seconds{part}``.  Not a stage:
    the parts lie inside ``cache.repair``, which keeps the whole fold."""
    from ..util.metrics import REGISTRY

    t0 = time.perf_counter()
    try:
        yield
    finally:
        REGISTRY.histogram(
            "tikv_coprocessor_region_cache_fold_part_seconds",
            "Wall time of the parts of a fold inside cache.repair, by part: "
            "decode, rows, blocks, place",
        ).observe(time.perf_counter() - t0, part=part)


def notify_region_epoch_change(region_id: int, reason: str = "epoch") -> None:
    """Raft-side invalidation hook: a region's epoch moved (split / merge /
    conf change) — every live cache drops its images of that region."""
    for c in list(_CACHES):
        c.invalidate_region(region_id, reason=reason)


def notify_region_write(region_id: int, ops, apply_index: int,
                        get_default=None, token=None) -> None:
    """Write-through hook: a committed data batch applied to ``region_id``
    at ``apply_index``.  ``ops`` are the batch's ``(op, cf, key, val)``
    tuples in MVCC key space (pre data-prefix); ``get_default`` resolves a
    ``CF_DEFAULT`` key for PUT records whose value is not inline;
    ``token`` identifies the emitting engine (region ids are not
    process-unique — each cache only accepts deltas from the engine it
    serves).  Interested caches buffer the parsed delta on their images of
    the region; warm reads fold it in without re-scanning CF_WRITE.  The
    parse (which may read CF_DEFAULT) runs at most ONCE per notify and
    outside every cache lock."""
    memo: list = []

    def parse_once():
        if not memo:
            memo.append(_parse_write_ops(ops, get_default))
        return memo[0]

    for c in list(_CACHES):
        c.apply_write(region_id, parse_once, apply_index, token=token)


def notify_region_write_lost(region_id: int, apply_index: int,
                             token=None) -> None:
    """Write-through hook for a data change of UNKNOWN content (emission
    disabled, snapshot apply, merge catch-up): pending deltas are dropped
    and the notify watermark advances, so reads fall back to ``scan_delta``
    until a read's snapshot catches up past ``apply_index``."""
    for c in list(_CACHES):
        c.note_write_lost(region_id, apply_index, token=token)


def _parse_write_ops(ops, get_default):
    """Parse a committed batch's ops into ``(writes, lock_ops)`` —
    ``writes`` = [(raw_key, commit_ts, value | None-for-delete)] in batch
    order, ``lock_ops`` = [(raw_key, Lock | None-for-delete)] of every
    CF_LOCK change, in batch order.  Returns None when any op is not
    expressible as an incremental change (delete_range, delete on CF_WRITE,
    exotic records, a missing CF_DEFAULT value, an unreadable lock) — the
    caller then degrades to the scan_delta path."""
    writes: list[tuple[bytes, int, bytes | None]] = []
    lock_ops: list[tuple[bytes, Lock | None]] = []
    for op, cf, key, val in ops:
        if cf == CF_LOCK:
            if op not in ("put", "delete"):
                return None
            try:
                lock_ops.append((Key.from_encoded(key).to_raw(),
                                 Lock.from_bytes(val) if op == "put" else None))
            except Exception:  # noqa: BLE001 — undecodable lock key or record
                return None
            continue
        if cf != CF_WRITE:
            continue  # CF_DEFAULT rides along with its CF_WRITE record
        if op != "put":
            return None  # GC / collapse deletes: not an incremental change
        try:
            enc_user, cts = split_ts(key)
            w = Write.from_bytes(val)
            raw = Key.from_encoded(enc_user).to_raw()
        except Exception:  # noqa: BLE001 — malformed record
            return None
        if w.write_type == WriteType.PUT:
            if w.gc_fence is not None:
                return None
            v = w.short_value
            if v is None:
                try:
                    v = get_default(append_ts(enc_user, w.start_ts)) if get_default else None
                except Exception:  # noqa: BLE001 — a faulting engine read
                    v = None  # must degrade, not propagate into apply
                if v is None:
                    return None
            writes.append((raw, int(cts), v))
        elif w.write_type == WriteType.DELETE:
            writes.append((raw, int(cts), None))
        # LOCK / ROLLBACK records change no visible row data: skip.  Their
        # fingerprint drift is repaired by the scan_delta fallback if a
        # reader ever diffs this range again.
    return writes, lock_ops


def _fold_writes(writes) -> dict:
    """Chain writes ``[(apply_index, handle, commit_ts, value | None)]`` as
    the row delta ``RegionImage.apply_delta`` takes: each handle's newest
    write wins."""
    last: dict[int, tuple] = {}
    for _i, h, cts, v in writes:
        if h not in last or cts >= last[h][0]:
            last[h] = (cts, v)
    changed = sorted(h for h, (_c, v) in last.items() if v is not None)
    return {
        "changed_handles": np.array(changed, dtype=np.int64),
        "changed_values": [last[h][1] for h in changed],
        "changed_commit_ts": np.array([last[h][0] for h in changed], dtype=np.int64),
        "deleted_handles": np.array(sorted(h for h, (_c, v) in last.items() if v is None),
                                    dtype=np.int64),
        "max_commit_ts": max(w[2] for w in writes),
    }


def _lock_in(snap, raw: bytes, lock: Lock) -> bool:
    """Whether ``snap`` holds ``lock`` on ``raw`` (a lock removed behind the
    write-through chain's back, by a range destroy, must not block a reader
    forever)."""
    v = snap.get_cf(CF_LOCK, Key.from_raw(raw).encoded)
    return v is not None and Lock.from_bytes(v).ts == lock.ts


class _Held:
    """``with``-exit for a lock already acquired (``_locked``)."""

    __slots__ = ("_lock",)

    def __init__(self, lock):
        self._lock = lock

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._lock.release()
        return False


def _in_ranges(raw: bytes, ranges) -> bool:
    for start, end in ranges:
        if start <= raw < end:
            return True
    return False


def _epoch_of(ctx_epoch) -> tuple[int, int] | None:
    if ctx_epoch is None:
        return None
    if isinstance(ctx_epoch, (tuple, list)) and len(ctx_epoch) == 2:
        return (int(ctx_epoch[0]), int(ctx_epoch[1]))
    conf_ver = getattr(ctx_epoch, "conf_ver", None)
    version = getattr(ctx_epoch, "version", None)
    if conf_ver is None or version is None:
        return None
    return (int(conf_ver), int(version))


def schema_sig(columns_info) -> tuple:
    return tuple(
        (
            c.col_id,
            c.ftype.eval_type,
            c.ftype.decimal,
            c.ftype.flag,
            bool(c.ftype.is_unsigned),
            bool(c.is_pk_handle),
            c.default_value,
        )
        for c in columns_info
    )


_STATE_KEYS = ("block_cache", "rows", "fp_value", "fp_integrity", "fp_valid",
               "nbytes", "apply_index", "max_commit_ts", "hidden_ct", "reserved",
               "encodings")
_BASE_EVERY = 256   # chain writes folded between two kept base versions
_BASES_KEPT = 4
_ROW_DTYPES = {"h": np.int64, "cts": np.int64, "fp": np.uint64, "nb": np.int64}


class _Rows:
    """One version's per-row arrays — handle (``h``), commit_ts (``cts``),
    integrity fingerprint (``fp``) and (key, value) bytes (``nb``) — as one
    piece per block, beside the block: a fold replaces the pieces of the
    blocks it changes and shares the others with the previous version, as it
    shares the blocks.  ``fp``/``nb`` are None where the fingerprint plane is
    off.  The whole-image arrays are made on first use (``whole``), for the
    readers off the warm path: the scrubber, the CF_WRITE walk, a repack."""

    __slots__ = ("h", "cts", "fp", "nb", "n", "_index", "_whole")

    def __init__(self, h: list, cts: list, fp: list | None = None,
                 nb: list | None = None):
        self.h, self.cts, self.fp, self.nb = h, cts, fp, nb
        self.n = sum(len(a) for a in h)
        self._index = None
        self._whole: dict = {}

    @classmethod
    def split(cls, counts, h, cts, fp=None, nb=None) -> "_Rows":
        """Whole-image arrays cut into pieces of ``counts`` rows (views),
        the arrays kept as the version's whole ones."""
        cuts = np.cumsum(counts)[:-1]

        def cut(a):
            return None if a is None else (np.split(a, cuts) if len(counts) else [])

        rows = cls(cut(h), cut(cts), cut(fp), cut(nb))
        if len(counts):
            rows._whole = {name: a for name, a in zip(("h", "cts", "fp", "nb"), (h, cts, fp, nb))
                           if a is not None}
        return rows

    def whole(self, name: str) -> np.ndarray:
        got = self._whole.get(name)
        if got is None:
            parts = getattr(self, name) or []
            got = self._whole[name] = (np.concatenate(parts) if parts
                                       else np.empty(0, dtype=_ROW_DTYPES[name]))
        return got

    def locate(self, hs: np.ndarray):
        """``(positions, found)``: where each handle of ``hs`` is, or would
        go, among the whole image's rows (``np.searchsorted`` over them), and
        whether it is there — looked up in the one block it can lie in, by
        the index of each non-empty block's first handle."""
        if self._index is None:
            ne = np.array([b for b, a in enumerate(self.h) if len(a)], dtype=np.int64)
            self._index = (ne, np.array([self.h[b][0] for b in ne], dtype=np.int64),
                           np.concatenate([[0], np.cumsum([len(a) for a in self.h])]))
        ne, first, off = self._index
        pos = np.zeros(len(hs), dtype=np.int64)
        found = np.zeros(len(hs), dtype=bool)
        if not len(ne) or not len(hs):
            return pos, found
        k = np.searchsorted(first, hs, side="right") - 1
        for kk in np.unique(k[k >= 0]).tolist():
            b = int(ne[kk])
            sel = k == kk
            piece, want = self.h[b], hs[sel]
            lp = np.searchsorted(piece, want)
            pos[sel] = off[b] + lp
            found[sel] = piece[np.minimum(lp, len(piece) - 1)] == want
        return pos, found


class _Room:
    """Spare rows behind the tail of an image's block arrays.  An append at
    a block's end writes past the rows of every version that holds the
    array (each holds a view that ends where its own rows end), so no
    version is written and the block is not copied.  It may do so only from
    the array that ends where the buffer's rows end (``ends``); any other
    (an older version, a fork for one reader, a block that lost its tail)
    copies into a buffer of its own.  Callers hold the image's lock."""

    def __init__(self, rows: int):
        self.rows = rows
        self.ends: dict = {}  # key -> (buffer, rows of it some version holds)

    def append(self, key, arr: np.ndarray, new) -> np.ndarray:
        new = np.asarray(new, dtype=arr.dtype)
        k = len(new)
        got = self.ends.get(key)
        if got is not None and len(arr) and arr.base is got[0]:
            buf, end = got
            at = (arr.__array_interface__["data"][0]
                  - buf.__array_interface__["data"][0]) // buf.itemsize
            if at + len(arr) == end and end + k <= len(buf):
                buf[end:end + k] = new
                self.ends[key] = (buf, end + k)
                return buf[at:end + k]
        buf = np.empty(max(self.rows, len(arr) + k), dtype=arr.dtype)
        buf[:len(arr)] = arr
        buf[len(arr):len(arr) + k] = new
        self.ends[key] = (buf, len(arr) + k)
        return buf[:len(arr) + k]

    def nbytes(self) -> int:
        """Bytes of the spare rows, past every version's, that the room's
        buffers hold: a version's own rows are counted with its blocks."""
        return sum((len(buf) - end) * buf.itemsize for buf, end in self.ends.values())


class _Splice:
    """Rows ``dpos`` out of an ``n``-row array and new rows in at ``at``
    (positions in the array without them).  The shapes the refresh
    functions make cost nothing of the array's size: a run of deletes at its
    head or its end is a view, and an append at its end writes into the
    ``_Room`` behind it where one is given."""

    def __init__(self, n: int, dpos: np.ndarray, at: np.ndarray):
        self.n = n
        self.dpos = np.sort(dpos)
        self.at = at
        self.span = (len(self.dpos) and int(self.dpos[-1]) - int(self.dpos[0]) + 1
                     == len(self.dpos))
        self.tail = bool(len(at)) and bool((at >= n - len(dpos)).all())

    def __call__(self, arr: np.ndarray, new, room: _Room | None = None,
                 key=None) -> np.ndarray:
        if len(self.dpos):
            a, b = int(self.dpos[0]), int(self.dpos[-1]) + 1
            if self.span and a == 0:
                arr = arr[b:]
            elif self.span and b == len(arr):
                arr = arr[:a]
            elif self.span:
                arr = np.concatenate([arr[:a], arr[b:]])
            else:
                arr = np.delete(arr, self.dpos)
        if not len(self.at):
            return arr
        if self.tail:
            if room is not None:
                return room.append(key, arr, new)
            return np.concatenate([arr, np.asarray(new, dtype=arr.dtype)])
        return np.insert(arr, self.at, new)


class _Plan:
    """A block-local fold's plan: the new version's block list (the empty
    blocks a first tail append reserves included), each changed block's
    ``_Edit``, and the indexes of the blocks that are new objects."""

    __slots__ = ("blocks", "edits", "changed")

    def __init__(self, blocks: list, edits: dict):
        self.blocks, self.edits = blocks, edits
        self.changed = set(edits)


class _Edit:
    """One block's share of a fold (``RegionImage._plan``), in rows of the
    block: updated rows (``upd``, with their delta indexes ``upd_idx``),
    deleted rows (``dels``, sorted), and inserts at ``at`` (positions among
    the rows the deletes leave, with their delta indexes ``ins_idx``)."""

    __slots__ = ("b", "old_nv", "new_nv", "upd", "upd_idx", "dels", "at", "ins_idx")

    def __init__(self, b: int, old_nv: int, upd, upd_idx, dels, at, ins_idx):
        self.b, self.old_nv = b, old_nv
        self.upd, self.upd_idx = upd, upd_idx
        self.dels = np.sort(dels)
        # ``at`` comes as positions among the block's rows before the deletes
        self.at = at - np.searchsorted(self.dels, at, side="left")
        self.ins_idx = ins_idx
        self.new_nv = old_nv - len(self.dels) + len(self.at)

    def device(self) -> BlockEdit:
        """The edit as the new block's rows hold it (``BlockEdit``): a kept
        row moves up past the deletes before it and down past the inserts at
        or before it; the j-th insert lands at ``at[j] + j``."""
        ins = self.at + np.arange(len(self.at))
        k = self.upd - np.searchsorted(self.dels, self.upd, side="left")
        upd = k + np.searchsorted(self.at, k, side="right")
        return BlockEdit(self.old_nv, self.new_nv, self.dels, ins,
                         np.sort(np.concatenate([ins, upd])))


class _Repack(Exception):
    """A block-local fold cannot hold the delta: repack the whole image."""


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _empty_col(col: Column) -> Column:
    """A zero-row column in ``col``'s representation (encoding, lane,
    dictionary), for the empty blocks an image reserves."""
    E = _encoding.EncodedColumn
    if isinstance(col, E):
        if col.kind == "bp":
            return E(col.eval_type, col.frac, "bp", 0,
                     packed=np.empty(0, dtype=col.packed.dtype), ref=col.ref,
                     nulls=np.empty(0, dtype=bool))
        return E(col.eval_type, col.frac, "rle", 0,
                 run_values=np.empty(0, dtype=col.run_values.dtype),
                 run_ends=np.empty(0, dtype=np.int64),
                 run_nulls=np.empty(0, dtype=bool), k_cap=col.k_cap)
    return Column(col.eval_type, np.empty(0, dtype=np.asarray(col.data).dtype),
                  np.empty(0, dtype=bool), col.frac, col.dictionary)


def _empty_block(template: _Block) -> _Block:
    return _Block([_empty_col(c) for c in template.cols], 0)


def _encode_like(col: Column, d: np.ndarray, nl: np.ndarray):
    """Host values ``d`` (nulls ``nl``) in ``col``'s encoding and lane, or
    None where they do not fit it (a bitpacked value off its lane, more runs
    than the column's run capacity, a code past its narrowed lane)."""
    E = _encoding.EncodedColumn
    if isinstance(col, E):
        a = d.astype(np.int64, copy=False)
        live = ~nl
        if col.kind == "bp":
            info = np.iinfo(col.packed.dtype)
            rel = a - col.ref
            if live.any() and (int(rel[live].min()) < info.min
                               or int(rel[live].max()) > info.max):
                return None
            return E(col.eval_type, col.frac, "bp", len(a),
                     packed=np.where(live, rel, 0).astype(col.packed.dtype),
                     ref=col.ref, nulls=nl)
        n = len(a)
        change = np.ones(n, dtype=bool)
        if n > 1:
            change[1:] = (a[1:] != a[:-1]) | (nl[1:] != nl[:-1])
        starts = np.flatnonzero(change)
        if len(starts) > col.k_cap:
            return None
        ends = np.append(starts[1:], n).astype(np.int64)
        return E(col.eval_type, col.frac, "rle", n,
                 run_values=a[starts].astype(col.run_values.dtype), run_ends=ends,
                 run_nulls=nl[starts].copy(), k_cap=col.k_cap)
    d0 = np.asarray(col.data)
    if (col.dictionary is not None and d0.dtype != object and d0.dtype.kind in "iu"
            and len(d) and int(d.max()) > np.iinfo(d0.dtype).max):
        return None
    return Column(col.eval_type, d.astype(d0.dtype, copy=False), nl, col.frac,
                  col.dictionary)


class RegionImage:
    """One region's decoded, device-pinnable columnar state."""

    def __init__(self, key, epoch, schema, block_rows: int):
        self.key = key
        self.epoch = epoch
        self.schema = schema
        self.block_rows = block_rows
        # overload plane (docs/robustness.md "Overload"): the tenant whose
        # request built this image — HBM partition accounting and the
        # memory-pressure ladder key on it
        self.tenant = "default"
        # serializes this image's repairs and the reads that judge it; the
        # cache's manager lock is never waited for while this one is held
        # except briefly, and never the other way round (Concurrency)
        self.mu = make_rlock("copr.region_image")
        self.dropped = False  # invalidated or evicted: no longer resident
        self.apply_index = -1
        self.snapshot_ts = -1  # the highest start_ts the image has served
        # the image holds the engine's rows at apply_index as a read at any
        # timestamp in [max_commit_ts, hidden_ct) sees them: max_commit_ts is
        # the newest commit it holds, hidden_ct the oldest version at
        # apply_index it does not hold and no pending batch carries (None:
        # there is none)
        self.max_commit_ts = 0
        self.hidden_ct: int | None = None
        # the version's per-row arrays, one piece per block (_Rows), and the
        # spare rows behind the blocks' tails that appends write into
        self.rows = _Rows([], [])
        self._room = _Room(block_rows)
        self.block_cache = ColumnBlockCache(key=key)
        self.decoder = RowBatchDecoder(schema)
        self.nbytes = 0
        # compressed residency (docs/compressed_columns.md): whether fill
        # ran the encoding stats pass, and which columns it encoded
        self.encode_enabled = False
        self.encodings: dict[int, str] = {}
        # bytes->code maps for dict-encoded columns, built on first delta
        self._dict_maps: dict[int, tuple] = {}
        # the first append at the tail reserved empty blocks behind it
        self.reserved = False
        # write-through chain (the cache's manager lock guards it): every
        # committed batch that touched these ranges since apply index
        # chain_from, in apply order, as (apply_index, [(handle, commit_ts,
        # value | None)], [(raw key, Lock | None)]).  Batches at or below
        # apply_index hold only writes above the image's timestamps (held
        # back for later readers); chain_from None = gapped, and chain_gap
        # says why a read cannot fold
        self.chain: list = []
        self.chain_from: int | None = None
        self.chain_gap = "gapped"
        # every CF_LOCK entry in range at apply_index (raw key -> Lock), or
        # None where that is not known
        self.locks: dict | None = None
        # earlier versions a reader below max_commit_ts is folded onto
        # (``_serve_base``): [(state, position in ``since``)], oldest first,
        # and every chain write folded in since the oldest, in fold order
        self.bases: list = []
        self.since: list = []
        # a write-through batch touched CF_LOCK in range: the next warm
        # serve must check locks even at an unchanged start_ts.  Cleared
        # only when a lock-free check ran on a snapshot at/after the batch
        # that dirtied it (locks_dirty_at) — an older snapshot proves
        # nothing about that batch's lock.
        self.locks_dirty = False
        self.locks_dirty_at = 0
        # lock-free memo: the engine sequence of the newest snapshot whose
        # CF_LOCK scan over this image's ranges met no lock at all, or None.
        # A reader whose snapshot provably reads the same CF_LOCK skips the
        # scan at any start_ts (_check_locks).
        self.lock_free_seq: int | None = None
        # integrity fingerprint (docs/integrity.md): one crc64 per row over
        # the RAW (key, value) chain — byte-identical to the coprocessor
        # Checksum entry — plus a commit_ts-mixed variant, both folded
        # incrementally by every delta apply.  fp_valid=False (multi-table
        # ranges, unhashable delta keys) disables the whole plane for this
        # image: the scrubber reports it unverifiable, checksum serves cold.
        self.fp_valid = False
        self.table_id: int | None = None
        self.fp_value = 0      # fold(row_fp): the warm Checksum answer
        self.fp_integrity = 0  # fold(mix_fp(row_fp, row_commit_ts))

    @property
    def n_rows(self) -> int:
        return self.rows.n

    # the whole-image row arrays (``_Rows.whole``): none of their readers is
    # on the warm path
    @property
    def handles(self) -> np.ndarray:
        return self.rows.whole("h")

    @property
    def row_commit_ts(self) -> np.ndarray:
        return self.rows.whole("cts")

    @property
    def row_fp(self) -> np.ndarray:
        return self.rows.whole("fp")

    @property
    def row_nbytes(self) -> np.ndarray:
        return self.rows.whole("nb")

    @property
    def wt_pending(self) -> dict | None:
        """The pending chain's row changes, newest per handle (a view for
        debugging surfaces), or None where no batch is pending."""
        writes = [w for _i, ws, _l in self.chain for w in ws]
        if self.chain_from is None or not writes:
            return None
        changed, deleted = {}, set()
        for h, cts, v in writes:
            if v is None:
                changed.pop(h, None)
                deleted.add(h)
            else:
                deleted.discard(h)
                changed[h] = (v, cts)
        return {"base": self.chain_from, "apply_index": self.chain[-1][0],
                "changed": changed, "deleted": deleted,
                "max_ct": max(c for _h, c, _v in writes)}

    def _recount(self) -> None:
        # handles and commit_ts, and the room's spare rows
        self.nbytes = (self.block_cache.nbytes() + 16 * self.rows.n
                       + self._room.nbytes())

    # -- build -------------------------------------------------------------

    def fill(self, handles: np.ndarray, values: list[bytes], cts: np.ndarray,
             max_commit_ts: int, apply_index: int, start_ts: int,
             raw_keys: list[bytes] | None = None, encode: bool = False,
             hidden_ct: int | None = None) -> None:
        with trace.stage("fill.fingerprint"):
            fp, nb = self._init_fingerprint(handles, values, raw_keys)
        cache = self.block_cache
        cache.clear_blocks()
        br = self.block_rows
        with trace.stage("fill.decode") as st:
            paths = set()
            for s in range(0, len(values), br):
                e = min(s + br, len(values))
                cols = self.decoder.decode(handles[s:e], values[s:e])
                paths.add(self.decoder.path)
                cache.add(cols, e - s)
            st.tag(rows=len(values), path=",".join(sorted(paths)))
        cache.filled = True
        cache.takes_folds = True
        self.rows = _Rows.split([b.n_valid for b in cache.blocks], handles, cts, fp, nb)
        self._room = _Room(self.block_rows)
        if self.fp_valid:
            self.fp_value = _integrity.fold(fp)
            self.fp_integrity = _integrity.fold(_integrity.mix_fp(fp, cts))
        # fill-time stats pass (docs/compressed_columns.md): eligible
        # columns become ENCODED residents — dict codes narrowed, runs
        # collapsed to RLE, narrow ranges bitpacked — and the recount below
        # accounts the budget in ENCODED bytes, which is what multiplies
        # warm capacity.  Fingerprints above hash the LOGICAL rows, so the
        # integrity plane cross-checks encoded and decoded images alike.
        self.encode_enabled = bool(encode)
        if encode:
            self.encodings = _encoding.encode_blocks(cache, self.schema)
        self.apply_index = apply_index
        self.snapshot_ts = start_ts
        self.max_commit_ts = max_commit_ts
        self.hidden_ct = hidden_ct
        self._recount()
        self.rebase()

    # -- integrity fingerprint ---------------------------------------------

    def _init_fingerprint(self, handles, values, raw_keys):
        """The per-row integrity hashes and (key, value) bytes at build time,
        or ``(None, None)`` with the plane off.  Delta folds reconstruct raw
        keys from (table_id, handle), so a single-table range is required —
        raw record keys ARE (table_id, handle) encoded, making the
        reconstruction exact."""
        self.fp_valid = False
        self.table_id = None
        self.fp_value = self.fp_integrity = 0
        try:
            if raw_keys is None:
                self.table_id = self._table_id_from_ranges()
                if self.table_id is None:
                    return None, None
                raw_keys = [record_key(self.table_id, int(h)) for h in handles]
            elif len(raw_keys):
                tid_first = decode_record_key(raw_keys[0])[0]
                # keys are sorted: same first/last table prefix = one table
                if decode_record_key(raw_keys[-1])[0] != tid_first:
                    return None, None
                self.table_id = tid_first
            else:
                self.table_id = self._table_id_from_ranges()
            keys, vals = ByteRows.of(raw_keys), ByteRows.of(values)
            fp = _integrity.row_checksums(keys, vals)
            nb = keys.lens + vals.lens
        except Exception:  # noqa: BLE001 — exotic keys: plane off, serve on
            return None, None
        self.fp_valid = True
        return fp, np.asarray(nb, dtype=np.int64)

    def _table_id_from_ranges(self) -> int | None:
        from ..util import codec as _codec

        tids = set()
        for start, _end in self.key[1]:
            if len(start) < 9 or start[:1] != b"t":
                return None
            tids.add(_codec.decode_i64(start, 1))
        return tids.pop() if len(tids) == 1 else None

    def _invalidate_fp(self) -> None:
        """An unhashable delta landed: the fingerprint plane turns off for
        this image (it would otherwise drift silently)."""
        self.fp_valid = False
        self.rows = _Rows(self.rows.h, self.rows.cts)
        self.fp_value = self.fp_integrity = 0

    def checksum_parts(self) -> tuple[int, int, int] | None:
        """(checksum, total_kvs, total_bytes) exactly as the CPU-oracle
        Checksum scan would answer over this image's rows, or None when the
        fingerprint plane is off for this image."""
        if not self.fp_valid:
            return None
        return self.fp_value, self.n_rows, sum(int(a.sum()) for a in self.rows.nb)

    # -- versions ----------------------------------------------------------

    def state(self) -> dict:
        """The version as it stands: what ``apply_delta`` replaces (it never
        writes into these arrays or blocks), so a copy of the references
        keeps the version whole."""
        return {k: getattr(self, k) for k in _STATE_KEYS}

    def rebase(self) -> None:
        """The version as it stands is the only base; nothing folded since."""
        self.bases = [(self.state(), 0)]
        self.since = []

    def note_folded(self, writes) -> None:
        """``writes`` [(apply_index, handle, commit_ts, value)] were folded
        into the image: keep them, and every ``_BASE_EVERY`` of them the
        version they made as one more base (the oldest beyond
        ``_BASES_KEPT`` goes, with the writes before the next one)."""
        self.since.extend(writes)
        if len(self.since) - self.bases[-1][1] >= _BASE_EVERY:
            self.bases.append((self.state(), len(self.since)))
            if len(self.bases) > _BASES_KEPT:
                cut = self.bases[1][1]
                self.since = self.since[cut:]
                self.bases = [(st, pos - cut) for st, pos in self.bases[1:]]

    def fork(self, state: dict, delta: dict):
        """``delta`` folded into the version ``state`` — a new block cache
        for one reader; the image is left as it stands."""
        shell = copy.copy(self)
        shell.__dict__.update(state)
        shell.apply_delta(delta, fork=True)
        return shell.block_cache

    # -- delta -------------------------------------------------------------

    def apply_delta(self, delta: dict, fork: bool = False) -> int:
        """Fold a row delta (``mvcc_batch.scan_delta``'s shape, which the
        write-through chain's fold builds too) into a NEW version of the
        image; returns the rows touched.  The caller holds the image's lock
        and keeps the version bookkeeping (apply index, timestamps).

        Copy-on-write, block by block, in four parts (``_fold_part``):
        ``decode`` decodes and fingerprints the delta's rows; ``rows`` finds
        each row's block through the index of the blocks' first handles and,
        once the blocks are edited, edits the changed blocks' pieces of the
        per-row arrays (``_Rows``); ``blocks`` edits the changed blocks'
        columns in their own encodings; ``place`` re-places the changed rows
        on the device (``ColumnBlockCache.successor``).  An update
        rewrites its rows in a copy of its block, a delete drops its rows
        from its block (the block's ``n_valid`` shrinks: every program reads
        ``n_valid`` per block; a run at the block's head is a view), an
        insert goes into its block where that has room, and one past the
        image's last handle into the tail block and the empty blocks behind
        it, written past the rows every version holds (``_Room``).  The
        image's first tail append reserves room for as many rows again, the
        block count rounded up to a power of two, so the block count (part
        of every program's key) moves once and not with every append.
        Untouched blocks and their row pieces are shared with the previous
        version, device pins and all, and a reader that holds the previous
        version keeps it whole.  An insert into a full block, a value its
        column's encoding cannot hold, or a new dictionary entry repacks the
        whole image into the new version instead (``_repack``).  ``fork``: a
        version for one reader (``fork``), which writes into no room and
        leaves the pins' accounting to the image's own."""
        ch = np.asarray(delta["changed_handles"], dtype=np.int64)
        dh = np.asarray(delta["deleted_handles"], dtype=np.int64)
        if not len(ch) + len(dh):
            return 0
        values = delta["changed_values"]
        cts = np.asarray(delta["changed_commit_ts"], dtype=np.int64)
        with _fold_part("decode"):
            cols = self.decoder.decode(ch, values) if len(ch) else None
            # fingerprint fold (docs/integrity.md): hash the delta rows off the
            # RAW value chain — the fold tracks what the image will CONTAIN, the
            # scrubber's oracle says what it SHOULD contain
            new_fp = new_nb = None
            if self.fp_valid and len(ch):
                try:
                    dkeys = [record_key(self.table_id, int(h)) for h in ch]
                    new_fp = _integrity.row_checksums(dkeys, values)
                    new_nb = np.fromiter((len(k) + len(v) for k, v in zip(dkeys, values)),
                                         dtype=np.int64, count=len(ch))
                except Exception:  # noqa: BLE001 — unhashable: plane off
                    self._invalidate_fp()
            if self.fp_valid and new_fp is None:
                new_fp = np.empty(0, dtype=np.uint64)
                new_nb = np.empty(0, dtype=np.int64)
        room = None if fork else self._room
        with _fold_part("rows"):
            dpos = np.empty(0, dtype=np.int64)
            if len(dh):
                dp, found = self.rows.locate(dh)
                dpos = dp[found]
            pos, upd = self.rows.locate(ch)
        with _fold_part("blocks"):
            plan = self._plan(pos, upd, dpos)
            blocks = None
            if plan is not None:
                try:
                    blocks = self._edit_blocks(plan, ch, cols, room)
                except _Repack:
                    pass
            if blocks is None:
                new_cache = self._repack(ch, cols, upd, dpos, {})
        with _fold_part("rows"):
            if blocks is None:
                self._repack_rows(ch, cts, pos, upd, dpos, new_fp, new_nb, new_cache)
                if not fork:
                    self._room = _Room(self.block_rows)
            else:
                self._edit_rows(plan, ch, cts, new_fp, new_nb, room)
        if blocks is not None:
            with _fold_part("place"):
                old = self.block_cache
                edits = {b: e.device() for b, e in plan.edits.items() if b < len(old.blocks)}
                new_cache = old.successor(blocks, plan.changed, account=not fork,
                                          edits=edits)
        self.block_cache = new_cache
        self._recount()
        return len(ch) + len(dh)

    def _fold_fp(self, gone_fp, gone_cts, add_fp, add_cts) -> None:
        """The fingerprints less the rows a fold takes out, plus the rows it
        puts in (XOR folds: order does not matter)."""
        cat = np.concatenate
        gone_fp, add_fp = cat(gone_fp), cat(add_fp)
        gone_cts, add_cts = cat(gone_cts), cat(add_cts)
        self.fp_value ^= _integrity.fold(gone_fp) ^ _integrity.fold(add_fp)
        self.fp_integrity ^= (_integrity.fold(_integrity.mix_fp(gone_fp, gone_cts))
                              ^ _integrity.fold(_integrity.mix_fp(add_fp, add_cts)))

    def _edit_rows(self, plan, ch, cts, new_fp, new_nb, room) -> None:
        """The new version's per-row pieces: each changed block's pieces
        edited as its columns were, every other piece shared; and the
        fingerprints folded."""
        names = ("h", "cts", "fp", "nb") if self.fp_valid else ("h", "cts")
        pieces = {}
        for name in names:
            got = list(getattr(self.rows, name))
            got += [np.empty(0, dtype=_ROW_DTYPES[name])] * (len(plan.blocks) - len(got))
            pieces[name] = got
        new = {"h": ch, "cts": cts, "fp": new_fp, "nb": new_nb}
        gone_fp, gone_cts, add_fp, add_cts = [], [], [], []
        for b, e in plan.edits.items():
            if self.fp_valid:
                gone = np.concatenate([e.upd, e.dels])
                gone_fp.append(pieces["fp"][b][gone])
                gone_cts.append(pieces["cts"][b][gone])
                add_fp += [new_fp[e.upd_idx], new_fp[e.ins_idx]]
                add_cts += [cts[e.upd_idx], cts[e.ins_idx]]
            splice = _Splice(e.old_nv, e.dels, e.at)
            for name in names:
                arr = pieces[name][b]
                if len(e.upd) and name != "h":
                    arr = arr.copy()
                    arr[e.upd] = new[name][e.upd_idx]
                pieces[name][b] = (splice(arr, new[name][e.ins_idx], room, (b, name))
                                   if len(e.at) else splice(arr, None))
        if self.fp_valid:
            self._fold_fp(gone_fp, gone_cts, add_fp, add_cts)
        self.rows = _Rows(*(pieces.get(name) for name in ("h", "cts", "fp", "nb")))

    def _repack_rows(self, ch, cts, pos, upd, dpos, new_fp, new_nb, new_cache) -> None:
        """``_edit_rows`` for a repacked version: the whole-image arrays
        edited, then cut into pieces as the new blocks hold the rows."""
        old = self.rows
        h = old.whole("h")
        ins = ~upd
        upd_pos = pos[upd]
        whole = {"cts": old.whole("cts")}
        if self.fp_valid:
            whole["fp"], whole["nb"] = old.whole("fp"), old.whole("nb")
            gone = np.concatenate([upd_pos, dpos])
            self._fold_fp([whole["fp"][gone]], [whole["cts"][gone]],
                          [new_fp[upd], new_fp[ins]], [cts[upd], cts[ins]])
        new = {"cts": cts, "fp": new_fp, "nb": new_nb}
        splice = _Splice(len(h), dpos, np.searchsorted(np.delete(h, dpos), ch[ins])
                         if len(dpos) else pos[ins])
        out = {"h": splice(h, ch[ins])}
        for name, arr in whole.items():
            if len(upd_pos):
                arr = arr.copy()
                arr[upd_pos] = new[name][upd]
            out[name] = splice(arr, new[name][ins])
        self.rows = _Rows.split([b.n_valid for b in new_cache.blocks], out["h"],
                                out["cts"], out.get("fp"), out.get("nb"))

    def _code_of(self, ci: int, blocks, value: bytes, grow: dict | None) -> int:
        """Image dictionary code for ``value`` on column ``ci``.  An unseen
        value raises ``_Repack``, or with ``grow`` gets the next code of the
        grown dictionary ``_repack`` gives every block."""
        dictionary = blocks[0].cols[ci].dictionary
        dmap = self._dict_maps.get(ci)
        if dmap is None or dmap[0] is not dictionary:
            dmap = self._dict_maps[ci] = (
                dictionary, {bytes(v): j for j, v in enumerate(dictionary)})
        code = dmap[1].get(value)
        if code is not None:
            return code
        if grow is None:
            raise _Repack
        extra = grow.setdefault(ci, [])
        if value in extra:
            return len(dictionary) + extra.index(value)
        extra.append(value)
        return len(dictionary) + len(extra) - 1

    def _delta_columns(self, blocks, cols, n: int, grow=None):
        """Per image column, the changed rows' (values, nulls) in the
        image's host representation: a numeric column at once, a bytes or
        dictionary one row by row (a dictionary column's rows as the image's
        codes, ``_code_of``)."""
        out = []
        for ci in range(len(self.schema)):
            col = cols[ci]
            image_col = blocks[0].cols[ci]
            nls = np.asarray(col.nulls, dtype=bool)
            data = None if col.is_dict_encoded or image_col.is_dict_encoded else col.data
            if isinstance(data, np.ndarray) and data.dtype.kind in "iuf":
                out.append((np.where(nls, 0, data), nls))
                continue
            coded = image_col.is_dict_encoded
            if isinstance(image_col, _encoding.EncodedColumn):
                # int-family lanes by construction — and the ``.data`` probe
                # below would permanently cache a full decode the encoded byte
                # budget never accounted for
                obj_col = False
            elif isinstance(image_col.data, np.ndarray):
                obj_col = image_col.data.dtype == object
            else:
                obj_col = (self.schema[ci].ftype.eval_type in (EvalType.BYTES, EvalType.JSON)
                           and not coded)
            null_value = b"" if obj_col and not coded else 0
            vals = []
            for v, nl in zip(col.decoded().data if col.is_dict_encoded else col.data, nls):
                if nl:
                    vals.append(null_value)
                elif coded:
                    vals.append(self._code_of(ci, blocks, bytes(v), grow))
                else:
                    vals.append(v)
            out.append((vals, nls))
        return out

    def _plan(self, pos, upd, dpos):
        """The block-local fold's plan (``apply_delta``): for each block the
        delta touches, its ``_Edit``, with the empty blocks a first tail
        append reserves; or None where the image has no blocks or an insert
        finds no room in its block."""
        blocks = list(self.block_cache.blocks)
        if not blocks:
            return None
        br = self.block_rows
        n = self.n_rows
        nv = np.array([b.n_valid for b in blocks], dtype=np.int64)
        off = np.concatenate([[0], np.cumsum(nv)])
        upd_idx = np.flatnonzero(upd)
        ins_idx = np.flatnonzero(~upd)
        inner, tail = ins_idx[pos[ins_idx] < n], ins_idx[pos[ins_idx] >= n]
        # a position's block: the last one that starts at or before it
        ub, db, ib = (np.searchsorted(off, p, side="right") - 1
                      for p in (pos[upd_idx], dpos, pos[inner]))
        final = nv.copy()
        np.subtract.at(final, db, 1)
        np.add.at(final, ib, 1)
        if (final > br).any():
            return None
        tb = np.empty(0, dtype=np.int64)
        if len(tail):
            last = int(np.flatnonzero(nv)[-1]) if nv.any() else 0
            n_after = n - len(dpos) + len(ins_idx)
            free = int((br - final[last:]).sum())
            if not self.reserved or free < len(tail):
                # room for as many rows again, the block count a power of two
                want = max(len(blocks), _pow2(-(-2 * n_after // br)))
                free += (want - len(blocks)) * br
                while free < len(tail):
                    free += want * br
                    want *= 2
                blocks += [_empty_block(blocks[0]) for _ in range(want - len(blocks))]
                self.reserved = True
            room = np.concatenate([br - final[last:], np.full(len(blocks) - len(nv), br)])
            tb = last + np.searchsorted(np.cumsum(room), np.arange(len(tail)), side="right")
        edits = {}
        for b in sorted(set(ub.tolist()) | set(db.tolist()) | set(ib.tolist())
                        | set(tb.tolist())):
            # a reserved block holds no row yet: only tail rows land in it
            old_nv, o = (int(nv[b]), int(off[b])) if b < len(nv) else (0, n)
            at = np.concatenate([pos[inner[ib == b]] - o,
                                 np.full(int((tb == b).sum()), old_nv)]).astype(np.int64)
            edits[b] = _Edit(b, old_nv, pos[upd_idx[ub == b]] - o, upd_idx[ub == b],
                             dpos[db == b] - o, at,
                             np.concatenate([inner[ib == b], tail[tb == b]]))
        plan = _Plan(blocks, edits)
        plan.changed.update(range(len(nv), len(blocks)))
        return plan

    def _edit_blocks(self, plan, ch, cols, room):
        """The plan's blocks with each changed block's columns edited (new
        block objects), the rest shared; raises ``_Repack`` where a column's
        lane cannot hold the new values."""
        blocks = list(plan.blocks)
        n_ch = len(ch)
        dvals = self._delta_columns(blocks, cols, n_ch) if n_ch else None
        for b, e in plan.edits.items():
            new_cols = []
            for ci, col in enumerate(blocks[b].cols):
                c = self._edit_column(ci, col, e, ch, dvals, room)
                if c is None:
                    raise _Repack
                new_cols.append(c)
            blocks[b] = _Block(new_cols, e.new_nv)
        return blocks

    def _edit_column(self, ci: int, col: Column, e: "_Edit", ch, dvals, room=None):
        """One block column with the block's edit applied, in the column's
        own encoding and lane (a bitpacked or coded column is edited in its
        narrow payload); None where the lane cannot hold the new values."""
        E = _encoding.EncodedColumn
        if isinstance(col, E) and col.kind == "rle":
            return self._edit_decoded(ci, col, e, ch, dvals)
        if self.schema[ci].is_pk_handle:
            def new_vals(idx):
                return ch[idx].astype(np.int64), np.zeros(len(idx), dtype=bool)
        else:
            vals, nls = dvals[ci] if dvals is not None else ((), ())

            def new_vals(idx):
                if isinstance(vals, np.ndarray):
                    return vals[idx], nls[idx]
                v = np.empty(len(idx), dtype=_encoding.host_dtype(col))
                for j, i in enumerate(idx):
                    v[j] = vals[i]
                return v, nls[idx]
        if isinstance(col, E):
            stored, nl = col.packed, col._nulls
            info = np.iinfo(stored.dtype)

            def lane(v, vn):
                live = ~vn
                rel = np.asarray(v).astype(np.int64) - col.ref
                if live.any() and (int(rel[live].min()) < info.min
                                   or int(rel[live].max()) > info.max):
                    raise _Repack
                return np.where(live, rel, 0).astype(stored.dtype)
        else:
            stored, nl = np.asarray(col.data), np.asarray(col.nulls, dtype=bool)
            coded = (col.dictionary is not None and stored.dtype != object
                     and stored.dtype.kind in "iu")

            def lane(v, vn):
                v = np.asarray(v)
                if coded and len(v) and int(v.max()) > np.iinfo(stored.dtype).max:
                    raise _Repack
                return v.astype(stored.dtype, copy=False)
        if len(e.upd) and not self.schema[ci].is_pk_handle:
            v, vn = new_vals(e.upd_idx)
            stored, nl = stored.copy(), nl.copy()
            stored[e.upd] = lane(v, vn)
            nl[e.upd] = vn
        splice = _Splice(len(stored), e.dels, e.at)
        if len(e.at):
            v, vn = new_vals(e.ins_idx)
            stored = splice(stored, lane(v, vn), room, (e.b, ci, "data"))
            nl = splice(nl, vn, room, (e.b, ci, "nulls"))
        elif len(e.dels):
            stored, nl = splice(stored, None), splice(nl, None)
        if isinstance(col, E):
            return E(col.eval_type, col.frac, "bp", len(stored), packed=stored,
                     ref=col.ref, nulls=nl)
        return Column(col.eval_type, stored, nl, col.frac, col.dictionary)

    def _edit_decoded(self, ci: int, col: Column, e: "_Edit", ch, dvals):
        """``_edit_column`` through the decoded values, for a run-length
        column: its runs are made again from the edited rows."""
        work = _encoding.host_dtype(col)
        d = np.asarray(_encoding.decoded_data(col)).astype(work, copy=True)
        nl = np.asarray(_encoding.decoded_nulls(col), dtype=bool).copy()
        vals, nls = dvals[ci] if dvals is not None else ((), ())

        def new_vals(idx):
            v = np.empty(len(idx), dtype=work)
            for j, i in enumerate(idx):
                v[j] = vals[i]
            return v, nls[idx]

        if len(e.upd):
            v, vn = new_vals(e.upd_idx)
            d[e.upd] = v
            nl[e.upd] = vn
        if len(e.dels):
            keep = np.ones(len(d), dtype=bool)
            keep[e.dels] = False
            d, nl = d[keep], nl[keep]
        if len(e.at):
            v, vn = new_vals(e.ins_idx)
            d = np.insert(d, e.at, v)
            nl = np.insert(nl, e.at, vn)
        return _encode_like(col, d, nl)

    def _repack(self, ch, cols, upd, dpos, grow):
        """The whole image re-chunked into full blocks from its resident
        columns (no KV decode) and re-encoded: a new version with no pins.
        The block count never falls below the current one."""
        old = self.block_cache
        blocks = list(old.blocks)
        n_ch = len(ch)
        dvals = self._delta_columns(blocks, cols, n_ch, grow) if n_ch else None
        n = self.n_rows
        h = self.handles
        keep = np.ones(n, dtype=bool)
        keep[dpos] = False
        pos = np.searchsorted(h, ch)
        ins = ~upd
        kept_h = h[keep]
        ins_at = np.searchsorted(kept_h, ch[ins])
        gdata, gnulls = [], []
        for ci in range(len(self.schema)):
            if blocks:
                parts = [np.asarray(_encoding.decoded_data(b.cols[ci])) for b in blocks]
                work = _encoding.host_dtype(blocks[0].cols[ci])
                g = np.concatenate(parts).astype(work, copy=True) if parts else np.empty(0, work)
                gn = np.concatenate([np.asarray(_encoding.decoded_nulls(b.cols[ci]), dtype=bool)
                                     for b in blocks])
            else:
                et = self.schema[ci].ftype.eval_type
                work = (object if et in (EvalType.BYTES, EvalType.JSON)
                        else np.float64 if et == EvalType.REAL else np.int64)
                g, gn = np.empty(0, dtype=work), np.empty(0, dtype=bool)
            if n_ch:
                if self.schema[ci].is_pk_handle:
                    v, vn = ch.astype(g.dtype), np.zeros(n_ch, dtype=bool)
                else:
                    v = np.empty(n_ch, dtype=g.dtype)
                    for j, x in enumerate(dvals[ci][0]):
                        v[j] = x
                    vn = dvals[ci][1]
                    g[pos[upd]] = v[upd]
                    gn[pos[upd]] = vn[upd]
                g = np.insert(g[keep], ins_at, v[ins])
                gn = np.insert(gn[keep], ins_at, vn[ins])
            else:
                g, gn = g[keep], gn[keep]
            gdata.append(g)
            gnulls.append(gn)
        templates = [blocks[0].cols[ci] if blocks else None for ci in range(len(self.schema))]
        dicts = {}
        for ci, extra in grow.items():
            base = templates[ci].dictionary
            grown = np.empty(len(base) + len(extra), dtype=object)
            grown[: len(base)] = base
            grown[len(base):] = extra
            dicts[ci] = grown
        new = ColumnBlockCache(key=old.key)
        br = self.block_rows
        total = len(gdata[0]) if gdata else 0
        for s in range(0, total, br):
            e = min(s + br, total)
            bcols = []
            for ci in range(len(self.schema)):
                t = templates[ci]
                d = gdata[ci][s:e]
                if t is not None and t.dictionary is not None and d.dtype != object:
                    d = d.astype(np.int64)
                bcols.append(Column(
                    t.eval_type if t is not None else self.schema[ci].ftype.eval_type,
                    d, gnulls[ci][s:e],
                    t.frac if t is not None else self.schema[ci].ftype.decimal,
                    dicts.get(ci, t.dictionary if t is not None else None),
                ))
            new.add(bcols, e - s)
        if self.encode_enabled and new.blocks:
            # the stats pass again, over fresh value ranges and runs (no KV
            # decode: the repack stayed on resident columns)
            self.encodings = _encoding.encode_blocks(new, self.schema)
        while len(new.blocks) < len(blocks) or not new.blocks:
            template = new.blocks[0] if new.blocks else blocks[0]
            new.blocks.append(_empty_block(template))
        new.filled = True
        new.folded = True
        new.takes_folds = True
        new.enc_version = old.enc_version + 1
        return new


class RegionCacheStats:
    __slots__ = ("hits", "misses", "deltas", "delta_rows", "stale",
                 "below_snapshot", "uncacheable",
                 "evictions", "invalidations", "bytes_pinned",
                 "wt_deltas", "wt_rows", "wt_lost", "prefix_folds",
                 "below_image")

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.deltas = 0      # scan_delta-path serves (CF_WRITE re-scans)
        self.delta_rows = 0
        self.stale = 0
        self.below_snapshot = 0  # readers below snapshot_ts the image served
        self.uncacheable = 0
        self.evictions = 0
        self.invalidations = 0
        self.bytes_pinned = 0
        self.wt_deltas = 0   # write-through folds (zero CF_WRITE scans)
        self.wt_rows = 0
        self.wt_lost = 0     # emission gaps forcing a scan_delta repair
        # reads answered by folding the chain's prefix up to their apply
        # index (each one also a wt_delta)
        self.prefix_folds = 0
        # readers whose snapshot the image had passed (below its apply
        # index), folded onto a kept earlier version
        self.below_image = 0

    def to_dict(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


class RegionColumnCache:
    """LRU of :class:`RegionImage` under a byte budget.

    **Sharded mode** (``mesh`` with >1 device): every image is assigned an
    OWNER device under a per-device byte budget — the whole image on the
    least-loaded device normally, block-level round-robin for a single huge
    region (one region bigger than a device's budget share).  The placement
    is written onto each image's block cache as ``owner_devices`` (device id
    per block); the mesh-sharded warm launcher
    (``parallel.mesh.launch_xregion_sharded``) pins the slab stacks there, so
    a cross-region batch runs with zero re-sharding — each device already
    holds its shard.  Eviction/invalidation rebalances: images migrate from
    the most- to the least-loaded device (pins rebuild lazily on the new
    owner)."""

    def __init__(
        self,
        byte_budget: int = DEFAULT_BYTE_BUDGET,
        max_regions: int = DEFAULT_MAX_REGIONS,
        block_rows: int | None = None,
        mesh=None,
        per_device_budget: int | None = None,
        write_through: bool = True,
        data_token: object = _TOKEN_UNSET,
        encode_columns: bool = True,
    ):
        from .jax_eval import DEFAULT_BLOCK_ROWS

        self.byte_budget = byte_budget
        self.max_regions = max_regions
        self.block_rows = block_rows or DEFAULT_BLOCK_ROWS
        # compressed residency (docs/compressed_columns.md): fill runs the
        # encoding stats pass and the byte budget accounts ENCODED bytes —
        # encode_columns=False is the kill switch (decoded residency, PR-9
        # behavior exactly)
        self.encode_columns = encode_columns
        self._images: dict = {}  # key -> RegionImage, insertion = LRU order
        self._mu = make_rlock("copr.region_cache")
        self.stats = RegionCacheStats()
        # quarantine ledger (docs/integrity.md): every image invalidated by
        # an integrity mismatch leaves an entry here — the operator's
        # forensic trail behind tikv_coprocessor_integrity_quarantine_total
        self.quarantine_ledger: list[dict] = []
        # write-through delta intake (docs/write_path.md): per-region
        # watermark of the highest apply index whose data change this cache
        # has SEEN (as a parsed delta or a lost marker).  Pending deltas may
        # only start on an image whose apply_index has caught up to the
        # watermark — anything else means a missed batch, and missed batches
        # must repair through scan_delta, never through a gapped pending.
        self.write_through = write_through
        self._wt_seen: dict[int, int] = {}
        # engine identity this cache serves: notifies from any OTHER engine
        # are dropped — region ids alone don't identify data in a process
        # that hosts several stores or embedded endpoints.  Bound at
        # construction when the owner knows its engine (Endpoint passes the
        # engine's data_token; None for plain local engines); otherwise
        # learned from the first served snapshot — late binding silently
        # drops any notify racing the early serves (the watermark cannot
        # see them), so a late-bound cache additionally refuses to START a
        # pending chain for a region until one notify has been observed
        # and a read has repaired past it (_merge_pending's prev>=0 gate).
        self._wt_token = data_token
        self._wt_late_bound = False
        # per-tenant HBM partitions (docs/robustness.md "Overload"): byte
        # budgets splitting the global budget per tenant; the default
        # tenant owns the remainder pool.  An over-budget tenant degrades
        # down the pressure ladder (_enforce_tenant_budgets): evict ITS
        # coldest images → demote ITS pins to host → CPU-fallback ITS
        # device paths for a cooldown — never another tenant's warm set.
        self._tenant_budgets: dict[str, int] = {}
        self._device_blocked: dict[str, float] = {}
        self.device_block_cooldown_s = 2.0
        self._clock = time.monotonic
        self.devices: list = []
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            try:
                devs = list(np.asarray(mesh.devices).reshape(-1))
            except Exception:  # noqa: BLE001 — a fake/broken mesh: unsharded
                devs = []
            if len(devs) > 1:
                self.devices = devs
        self.per_device_budget = (
            per_device_budget
            if per_device_budget is not None
            else byte_budget // max(len(self.devices), 1)
        )
        self._device_bytes: dict[int, int] = {d.id: 0 for d in self.devices}
        _CACHES.add(self)

    @property
    def sharded(self) -> bool:
        return bool(self.devices)

    # -- public ------------------------------------------------------------

    def serve(self, snap, context: dict, columns_info, ranges, start_ts: int,
              statistics: Statistics | None = None):
        """Resolve a request against the cache.

        Returns ``(block_cache | None, outcome, delta_rows)``; a None block
        cache means "serve through the normal path" (outcome says why)."""
        # the stage is the whole call LESS the lock check, any build or
        # repair and any wait for the manager lock: those are stages of
        # their own, and suspend this one
        with trace.stage("cache.lookup") as st:
            *out, below = self._serve(snap, context, columns_info, ranges,
                                      start_ts, statistics)
            st.tag(outcome=out[1])
            if below:
                st.tag(below_snapshot=1)
        return tuple(out)

    def _locked(self):
        """The manager lock, its wait timed as stage ``cache.mu_wait``."""
        with trace.stage("cache.mu_wait"):
            self._mu.acquire()
        return _Held(self._mu)

    def _serve(self, snap, context, columns_info, ranges, start_ts, statistics):
        """``serve``'s answer, and last whether the reader came below the
        image's ``snapshot_ts`` (another session overtook it)."""
        region_id = (context or {}).get("region_id")
        epoch = _epoch_of((context or {}).get("region_epoch"))
        apply_index = (context or {}).get("apply_index")
        if region_id is None or epoch is None or apply_index is None:
            return None, "off", 0, False
        tenant = str((context or {}).get("tenant") or "default")
        key = (region_id, tuple(ranges), schema_sig(columns_info))
        stats = statistics or Statistics()
        with self._locked():
            if self._wt_token is _TOKEN_UNSET:
                # bind to the engine behind the first served snapshot —
                # from here on, only ITS write-through notifies are accepted.
                # Notifies BEFORE this bind were dropped unseen, so chains
                # stay gated until the stream re-anchors.
                self._wt_token = getattr(snap, "data_token", None)
                self._wt_late_bound = True
            img = self._images.get(key)
            if img is not None and img.epoch != epoch:
                self._drop(key, reason="epoch")
                img = None
            if img is not None:
                # LRU touch
                self._images.pop(key)
                self._images[key] = img
        if img is None:
            # build OUTSIDE the manager lock: a cold build of a large region
            # (full MVCC resolve + decode) must not stall hits on warm
            # regions.  A concurrent build of the same key wastes one build;
            # the insert below keeps whichever image is newest.
            return *self._build(key, epoch, snap, columns_info, ranges,
                                start_ts, apply_index, stats,
                                tenant=tenant), False
        # one image's repair waits for that image alone: readers of other
        # images, and apply_write, take only the manager lock, briefly
        with img.mu:
            if img.dropped or img.epoch != epoch:
                # raced with an invalidation between lookup and here
                self.stats.uncacheable += 1
                self._count("uncacheable")
                return None, "uncacheable", 0, False
            # img.mu is this image's own repair lock: a walk must be atomic
            # with the image's version bump, and no reader of another image
            # and no notify waits on it (Concurrency)
            # lint: allow(lock-blocking-call) -- one image's repair, off _mu
            out = self._resolve(img, key, epoch, snap, columns_info, ranges,
                                start_ts, apply_index, stats, tenant)
        if out[1] in ("wt_delta", "delta"):
            with self._locked():
                if not img.dropped:
                    if self.devices:
                        # a structural fold can change the block count and
                        # bytes: keep owner_devices block-aligned
                        self._unplace(img)
                        self._place(img)
                    self._enforce_budget(keep=key)
                    self._gauge_bytes(full=False)
        return out

    def _resolve(self, img, key, epoch, snap, columns_info, ranges, start_ts,
                 apply_index, stats, tenant):
        """``_serve`` for a resident image, under the image's lock: a hit, a
        fold of the pending chain's prefix up to the reader's apply index, a
        walk of the range's CF_WRITE (``scan_delta``), a rebuild, or stale.

        The image holds the rows a read at apply index ``I`` sees at any
        timestamp in ``[max_commit_ts, hi)``, ``hi`` the oldest version at
        ``I`` it does not hold (``hidden_ct``, or a write the chain holds
        back).  A reader at apply index ``A >= I`` and ``start_ts`` S in that
        interval is answered by folding every chain write of a batch at or
        below ``A`` committed at or below S, in apply order (per key, apply
        order is commit order): that is the engine's own state at ``A`` as S
        reads it.  A write committed above S stays pending for later
        readers.  A reader below ``max_commit_ts``, or below the image's
        apply index, is folded onto a kept earlier version (``_serve_base``)
        or is stale; a gapped chain or a version the chain never carried
        sends the reader to the walk."""
        below = start_ts < img.snapshot_ts
        I = img.apply_index
        if self._hit_fresh_locked(img, apply_index, start_ts, snap, ranges, stats):
            if below:
                self._count_below_snapshot(served=True)
            self.stats.hits += 1
            self._count("hit")
            return img.block_cache, "hit", 0, below
        with self._locked():
            chain_ok = img.chain_from is not None and img.chain_from <= I
            batches = ([b for b in img.chain if b[0] <= apply_index]
                       if chain_ok else [])
            gap = img.chain_gap if not chain_ok else None
        if apply_index < I or start_ts < img.max_commit_ts:
            out = self._serve_base(img, snap, ranges, start_ts, apply_index,
                                   batches, chain_ok, stats)
            if below:
                self._count_below_snapshot(served=out is not None)
            if out is not None:
                cache, n = out
                self.stats.wt_deltas += 1
                self.stats.wt_rows += n
                self.stats.prefix_folds += 1
                self.stats.below_image += apply_index < I
                self._count_repair("fold")
                self._count("wt_delta")
                self._count_delta_rows(n)
                return cache, "wt_delta", n, below
            # the image holds rows this reader must not see, and no kept
            # version is old enough: only a fresh scan can answer it
            self._count_fold_miss("reader_below_image")
            self.stats.stale += 1
            self._count("stale")
            return None, "stale", 0, below
        walk_why = None
        if apply_index > I and not chain_ok:
            walk_why = gap
        elif img.hidden_ct is not None and start_ts >= img.hidden_ct:
            walk_why = "gapped"  # a version at I that no pending batch carries
        if walk_why is not None:
            self._count_fold_miss(walk_why)
            if below:
                # a walk would rewind the image under the readers above it
                self._count_below_snapshot(served=False)
                self.stats.stale += 1
                self._count("stale")
                return None, "stale", 0, True
            return self._walk(img, key, epoch, snap, columns_info, ranges,
                              start_ts, apply_index, stats, tenant)
        writes = [(i, *w) for i, ws, _l in batches for w in ws if w[1] <= start_ts]
        locks = self._check_locks(img, snap, ranges, start_ts, stats,
                                  known=self._locks_at(img, batches, apply_index))
        if not locks and apply_index >= img.locks_dirty_at:
            # this snapshot contains the dirtying batch and the range is
            # lock-free — safe to stop checking.  An OLDER snapshot seeing
            # no locks proves nothing.
            img.locks_dirty = False
        if below:
            self._count_below_snapshot(served=True)
        if not writes:
            # nothing this reader may see is missing: a hit (the batches up
            # to its apply index, if any, are all above its timestamp)
            self._advance(img, apply_index, start_ts, locks)
            self.stats.hits += 1
            self._count("hit")
            return img.block_cache, "hit", 0, below
        delta = _fold_writes(writes)
        n_touch = len(delta["changed_handles"]) + len(delta["deleted_handles"])
        if img.n_rows and n_touch > _REBUILD_FRACTION * img.n_rows:
            return self._rebuild(key, epoch, snap, columns_info, ranges,
                                 start_ts, apply_index, stats, tenant)
        with trace.stage("cache.repair", how="fold") as st:
            n = img.apply_delta(delta)
            st.tag(rows=n)
        img.max_commit_ts = max(img.max_commit_ts, delta["max_commit_ts"])
        self._advance(img, apply_index, start_ts, locks)
        img.note_folded(writes)
        self.stats.wt_deltas += 1
        self.stats.wt_rows += n
        self.stats.prefix_folds += 1
        self._count_repair("fold")
        self._count("wt_delta")
        self._count_delta_rows(n)
        return img.block_cache, "wt_delta", n, below

    def _serve_base(self, img, snap, ranges, start_ts, apply_index, batches,
                    chain_ok, stats):
        """A reader whose start_ts lies below a commit the image holds, or
        whose snapshot is older than the image (a read another thread's fold
        overtook between its snapshot and its turn at the image's lock): the
        newest kept base version at or below the reader's apply index and
        timestamp, with every write since it that the reader sees folded in
        (the writes the image has folded since, and the chain's, each at or
        below the reader's apply index), as a version of its own — ``(block
        cache, rows)``, or None where no base can answer.  The locks of a
        reader below the image's apply index are not the chain's to tell:
        they are checked on its own snapshot.  The image is left as it
        stands."""
        for state, pos in reversed(img.bases):
            if (state["apply_index"] <= apply_index
                    and state["max_commit_ts"] <= start_ts
                    and (state["hidden_ct"] is None or start_ts < state["hidden_ct"])):
                break
        else:
            return None
        if img.hidden_ct is not None and start_ts >= img.hidden_ct:
            return None  # a version a dropped chain held back
        if apply_index > img.apply_index and not chain_ok:
            return None  # batches past the image nobody carried
        writes = [w for w in img.since[pos:] if w[0] <= apply_index and w[2] <= start_ts]
        writes += [(i, *w) for i, ws, _l in batches for w in ws if w[1] <= start_ts]
        known = (self._locks_at(img, batches, apply_index)
                 if apply_index >= img.apply_index else None)
        self._check_locks(img, snap, ranges, start_ts, stats, known=known)
        if not writes:
            return state["block_cache"], 0
        delta = _fold_writes(writes)
        with trace.stage("cache.repair", how="fold") as st:
            cache = img.fork(state, delta)
            n = len(delta["changed_handles"]) + len(delta["deleted_handles"])
            st.tag(rows=n, base=1)
        return cache, n

    def _locks_at(self, img, batches, apply_index) -> dict | None:
        """Every CF_LOCK entry of the image's ranges at ``apply_index``: the
        image's own at its apply index with the lock changes of the chain's
        batches up to ``apply_index`` laid over them; None where that is not
        known."""
        if img.locks is None:
            return None
        if apply_index == img.apply_index:
            return img.locks
        locks = dict(img.locks)
        for i, _ws, ls in batches:
            if i > img.apply_index:
                for raw, lock in ls:
                    if lock is None:
                        locks.pop(raw, None)
                    else:
                        locks[raw] = lock
        return locks

    def _advance(self, img, apply_index, start_ts, locks) -> None:
        """The image now answers for ``apply_index``: its pending chain keeps
        only the writes above ``start_ts`` of the batches up to it (held back
        for later readers) and the batches after it.  Caller holds the
        image's lock; the apply index moves under the manager lock with the
        chain, so a notify sees them agree."""
        with self._locked():
            kept = []
            for i, ws, ls in img.chain:
                if i > apply_index:
                    kept.append((i, ws, ls))
                    continue
                held = [w for w in ws if w[1] > start_ts]
                if held:
                    # its lock changes are in ``locks`` now
                    kept.append((i, held, []))
            img.chain = kept
            if not kept:
                # nothing pending: the next notify anchors a new chain (a
                # read past the image with none in between walks)
                img.chain_from = None
                img.chain_gap = "gapped"
            if apply_index > img.apply_index:
                img.apply_index = apply_index
                img.locks = locks
            elif locks is not None:
                img.locks = locks
        # never lowered: a reader below it leaves it where it was
        img.snapshot_ts = max(img.snapshot_ts, start_ts)

    def _walk(self, img, key, epoch, snap, columns_info, ranges, start_ts,
              apply_index, stats, tenant):
        """Repair by diffing the range's CF_WRITE against the image
        (``scan_delta``): the chain is gapped, late-bound or carried a batch
        it could not parse, or the reader may see a version no pending batch
        carries."""
        with trace.stage("cache.repair", how="walk") as st:
            delta = scan_delta(snap, start_ts, ranges, img.handles,
                               img.row_commit_ts, statistics=stats)
            if delta is None:
                self.stats.uncacheable += 1
                self._count("uncacheable")
                with self._locked():
                    self._drop(key, reason="unvectorizable")
                return None, "uncacheable", 0, False
            n_touch = len(delta["changed_handles"]) + len(delta["deleted_handles"])
            if img.n_rows and n_touch > _REBUILD_FRACTION * img.n_rows:
                st.tag(rows=n_touch, rebuild=1)
                return self._rebuild(key, epoch, snap, columns_info, ranges,
                                     start_ts, apply_index, stats, tenant)
            n = img.apply_delta(delta)
            st.tag(rows=n)
        img.max_commit_ts = delta["visible_max_ct"]
        img.hidden_ct = delta["hidden_min_ct"]
        img.snapshot_ts = max(img.snapshot_ts, start_ts)
        img.locks = {} if delta["locks_seen"] == 0 else None
        if apply_index >= img.locks_dirty_at:
            # scan_delta lock-checked the ranges on a snapshot that
            # contains the dirtying batch
            img.locks_dirty = False
        with self._locked():
            # the scan saw every batch up to apply_index: what the chain held
            # of them is in the image or behind hidden_ct now
            img.apply_index = apply_index
            img.chain = [b for b in img.chain if b[0] > apply_index]
            if not img.chain:
                img.chain_from = None
                img.chain_gap = "gapped"
        img.rebase()
        self.stats.deltas += 1
        self.stats.delta_rows += n
        self._count_repair("walk")
        self._count("delta")
        self._count_delta_rows(n)
        return img.block_cache, "delta", n, False

    def _rebuild(self, key, epoch, snap, columns_info, ranges, start_ts,
                 apply_index, stats, tenant):
        """A delta over a quarter of the image: build it anew."""
        self._count_repair("rebuild")
        with self._locked():
            self._drop(key, reason="delta_too_big")
        return self._build(key, epoch, snap, columns_info, ranges, start_ts,
                           apply_index, stats, tenant=tenant)

    # -- integrity plane (docs/integrity.md) ---------------------------------

    def quarantine_image(self, key, stage: str, detail: dict | None = None):
        """Quarantine ONE image: ledger entry + invalidation (counted under
        its own reason so dashboards separate corruption from churn).  The
        rebuild happens on the next serve — or eagerly by the scrubber.
        Safe to call with the manager lock held (it is reentrant)."""
        import time as _time

        with self._mu:
            img = self._images.get(key)
            if img is None:
                return None
            entry = {
                "time": _time.time(),
                "region_id": key[0],
                "key_id": _integrity.image_key_id(key),
                "ranges": [(s.hex(), e.hex()) for s, e in key[1]],
                "stage": stage,
                "epoch": list(img.epoch),
                "apply_index": img.apply_index,
                "snapshot_ts": img.snapshot_ts,
                "rows": img.n_rows,
                "fingerprint": img.fp_integrity if img.fp_valid else None,
            }
            if detail:
                entry.update(detail)
            self.quarantine_ledger.append(entry)
            del self.quarantine_ledger[:-256]
            self._drop(key, reason="quarantine")
        _integrity.count_quarantine(stage)
        return entry

    def quarantine_region(self, region_id: int, ranges=None, stage: str = "scrub",
                          detail: dict | None = None) -> list:
        """Quarantine every image of ``region_id`` (narrowed to one range
        set when ``ranges`` is given) — the shadow-read mismatch path."""
        with self._mu:
            keys = [
                k for k in self._images
                if k[0] == region_id and (ranges is None or k[1] == tuple(ranges))
            ]
            return [self.quarantine_image(k, stage, detail) for k in keys]

    def image_fingerprints(self) -> list[dict]:
        """Per-image integrity view for the debug surface: fingerprint,
        apply point, and write-through pending state of every resident
        image."""
        with self._mu:
            out = []
            for key, img in self._images.items():
                out.append({
                    "region_id": key[0],
                    "key_id": _integrity.image_key_id(key),
                    "epoch": list(img.epoch),
                    "apply_index": img.apply_index,
                    "snapshot_ts": img.snapshot_ts,
                    "rows": img.n_rows,
                    "fp_valid": img.fp_valid,
                    "fingerprint": img.fp_integrity if img.fp_valid else None,
                    "checksum": img.fp_value if img.fp_valid else None,
                    "pending": img.wt_pending is not None,
                })
            return out

    def checksum_serve(self, snap, context: dict, ranges, start_ts: int):
        """Answer a coprocessor Checksum (tp=105) off a warm image
        fingerprint: returns (checksum, total_kvs, total_bytes) when an
        image of exactly these ranges is fresh for (apply_index, start_ts),
        else None (the CPU-oracle scan serves).  The per-row hash is the
        checksum_range entry by construction, so warm and cold answers are
        byte-identical.  Locks are the one thing the fingerprint cannot
        prove absent — a dirty/newer-ts serve checks CF_LOCK exactly like
        the hit path (and raises KeyIsLocked exactly like the oracle scan
        would)."""
        region_id = (context or {}).get("region_id")
        epoch = _epoch_of((context or {}).get("region_epoch"))
        apply_index = (context or {}).get("apply_index")
        if region_id is None or epoch is None or apply_index is None:
            return None
        rkey = tuple(ranges)
        stats = Statistics()
        below = False  # below the snapshot_ts of an image it was held to
        with self._locked():
            imgs = [img for key, img in self._images.items()
                    if key[0] == region_id and key[1] == rkey]
        for img in imgs:
            with img.mu:
                if img.epoch != epoch or not img.fp_valid:
                    continue
                img_below = start_ts < img.snapshot_ts
                # the hit path's exact freshness + lock rules (ONE
                # definition — _hit_fresh_locked — so the warm Checksum path
                # can never drift from what a served hit would have answered)
                if self._hit_fresh_locked(img, apply_index, start_ts, snap,
                                          ranges, stats):
                    if img_below:
                        self._count_below_snapshot(served=True)
                    return img.checksum_parts()
                below = below or img_below
        if below:
            # one reader, once: no image of the region served it
            self._count_below_snapshot(served=False)
        return None

    def invalidate_region(self, region_id: int, reason: str = "epoch") -> None:
        with self._mu:
            for key in [k for k in self._images if k[0] == region_id]:
                self._drop(key, reason=reason)
            # the notify watermark dies with the images (dead region ids —
            # merge sources, destroyed peers — must not leak an entry each);
            # a live region's next notify re-seeds it before any new image
            # can finish building
            self._wt_seen.pop(region_id, None)
            self._rebalance()

    # -- write-through intake (raft apply -> pending chains) -----------------

    def apply_write(self, region_id: int, parse_once, apply_index: int,
                    token=None) -> None:
        """Append a committed batch's row and lock changes to the chain of
        every resident image of ``region_id``.  Raft applies a region's
        entries in order on one worker, so notifies arrive in apply-index
        order per region; an index at or below the watermark is a replica's
        replay of a batch already merged (identical ops by raft) and is
        skipped.  ``parse_once`` is the notify's memoized op parser — invoked
        OUTSIDE the manager lock (it may read CF_DEFAULT), at most once
        across every live cache.  Only the manager lock is taken, and only
        briefly: an image under repair does not hold this up."""
        with self._locked():
            if self._wt_token is _TOKEN_UNSET or token != self._wt_token:
                return  # not this cache's engine (or cache never served yet)
            prev = self._wt_seen.get(region_id, -1)
            if apply_index <= prev:
                return
            # the watermark advances even with write_through off: flipping
            # it back on must not let a chain start across unseen batches
            self._wt_seen[region_id] = apply_index
            if not self.write_through:
                # an unbuffered batch gaps any surviving chain — drop it,
                # or re-enabling would merge later batches into the gap
                self._drop_pendings_locked(region_id)
                return
            if not any(k[0] == region_id for k in self._images):
                return
        parsed = parse_once()
        with self._locked():
            # images may have churned while parsing: re-list.  The ``prev``
            # check in _merge_pending still blocks any image whose state
            # predates an unbuffered notify.
            imgs = [img for k, img in self._images.items() if k[0] == region_id]
            if not imgs:
                return
            if parsed is None:
                # not expressible as row changes: the chains are now gapped
                for img in imgs:
                    self._drop_chain(img, "unparsable")
                self.stats.wt_lost += 1
                self._count_wt_lost()
                return
            writes, lock_ops = parsed
            for img in imgs:
                self._merge_pending(img, writes, lock_ops, prev, apply_index)

    def note_write_lost(self, region_id: int, apply_index: int,
                        token=None) -> None:
        """A data change of unknown content landed (emission off, raft
        snapshot apply, merge catch-up, OR a notify that faulted after the
        watermark already advanced): drop chains unconditionally — a dropped
        chain only costs a scan_delta repair, while a chain kept across an
        unbuffered batch serves wrong rows forever — and advance the
        watermark so no chain restarts until a read catches the image up
        past ``apply_index``."""
        with self._locked():
            if self._wt_token is _TOKEN_UNSET or token != self._wt_token:
                return
            if apply_index > self._wt_seen.get(region_id, -1):
                self._wt_seen[region_id] = apply_index
            self._drop_pendings_locked(region_id)

    def _drop_pendings_locked(self, region_id: int) -> None:
        dropped = False
        for k, img in self._images.items():
            if k[0] == region_id and img.chain_from is not None:
                dropped = bool(img.chain) or dropped
                self._drop_chain(img, "gapped")
        if dropped:
            self.stats.wt_lost += 1
            self._count_wt_lost()

    @staticmethod
    def _drop_chain(img, why: str) -> None:
        """Gap ONE image's chain (caller holds the manager lock).  What it
        held back is a version the image may come to answer for without
        holding it (a fold under way advances the image past batches it
        held back): every write it held moves into ``hidden_ct``."""
        held = [c for _i, ws, _l in img.chain for _h, c, _v in ws]
        if held:
            img.hidden_ct = min(held) if img.hidden_ct is None else min(img.hidden_ct, *held)
        img.chain = []
        img.chain_from = None
        img.chain_gap = why

    def _merge_pending(self, img, writes, lock_ops, prev: int,
                       apply_index: int) -> None:
        ranges = img.key[1]
        in_locks = [(raw, lock) for raw, lock in lock_ops if _in_ranges(raw, ranges)]
        if in_locks:
            img.locks_dirty = True
            img.locks_dirty_at = max(img.locks_dirty_at, apply_index)
        if img.chain_from is None:
            if prev > img.apply_index or apply_index <= img.apply_index:
                # a batch between the image's state and this one was never
                # buffered (image built mid-stream, or emission was off):
                # this image repairs through scan_delta, not a gapped chain
                img.chain_gap = "gapped"
                return
            if self._wt_late_bound and prev < 0:
                # first observed notify for this region on a LATE-bound
                # cache: earlier notifies may have been dropped unseen
                # while unbound, so this chain cannot anchor — the next
                # read repairs via scan_delta, re-anchoring the stream
                img.chain_gap = "late_bound"
                return
            img.chain_from = img.apply_index
        elif apply_index <= img.apply_index:
            # a batch the image is already past: replaying it could put an
            # older version over a newer one
            self._drop_pending_img(img)
            return
        mine = [w for w in writes if _in_ranges(w[0], ranges)]
        if any(len(raw) != 19 for raw, _c, _v in mine):
            # non-record key inside a record range: not foldable
            self._drop_pending_img(img, "unparsable")
            return
        try:
            hs = decode_record_handles([raw for raw, _c, _v in mine]).tolist()
        except Exception:  # noqa: BLE001
            self._drop_pending_img(img, "unparsable")
            return
        rows = [(h, cts, v) for h, (_raw, cts, v) in zip(hs, mine)]
        if rows or in_locks:
            img.chain.append((apply_index, rows, in_locks))
        if sum(len(ws) for _i, ws, _l in img.chain) > max(1024, img.n_rows):
            # the chain outgrew the image: a rebuild will beat replaying it
            self._drop_pending_img(img)

    def _drop_pending_img(self, img, why: str = "gapped") -> None:
        """Drop ONE image's chain, keeping the wt_lost accounting in step
        with every other drop path (the Grafana emission-gap series must see
        these, or a rising scan_delta rate is undiagnosable)."""
        if img.chain_from is not None:
            self._drop_chain(img, why)
            self.stats.wt_lost += 1
            self._count_wt_lost()

    # -- per-tenant HBM partitions (docs/robustness.md "Overload") -----------

    def set_tenant_budgets(self, budgets: dict[str, int]) -> None:
        """Partition the byte budget per tenant.  Tenants absent from the
        map share the remainder pool with the default tenant (explicitly
        listing ``default`` pins its pool too).  Enforcement runs now —
        shrinking a partition degrades its tenant immediately."""
        with self._mu:
            self._tenant_budgets = {str(t): int(b) for t, b in budgets.items()}
            self._enforce_tenant_budgets(keep=None)
            self._gauge_bytes()

    def resize_budget(self, byte_budget: int) -> None:
        """Online global-budget change (``Nemesis.memory_squeeze`` and ops
        reconfig): enforcement runs immediately under the new bound."""
        with self._mu:
            self.byte_budget = int(byte_budget)
            self._enforce_budget(keep=None)
            self._gauge_bytes()

    def tenant_budget(self, tenant: str) -> int | None:
        """The tenant's partition bytes, or None = unbounded (only the
        global budget applies).  The default tenant's implicit budget is
        the remainder after every explicit partition."""
        b = self._tenant_budgets.get(tenant)
        if b is not None:
            return b
        if tenant == "default" and self._tenant_budgets:
            explicit = sum(v for t, v in self._tenant_budgets.items()
                           if t != "default")
            return max(self.byte_budget - explicit, 0)
        return None

    def device_allowed(self, tenant: str) -> bool:
        """False while the tenant sits on the pressure ladder's last rung
        (CPU fallback); the block lifts itself after the cooldown."""
        with self._mu:
            until = self._device_blocked.get(tenant)
            if until is None:
                return True
            if self._clock() >= until:
                self._device_blocked.pop(tenant, None)
                return True
            return False

    def tenant_occupancy(self) -> dict:
        """Per-tenant partition view for ``/debug/overload``: resident
        bytes vs budget, image count, and any active device block."""
        with self._mu:
            per: dict[str, dict] = {}
            now = self._clock()
            for img in self._images.values():
                e = per.setdefault(img.tenant, {"bytes": 0, "images": 0})
                e["bytes"] += img.nbytes
                e["images"] += 1
            for tenant in set(per) | set(self._tenant_budgets) \
                    | set(self._device_blocked):
                e = per.setdefault(tenant, {"bytes": 0, "images": 0})
                e["budget"] = self.tenant_budget(tenant)
                until = self._device_blocked.get(tenant)
                e["device_blocked_s"] = (
                    round(max(until - now, 0.0), 3) if until is not None
                    and until > now else 0.0)
            return per

    def _tenant_bytes_locked(self, tenant: str) -> int:
        return sum(img.nbytes for img in self._images.values()
                   if img.tenant == tenant)

    def _enforce_tenant_budgets(self, keep) -> None:
        """The memory-pressure degradation ladder, per over-budget tenant
        (caller holds the manager lock):

        1. evict the tenant's COLDEST images (LRU order) — never another
           tenant's, never the image being served (``keep``);
        2. still over (only ``keep`` / a single over-sized image remains):
           demote the tenant's device pins to host — HBM frees, the host
           copy keeps serving through a rebuild-on-demand pin;
        3. still over: CPU-fallback the tenant's device paths for a
           cooldown (``device_allowed``), so it stops re-pinning what its
           partition cannot hold.  Other tenants' warm sets are untouched
           at every rung."""
        if not self._tenant_budgets:
            return
        from ..util.metrics import REGISTRY

        evict_c = REGISTRY.counter(
            "tikv_overload_hbm_evict_total",
            "Per-tenant HBM-partition pressure actions, by ladder step",
        )
        tenants = {img.tenant for img in self._images.values()}
        for tenant in sorted(tenants):
            budget = self.tenant_budget(tenant)
            if budget is None:
                continue
            if self._tenant_bytes_locked(tenant) <= budget:
                continue
            # rung 1: evict the tenant's own coldest images — sparing its
            # HOTTEST one (and the image being served): a tenant keeps one
            # warm image and the later rungs handle the case where that
            # single image alone exceeds the partition
            mine = [k for k, img in self._images.items()
                    if img.tenant == tenant]
            hottest = mine[-1] if mine else None
            for key in mine:
                if key == keep or key == hottest:
                    continue
                if self._tenant_bytes_locked(tenant) <= budget:
                    break
                self._drop(key, reason="tenant_budget")
                evict_c.inc(tenant=tenant, step="evict")
            if self._tenant_bytes_locked(tenant) <= budget:
                continue
            # rung 2: demote remaining device pins to host
            demoted = False
            for img in self._images.values():
                if img.tenant == tenant:
                    img.block_cache.drop_device()
                    demoted = True
            if demoted:
                evict_c.inc(tenant=tenant, step="demote")
            # rung 3: the host-resident set alone is over the partition —
            # block the tenant's device serving for a cooldown so it stops
            # rebuilding pins its budget cannot hold
            self._device_blocked[tenant] = (
                self._clock() + self.device_block_cooldown_s)
            evict_c.inc(tenant=tenant, step="cpu_block")
            REGISTRY.counter(
                "tikv_overload_device_block_total",
                "Tenants pushed to the pressure ladder's CPU-fallback rung",
            ).inc(tenant=tenant)
        self._rebalance()

    def warm_region_ids(self) -> list[int]:
        """Region ids with a resident device image — the placement this
        store advertises to PD each heartbeat so peers can forward
        device-eligible DAGs to the owner (docs/wire_path.md).  Doubles as
        the byte-gauge heartbeat: pure-hit traffic never re-gauges on the
        serve path, so the pinned-HBM/compression gauges refresh here."""
        with self._mu:
            self._gauge_bytes()
            return sorted({k[0] for k in self._images})

    def has_warm_region(self, region_id: int) -> bool:
        with self._mu:
            return any(k[0] == region_id for k in self._images)

    def total_bytes(self) -> int:
        with self._mu:
            return sum(img.nbytes for img in self._images.values())

    def placement(self) -> dict[int, int]:
        """{device_id: pinned bytes} placement metadata (sharded mode)."""
        with self._mu:
            return dict(self._device_bytes)

    def resident_block_caches(self) -> list:
        """The resident images' block caches (benches / introspection —
        feed to ``parallel.mesh.slab_assignment`` for the slab geometry)."""
        with self._mu:
            return [img.block_cache for img in self._images.values()]

    def __len__(self) -> int:
        return len(self._images)

    # -- sharded placement ---------------------------------------------------

    def _place(self, img) -> None:
        """Assign owner devices to a freshly built/repacked image: whole
        image to the least-loaded device, block-level round-robin when the
        image alone exceeds the per-device budget (a single huge region must
        spread, or one chip serves it while the rest idle)."""
        if not self.devices:
            return
        bc = img.block_cache
        n_blocks = len(bc.blocks)
        if n_blocks == 0:
            bc.owner_devices = []
            img.placement_bytes = {}
            return
        per_block = img.nbytes // n_blocks
        if img.nbytes > self.per_device_budget and n_blocks > 1:
            order = sorted(self.devices, key=lambda d: self._device_bytes[d.id])
            owners = [order[b % len(order)].id for b in range(n_blocks)]
        else:
            dev = min(self.devices, key=lambda d: self._device_bytes[d.id])
            owners = [dev.id] * n_blocks
        bc.owner_devices = owners
        pb: dict[int, int] = {}
        for did in owners:
            pb[did] = pb.get(did, 0) + per_block
        img.placement_bytes = pb
        for did, b in pb.items():
            self._device_bytes[did] += b

    def _unplace(self, img) -> None:
        for did, b in getattr(img, "placement_bytes", {}).items():
            self._device_bytes[did] = max(0, self._device_bytes.get(did, 0) - b)
        img.placement_bytes = {}
        img.block_cache.owner_devices = None

    def _rebalance(self) -> None:
        """Shrink the device-load spread after an eviction/invalidation:
        move the best-fitting whole image from the most- to the least-loaded
        device while that strictly narrows the gap.  Only the placement
        metadata moves — device pins drop and rebuild lazily on the new
        owner at the next warm batch."""
        if not self.devices or len(self._images) < 2:
            return
        for _ in range(len(self._images)):
            hi = max(self.devices, key=lambda d: self._device_bytes[d.id])
            lo = min(self.devices, key=lambda d: self._device_bytes[d.id])
            gap = self._device_bytes[hi.id] - self._device_bytes[lo.id]
            if gap <= 0:
                return
            cand = [
                i for i in self._images.values()
                if set(getattr(i, "placement_bytes", {})) == {hi.id}
                and 0 < i.nbytes < gap
            ]
            if not cand:
                return
            img = min(cand, key=lambda i: abs(gap - 2 * i.nbytes))
            self._unplace(img)
            img.block_cache.drop_device()
            img.block_cache.owner_devices = [lo.id] * len(img.block_cache.blocks)
            img.placement_bytes = {lo.id: img.nbytes}
            self._device_bytes[lo.id] += img.nbytes
            # the migration moved placement bytes AFTER the drop path's
            # last refresh — keep the per-device gauge truthful
            self._gauge_bytes(full=False)
        return

    # -- internals ---------------------------------------------------------

    def _build(self, key, epoch, snap, columns_info, ranges, start_ts,
               apply_index, stats, tenant: str = "default"):
        """Build an image for ``key`` (expensive part lock-free) and insert
        it.  Safe to call with or without the manager lock held (the lock is
        reentrant); a racing build of the same key keeps whichever image
        reflects the newer apply index — this request serves its own blocks
        either way."""
        with trace.stage("cache.fill", kind="build") as st:
            src = MvccBatchScanSource(snap, start_ts, ranges, statistics=stats,
                                      record_versions=True)
            with trace.stage("fill.resolve"):
                keys, values = src._resolve_all()
            if not src.versions_exact:
                self.stats.uncacheable += 1
                self._count("uncacheable")
                return None, "uncacheable", 0
            handles = decode_record_handles(keys)
            st.tag(rows=len(handles))
            if len(handles) > 1 and not (handles[1:] > handles[:-1]).all():
                self.stats.uncacheable += 1
                self._count("uncacheable")
                return None, "uncacheable", 0
            img = RegionImage(key, epoch, list(columns_info), self.block_rows)
            img.tenant = tenant
            img.fill(handles, values, src.row_commit_ts, src.visible_max_ct,
                     apply_index, start_ts, raw_keys=keys,
                     encode=self.encode_columns, hidden_ct=src.hidden_min_ct)
            # the build's lock check met no lock: none at apply_index
            img.locks = {} if src.locks_seen == 0 else None
            if img.nbytes > self.byte_budget:
                self.stats.uncacheable += 1
                self._count("too_big")
                # serve this request from the just-built blocks, but don't
                # keep them resident — the budget is the OOM guard
                return img.block_cache, "too_big", 0
            with self._mu:
                existing = self._images.get(key)
                if (existing is None or existing.epoch != epoch
                        or existing.apply_index <= apply_index):
                    if existing is not None:
                        existing.dropped = True
                        self._unplace(existing)
                    self._images[key] = img
                    self._place(img)
                    self._enforce_budget(keep=key)
                self.stats.misses += 1
                self._count("miss")
                self._gauge_bytes()
            return img.block_cache, "miss", 0

    def _hit_fresh_locked(self, img, apply_index, start_ts, snap, ranges,
                          stats) -> bool:
        """ONE definition of hit-path freshness (the warm Checksum path, and
        what ``_resolve`` serves as a hit): True iff the image may serve
        ``start_ts`` as-is at ``apply_index`` — the same apply index, and a
        timestamp that reads the rows the image holds: at or above
        ``max_commit_ts`` (the newest commit it holds), below ``hidden_ct``
        and below every write the chain holds back.  Checks CF_LOCK when it
        must (raising on a blocking lock, exactly like the oracle scan
        would) and maintains ``locks_dirty`` / ``snapshot_ts`` like a served
        hit.  Caller holds the image's lock."""
        if apply_index != img.apply_index or start_ts < img.max_commit_ts:
            return False
        if img.hidden_ct is not None and start_ts >= img.hidden_ct:
            return False
        if img.chain:
            with self._locked():
                if any(w[1] <= start_ts for _i, ws, _l in img.chain for w in ws):
                    return False
        if start_ts != img.snapshot_ts or img.locks_dirty:
            # at the READER's start_ts, below the snapshot as above it
            locks = self._check_locks(img, snap, ranges, start_ts, stats,
                                      known=img.locks)
            img.locks = locks
            if not locks and apply_index >= img.locks_dirty_at:
                # this snapshot contains the dirtying batch and the range is
                # lock-free — safe to stop re-checking.  An OLDER snapshot
                # seeing no locks proves nothing.
                img.locks_dirty = False
            # never lowered: a reader below it leaves it where it was
            img.snapshot_ts = max(img.snapshot_ts, start_ts)
        return True

    def _check_locks(self, img, snap, ranges, ts, stats, known=None) -> dict:
        """Raise on a blocking lock; return every CF_LOCK entry of the ranges
        in ``snap`` (raw key -> Lock).

        Answered three ways, counted by ``how``.  ``memo``: ``snap`` cannot
        differ, in CF_LOCK, from a snapshot whose scan of these ranges met no
        lock (an empty range blocks no reader whatever its timestamp).  The
        witness is the engine's own: ``snap`` reads at sequence S, the memo's
        snapshot read at S0, and no batch has touched CF_LOCK after the older
        of the two (``cf_touched_seq``, read after both were taken, so it can
        only be too high).  ``chain``: ``known`` holds the ranges' locks at
        the snapshot's apply index, as the write-through chain carried them
        (``_locks_at``), and the snapshot is a raft snapshot that vouches for
        that index; a known lock that would block is read back from the
        snapshot before it is believed (``_lock_in``).  ``scan``: the walk of the ranges'
        CF_LOCK, for everything else."""
        from ..util.metrics import REGISTRY

        walked0 = stats.lock.next
        with trace.stage("cache.lock_check") as st:
            seq = snap.sequence()
            memo = img.lock_free_seq
            how = "scan"
            if seq is not None and memo is not None and not img.locks_dirty:
                touched = snap.cf_touched_seq(CF_LOCK)
                if touched is not None and touched <= min(seq, memo):
                    how = "memo"
            blocking = ()
            if (how == "scan" and known is not None
                    and getattr(snap, "apply_index", None) is not None):
                blocking = [(raw, lock) for raw, lock in known.items()
                            if not lock.is_visible_to(ts, frozenset())]
                if all(_lock_in(snap, raw, lock) for raw, lock in blocking):
                    how = "chain"
            # tagged and counted before the check, which may raise
            st.tag(how=how)
            REGISTRY.counter(
                "tikv_coprocessor_region_cache_lock_check_total",
                "Warm-hit lock checks, by how they were answered (memo: the "
                "snapshot proved CF_LOCK unchanged since a lock-free scan; "
                "chain: the write-through chain's locks at the apply index)",
            ).inc(how=how)
            if how == "memo":
                seen = {}
            elif how == "chain":
                seen = known
                if blocking:
                    raise KeyIsLockedError(*blocking[0])
                if not seen and seq is not None:
                    # the snapshot's CF_LOCK holds no lock in range: what a
                    # lock-free scan of it would have recorded
                    img.lock_free_seq = seq if memo is None else max(memo, seq)
            else:
                seen = {}
                for start, end in ranges:
                    enc_start = Key.from_raw(start).encoded
                    enc_end = Key.from_raw(end).encoded
                    for k, v in snap.scan_cf(CF_LOCK, enc_start, enc_end):
                        stats.lock.next += 1
                        raw = Key.from_encoded(k).to_raw()
                        _check_lock(v, raw, ts, frozenset())
                        seen[raw] = Lock.from_bytes(v)
                if not seen and seq is not None:
                    # the SNAPSHOT's sequence, never a stamp read after it: a
                    # lock written since is not in what was scanned.  A lock
                    # that did not block this ts may block the next: seen > 0
                    # records nothing
                    img.lock_free_seq = seq if memo is None else max(memo, seq)
            st.tag(locks_seen=len(seen), keys_walked=stats.lock.next - walked0)
        return seen

    def _drop(self, key, reason: str) -> None:
        img = self._images.pop(key, None)
        if img is None:
            return
        img.dropped = True
        self._unplace(img)
        img.block_cache.clear_blocks()
        img.block_cache.filled = False
        self.stats.invalidations += 1
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_invalidate_total",
            "Region column cache invalidations, by reason",
        ).inc(reason=reason)
        self._gauge_bytes()

    def _enforce_budget(self, keep) -> None:
        # per-tenant partitions first: an over-budget tenant degrades down
        # its own ladder before global pressure evicts ANYONE
        self._enforce_tenant_budgets(keep)
        while len(self._images) > self.max_regions or (
            sum(i.nbytes for i in self._images.values()) > self.byte_budget
            and len(self._images) > 1
        ):
            victim = self._pick_victim_locked(keep)
            if victim is None:
                break
            img = self._images.pop(victim)
            img.dropped = True
            self._unplace(img)
            img.block_cache.clear_blocks()
            img.block_cache.filled = False
            self.stats.evictions += 1
            from ..util.metrics import REGISTRY

            REGISTRY.counter(
                "tikv_coprocessor_region_cache_evict_total",
                "Region column cache LRU/budget evictions",
            ).inc()
        self._rebalance()

    def _pick_victim_locked(self, keep):
        """Global-budget eviction victim: prefer images of tenants over
        their OWN partition (a hot tenant's global pressure must land on
        its warm set, not a well-behaved sibling's), else plain LRU."""
        if self._tenant_budgets:
            for k, img in self._images.items():
                if k == keep:
                    continue
                budget = self.tenant_budget(img.tenant)
                if budget is not None \
                        and self._tenant_bytes_locked(img.tenant) > budget:
                    return k
        return next((k for k in self._images if k != keep), None)

    def _count(self, outcome: str) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_total",
            "Region column cache lookups, by outcome",
        ).inc(outcome=outcome)

    def _count_below_snapshot(self, served: bool) -> None:
        """One reader below an image's snapshot_ts, once it is known whether
        an image served it or it goes on to ``stale``."""
        from ..util.metrics import REGISTRY

        if served:
            self.stats.below_snapshot += 1
        REGISTRY.counter(
            "tikv_coprocessor_region_cache_below_snapshot_total",
            "Readers below an image's snapshot_ts, by whether the image "
            "served them (served) or they went on to stale (refused)",
        ).inc(outcome="served" if served else "refused")

    def _count_repair(self, how: str) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_repair_total",
            "Repairs of a resident image behind the engine, by how (fold: the "
            "write-through chain's prefix; walk: scan_delta over CF_WRITE; "
            "rebuild: a delta over a quarter of the image)",
        ).inc(how=how)

    def _count_fold_miss(self, why: str) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_fold_miss_total",
            "Reads behind a resident image that the write-through chain "
            "could not answer, by why (gapped, late_bound, unparsable, "
            "reader_below_image)",
        ).inc(why=why)

    def _count_wt_lost(self) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_wt_lost_total",
            "Write-through emission gaps (pendings dropped; scan_delta repairs)",
        ).inc()

    def _count_delta_rows(self, n: int) -> None:
        if not n:
            return
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_region_cache_delta_rows_total",
            "Rows re-decoded by incremental delta applies",
        ).inc(n)

    def _gauge_bytes(self, full: bool = True) -> None:
        total = sum(i.nbytes for i in self._images.values())
        self.stats.bytes_pinned = total
        from ..util.metrics import REGISTRY

        REGISTRY.gauge(
            "tikv_coprocessor_region_cache_bytes",
            "Resident (encoded) bytes held by region images",
        ).set(total)
        # compressed-residency observability (docs/compressed_columns.md):
        # the ratio the budget win rides on, and the TRUE bytes pinned in
        # HBM right now (summed over every image's device signatures — with
        # encoded residency these are the narrow/encoded payloads, not a
        # host-side proxy).  These walk every image's columns and pin trees,
        # so delta/wt_delta applies (the write hot path, under this lock)
        # pass full=False and the heartbeat/build/drop paths refresh them.
        if full:
            decoded = sum(
                i.block_cache.nbytes_decoded() for i in self._images.values()
            )
            resident = sum(
                i.block_cache.nbytes() for i in self._images.values()
            )
            REGISTRY.gauge(
                "tikv_coprocessor_region_cache_compression_ratio",
                "Decoded-vs-resident byte ratio of the warm column blocks",
            ).set(decoded / resident if resident else 1.0)
            REGISTRY.gauge(
                "tikv_coprocessor_region_cache_device_pinned_bytes",
                "True bytes currently pinned on devices by region images",
            ).set(sum(
                i.block_cache.device_nbytes() for i in self._images.values()
            ))
            if self._tenant_budgets:
                per: dict[str, int] = {}
                for img in self._images.values():
                    per[img.tenant] = per.get(img.tenant, 0) + img.nbytes
                g = REGISTRY.gauge(
                    "tikv_overload_hbm_bytes",
                    "Resident bytes per tenant HBM partition",
                )
                for tenant in set(per) | set(self._tenant_budgets):
                    g.set(per.get(tenant, 0), tenant=tenant)
        if self.devices:
            g = REGISTRY.gauge(
                "tikv_coprocessor_region_cache_device_bytes",
                "Bytes pinned per owner device (sharded placement)",
            )
            for d in self.devices:
                g.set(self._device_bytes.get(d.id, 0), device=str(d.id))
