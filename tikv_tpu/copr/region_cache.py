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
* delta: a newer ``apply_index`` (or a later ``start_ts`` while future
  versions exist) triggers ``mvcc_batch.scan_delta``: one vectorized pass
  over the CF_WRITE *keys* finds rows whose version fingerprint moved; only
  those rows re-resolve and re-decode.  Pure in-place updates patch the
  pinned device arrays with ``.at[].set`` scatters; inserts/deletes repack
  the host blocks (still no KV decode) and drop the pins to rebuild lazily.
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
entry's apply index.  The parsed delta (changed handles/values/commit_ts,
deleted handles, lock touches) is buffered on the image as a PENDING delta;
the next warm read folds it in under the manager lock and serves WITHOUT
any CF_WRITE scan — ``scan_delta`` stays as the fallback whenever emission
is off (``apply_emit_write_delta`` failpoint, config), an op is not
vectorizable, or the pending chain has a gap (detected via the per-region
notify watermark; see docs/write_path.md for the contract).

Concurrency: cache resolution (lookup / build / delta apply) serializes
under the manager lock, but the evaluator reads the image's blocks after
``serve`` returns — a delta applying concurrently with another request's
read of the SAME image could tear that read.  Deltas mutate blocks only on
the serve path (write-through emission merely buffers pending rows), so
this needs a reader still in flight when a LATER read's fold-in lands;
endpoints that serve a region from multiple threads should serialize per
region.  ``apply_index`` is propagated end-to-end: ``RegionSnapshot``
carries the peer's applied index, and the endpoint reads region identity,
epoch and apply index straight off the snapshot — raft-backed deployments
need no context plumbing (explicit context still wins for tests and
embedded engines).
"""

from __future__ import annotations

import time
import weakref

import numpy as np

from ..analysis import bufsan as _bufsan
from ..analysis.sanitizer import make_rlock
from ..storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE
from ..storage.mvcc import Statistics
from ..storage.mvcc.reader import _check_lock
from ..storage.txn_types import Key, Write, WriteType, append_ts, split_ts
from ..util import trace
from . import encoding as _encoding
from . import integrity as _integrity
from .byterows import ByteRows
from .cache import ColumnBlockCache
from .datatypes import Column, EvalType
from .mvcc_batch import MvccBatchScanSource, scan_delta
from .table import RowBatchDecoder, decode_record_handles, decode_record_key, record_key

DEFAULT_BYTE_BUDGET = 256 << 20
DEFAULT_MAX_REGIONS = 64
_REBUILD_FRACTION = 0.25  # delta bigger than this fraction of rows ⇒ rebuild
_TOKEN_UNSET = object()  # cache not yet bound to an engine's data_token

_CACHES: "weakref.WeakSet[RegionColumnCache]" = weakref.WeakSet()


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
    """Parse a committed batch's ops into ``(writes, lock_keys)`` —
    ``writes`` = [(raw_key, commit_ts, value | None-for-delete)] in batch
    order, ``lock_keys`` = raw keys whose CF_LOCK state changed.  Returns
    None when any CF_WRITE op is not expressible as an incremental row
    change (delete/delete_range on CF_WRITE, exotic records, a missing
    CF_DEFAULT value) — the caller then degrades to the scan_delta path."""
    writes: list[tuple[bytes, int, bytes | None]] = []
    lock_keys: list[bytes] = []
    for op, cf, key, val in ops:
        if cf == CF_LOCK:
            try:
                lock_keys.append(Key.from_encoded(key).to_raw())
            except Exception:  # noqa: BLE001 — undecodable lock key
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
    return writes, lock_keys


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
        self.apply_index = -1
        self.snapshot_ts = -1
        self.max_commit_ts = 0
        self.handles = np.empty(0, dtype=np.int64)
        self.row_commit_ts = np.empty(0, dtype=np.int64)
        self.block_cache = ColumnBlockCache(key=key)
        self.decoder = RowBatchDecoder(schema)
        self.nbytes = 0
        # compressed residency (docs/compressed_columns.md): whether fill
        # ran the encoding stats pass, and which columns it encoded
        self.encode_enabled = False
        self.encodings: dict[int, str] = {}
        # bytes->code maps for dict-encoded columns, built on first delta
        self._dict_maps: dict[int, dict] = {}
        # write-through pending delta (apply_write buffers; serve folds in):
        # {"base", "apply_index", "changed": {handle: (value, cts)},
        #  "deleted": set[handle], "max_ct"} or None
        self.wt_pending: dict | None = None
        # a write-through batch touched CF_LOCK in range: the next warm
        # serve must re-scan locks even at an unchanged start_ts.  Cleared
        # only when a lock-free scan ran on a snapshot at/after the batch
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
        self.row_fp = np.empty(0, dtype=np.uint64)
        self.row_nbytes = np.empty(0, dtype=np.int64)
        self.fp_value = 0      # fold(row_fp): the warm Checksum answer
        self.fp_integrity = 0  # fold(mix_fp(row_fp, row_commit_ts))

    @property
    def n_rows(self) -> int:
        return len(self.handles)

    def _offsets(self) -> np.ndarray:
        nv = np.array([b.n_valid for b in self.block_cache.blocks], dtype=np.int64)
        return np.concatenate([[0], np.cumsum(nv)])

    def _recount(self) -> None:
        self.nbytes = (
            self.block_cache.nbytes() + self.handles.nbytes + self.row_commit_ts.nbytes
        )

    # -- build -------------------------------------------------------------

    def fill(self, handles: np.ndarray, values: list[bytes], cts: np.ndarray,
             max_commit_ts: int, apply_index: int, start_ts: int,
             raw_keys: list[bytes] | None = None, encode: bool = False) -> None:
        self.handles = handles
        self.row_commit_ts = cts
        with trace.stage("fill.fingerprint"):
            self._init_fingerprint(handles, values, raw_keys)
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
        self.wt_pending = None  # a rebuild reflects the engine directly
        self._recount()

    # -- integrity fingerprint ---------------------------------------------

    def _init_fingerprint(self, handles, values, raw_keys) -> None:
        """Compute the per-row integrity hashes at build time.  Delta folds
        reconstruct raw keys from (table_id, handle), so a single-table
        range is required — raw record keys ARE (table_id, handle) encoded,
        making the reconstruction exact."""
        self.fp_valid = False
        self.table_id = None
        try:
            if raw_keys is None:
                self.table_id = self._table_id_from_ranges()
                if self.table_id is None:
                    return
                raw_keys = [record_key(self.table_id, int(h)) for h in handles]
            elif len(raw_keys):
                tid_first = decode_record_key(raw_keys[0])[0]
                # keys are sorted: same first/last table prefix = one table
                if decode_record_key(raw_keys[-1])[0] != tid_first:
                    return
                self.table_id = tid_first
            else:
                self.table_id = self._table_id_from_ranges()
            keys, vals = ByteRows.of(raw_keys), ByteRows.of(values)
            self.row_fp = _integrity.row_checksums(keys, vals)
            self.row_nbytes = keys.lens + vals.lens
        except Exception:  # noqa: BLE001 — exotic keys: plane off, serve on
            self.row_fp = np.empty(0, dtype=np.uint64)
            self.row_nbytes = np.empty(0, dtype=np.int64)
            self.fp_value = self.fp_integrity = 0
            return
        self.fp_valid = True
        self._refold()

    def _table_id_from_ranges(self) -> int | None:
        from ..util import codec as _codec

        tids = set()
        for start, _end in self.key[1]:
            if len(start) < 9 or start[:1] != b"t":
                return None
            tids.add(_codec.decode_i64(start, 1))
        return tids.pop() if len(tids) == 1 else None

    def _refold(self) -> None:
        self.fp_value = _integrity.fold(self.row_fp)
        self.fp_integrity = _integrity.fold(
            _integrity.mix_fp(self.row_fp, self.row_commit_ts)
        )

    def _invalidate_fp(self) -> None:
        """An unhashable delta landed: the fingerprint plane turns off for
        this image (it would otherwise drift silently)."""
        self.fp_valid = False
        self.row_fp = np.empty(0, dtype=np.uint64)
        self.row_nbytes = np.empty(0, dtype=np.int64)
        self.fp_value = self.fp_integrity = 0

    def checksum_parts(self) -> tuple[int, int, int] | None:
        """(checksum, total_kvs, total_bytes) exactly as the CPU-oracle
        Checksum scan would answer over this image's rows, or None when the
        fingerprint plane is off for this image."""
        if not self.fp_valid:
            return None
        return self.fp_value, self.n_rows, int(self.row_nbytes.sum())

    # -- delta -------------------------------------------------------------

    def apply_delta(self, delta: dict, apply_index: int, start_ts: int) -> int:
        """Apply a ``mvcc_batch.scan_delta`` result; returns rows touched."""
        ch = delta["changed_handles"]
        dh = delta["deleted_handles"]
        n_touched = len(ch) + len(dh)
        if n_touched:
            pos = np.searchsorted(self.handles, ch)
            pos_c = np.minimum(pos, max(self.n_rows - 1, 0))
            in_place = (
                len(dh) == 0
                and self.n_rows > 0
                and bool((self.handles[pos_c] == ch).all())
            )
            cols = (
                self.decoder.decode(ch, delta["changed_values"]) if len(ch) else None
            )
            # fingerprint fold (docs/integrity.md): hash the delta rows off
            # the RAW value chain before decode touches them — the fold
            # tracks what the image will CONTAIN, the scrubber's oracle says
            # what it SHOULD contain
            new_fp = new_nb = None
            if self.fp_valid:
                try:
                    dkeys = [record_key(self.table_id, int(h)) for h in ch]
                    new_fp = _integrity.row_checksums(dkeys, delta["changed_values"])
                    new_nb = np.fromiter(
                        (len(k) + len(v)
                         for k, v in zip(dkeys, delta["changed_values"])),
                        dtype=np.int64, count=len(ch),
                    )
                except Exception:  # noqa: BLE001 — unhashable: plane off
                    self._invalidate_fp()
            if in_place:
                if self.fp_valid:
                    cts_new = np.asarray(delta["changed_commit_ts"], dtype=np.int64)
                    old_fp = self.row_fp[pos]
                    old_mix = _integrity.mix_fp(old_fp, self.row_commit_ts[pos])
                    self.fp_value ^= _integrity.fold(old_fp) ^ _integrity.fold(new_fp)
                    self.fp_integrity ^= _integrity.fold(old_mix) ^ _integrity.fold(
                        _integrity.mix_fp(new_fp, cts_new)
                    )
                    self.row_fp[pos] = new_fp
                    self.row_nbytes[pos] = new_nb
                self._apply_updates(pos, cols, ch, delta["changed_commit_ts"])
            else:
                self._apply_structural(ch, cols, delta["changed_commit_ts"], dh,
                                       new_fp, new_nb)
        self.apply_index = apply_index
        self.snapshot_ts = start_ts
        self.max_commit_ts = delta["max_commit_ts"]
        self._recount()
        return n_touched

    def _code_of(self, ci: int, blocks, value: bytes) -> int:
        """Image dictionary code for ``value`` on column ``ci``, appending a
        new entry (shared across every block) when unseen."""
        dmap = self._dict_maps.get(ci)
        dictionary = blocks[0].cols[ci].dictionary
        if dmap is None:
            dmap = self._dict_maps[ci] = {bytes(v): j for j, v in enumerate(dictionary)}
        code = dmap.get(value)
        if code is None:
            code = len(dmap)
            dmap[value] = code
            grown = np.empty(code + 1, dtype=object)
            grown[:code] = dictionary
            grown[code] = value
            for b in blocks:
                b.cols[ci].dictionary = grown
        return code

    def _delta_cell(self, ci: int, blocks, col: Column, r: int):
        """(value, is_null) of delta row ``r`` in the image's representation."""
        nl = bool(np.asarray(col.nulls)[r])
        image_col = blocks[0].cols[ci] if blocks else None
        dict_encoded = image_col is not None and image_col.is_dict_encoded
        if isinstance(image_col, _encoding.EncodedColumn):
            # int-family lanes by construction — and the ``.data`` probe
            # below would permanently cache a full decode the encoded byte
            # budget never accounted for
            obj_col = False
        else:
            obj_col = (
                image_col.data.dtype == object
                if image_col is not None and isinstance(image_col.data, np.ndarray)
                else self.schema[ci].ftype.eval_type in (EvalType.BYTES, EvalType.JSON)
                and not dict_encoded
            )
        if nl:
            return (b"" if obj_col and not dict_encoded else 0), True
        v = col.decoded().data[r] if col.is_dict_encoded else col.data[r]
        if dict_encoded:
            return self._code_of(ci, blocks, bytes(v)), False
        return v, False

    def _apply_updates(self, pos: np.ndarray, cols, ch: np.ndarray, cts: np.ndarray) -> None:
        """In-place row updates: mutate host arrays (patching encoded
        payloads where the encoding survives — docs/compressed_columns.md),
        scatter device pins."""
        blocks = self.block_cache.blocks
        offsets = self._offsets()
        bi_arr = np.searchsorted(offsets, pos, side="right") - 1
        if _bufsan.enabled():
            # mutation choke point: the fold is about to write these host
            # arrays in place — any of them still exposed (wire part mid
            # sendmsg, shadow-read snapshot) is a violation.  Encoded
            # columns list their payload arrays, never ``.data`` (the
            # property would cache a full decode).
            bufs: list = [self.row_commit_ts]
            for bi in np.unique(bi_arr):
                for col in blocks[int(bi)].cols:
                    if isinstance(col, _encoding.EncodedColumn):
                        bufs.extend(a for a in (col.packed, col.run_values,
                                                col.run_ends, col.run_nulls)
                                    if a is not None)
                    else:
                        bufs.append(col.data)
                        bufs.append(col.nulls)
            _bufsan.note_mutation(bufs, site="region_cache._apply_updates")
        # any in-place update to an RLE column breaks its runs: demote it
        # image-wide up front (decode-on-next-serve), so the assignments
        # below land on plain decoded arrays
        for ci in range(len(self.schema)):
            if self.schema[ci].is_pk_handle:
                continue
            c0 = blocks[0].cols[ci] if blocks else None
            if isinstance(c0, _encoding.EncodedColumn) and c0.kind == "rle":
                _encoding.demote_column(self.block_cache, ci, "inplace_update")
        updates: dict[int, tuple[np.ndarray, dict]] = {}
        for bi in np.unique(bi_arr):
            sel = np.flatnonzero(bi_arr == bi)
            rows = (pos[sel] - offsets[bi]).astype(np.int64)
            per_col: dict[int, tuple[np.ndarray, np.ndarray]] = {}
            for ci, col in enumerate(cols):
                if self.schema[ci].is_pk_handle:
                    continue  # handles are the row identity — never change
                image_col = blocks[int(bi)].cols[ci]
                vals = np.empty(len(sel), dtype=_encoding.host_dtype(image_col))
                nls = np.zeros(len(sel), dtype=bool)
                for j, si in enumerate(sel):
                    v, nl = self._delta_cell(ci, blocks, col, int(si))
                    vals[j] = v
                    nls[j] = nl
                if isinstance(image_col, _encoding.EncodedColumn):
                    if not image_col.try_patch(rows, vals, nls):
                        # the new values don't fit the narrow lanes: demote
                        # the column image-wide and write decoded
                        _encoding.demote_column(
                            self.block_cache, ci, "value_range")
                        image_col = blocks[int(bi)].cols[ci]
                        image_col.data[rows] = vals.astype(
                            image_col.data.dtype, copy=False)
                        image_col.nulls[rows] = nls
                else:
                    d = np.asarray(image_col.data)
                    if (image_col.dictionary is not None and d.dtype != object
                            and d.dtype.kind in "iu" and d.dtype.itemsize < 8
                            and len(vals)
                            and _encoding.ensure_code_capacity(
                                blocks, ci, int(vals.max()))):
                        # narrowed code lanes widened (a delta grew the
                        # dictionary past them) — pins rebuild from host
                        self.block_cache.enc_version += 1
                        self.block_cache.drop_device()
                        image_col = blocks[int(bi)].cols[ci]
                    image_col.data[rows] = vals.astype(
                        np.asarray(image_col.data).dtype, copy=False)
                    image_col.nulls[rows] = nls
                per_col[ci] = (vals, nls)
            updates[int(bi)] = (rows, per_col)
        self.row_commit_ts[pos] = cts
        self.block_cache.scatter_update(updates)

    def _apply_structural(self, ch: np.ndarray, cols, cts: np.ndarray, dh: np.ndarray,
                          new_fp: np.ndarray | None = None,
                          new_nb: np.ndarray | None = None) -> None:
        """Inserts and/or deletes: repack host blocks from the resident
        columns (no KV decode) and drop device pins to rebuild lazily.
        ``new_fp``/``new_nb`` are the changed rows' integrity hashes/sizes —
        mirrored through the same delete/update/insert index math as
        ``row_commit_ts`` so the fingerprint arrays stay row-aligned."""
        # repacks build NEW arrays (concatenate copies) so exposed buffers
        # are never written — but the old image is about to be replaced, so
        # sweep the ledger once: anything already corrupted reports here
        # with its export stack instead of at a far-away release
        _bufsan.verify_all(site="region_cache._apply_structural")
        if self.fp_valid and new_fp is None and len(ch):
            self._invalidate_fp()
        fp = self.row_fp if self.fp_valid else None
        nb = self.row_nbytes if self.fp_valid else None
        blocks = self.block_cache.blocks
        n_old = self.n_rows
        # global view of each column, preserving dictionary codes
        gdata, gnulls = [], []
        for ci in range(len(self.schema)):
            if blocks:
                g = np.concatenate([np.asarray(b.cols[ci].data) for b in blocks])
                if (blocks[0].cols[ci].dictionary is not None
                        and g.dtype != object and g.dtype.kind in "iu"
                        and g.dtype.itemsize < 8):
                    # narrowed code lanes widen for the repack math (new
                    # codes may exceed them); re-encode below re-narrows
                    g = g.astype(np.int64)
                gdata.append(g)
                gnulls.append(np.concatenate([np.asarray(b.cols[ci].nulls) for b in blocks]))
            else:
                et = self.schema[ci].ftype.eval_type
                dtype = (
                    object if et in (EvalType.BYTES, EvalType.JSON)
                    else np.float64 if et == EvalType.REAL
                    else np.int64
                )
                gdata.append(np.empty(0, dtype=dtype))
                gnulls.append(np.empty(0, dtype=bool))
        handles = self.handles
        row_cts = self.row_commit_ts
        if len(dh) and n_old:
            keep = np.ones(n_old, dtype=bool)
            dpos = np.searchsorted(handles, dh)
            ok = dpos < n_old
            ok &= handles[np.minimum(dpos, n_old - 1)] == dh
            keep[dpos[ok]] = False
            sel = np.flatnonzero(keep)
            handles = handles[sel]
            row_cts = row_cts[sel]
            if fp is not None:
                fp = fp[sel]
                nb = nb[sel]
            gdata = [d[sel] for d in gdata]
            gnulls = [nl[sel] for nl in gnulls]
        if len(ch):
            # split changed rows into updates of surviving rows vs inserts
            pos = np.searchsorted(handles, ch)
            pos_c = np.minimum(pos, max(len(handles) - 1, 0))
            is_upd = (len(handles) > 0) & (handles[pos_c] == ch) if len(handles) else (
                np.zeros(len(ch), dtype=bool)
            )
            new_vals: list[list] = [[] for _ in self.schema]
            new_nulls: list[list] = [[] for _ in self.schema]
            for r in range(len(ch)):
                for ci, col in enumerate(cols):
                    if self.schema[ci].is_pk_handle:
                        v, nl = int(ch[r]), False
                    else:
                        v, nl = self._delta_cell(ci, blocks, col, r)
                    new_vals[ci].append(v)
                    new_nulls[ci].append(nl)
            upd_idx = np.flatnonzero(np.asarray(is_upd))
            for ci in range(len(self.schema)):
                if len(upd_idx) and not self.schema[ci].is_pk_handle:
                    gdata[ci][pos_c[upd_idx]] = np.array(
                        [new_vals[ci][int(i)] for i in upd_idx], dtype=gdata[ci].dtype
                    )
                    gnulls[ci][pos_c[upd_idx]] = np.array(
                        [new_nulls[ci][int(i)] for i in upd_idx], dtype=bool
                    )
            if len(upd_idx):
                row_cts = row_cts.copy()
                row_cts[pos_c[upd_idx]] = cts[upd_idx]
                if fp is not None:
                    fp = fp.copy()
                    nb = nb.copy()
                    fp[pos_c[upd_idx]] = new_fp[upd_idx]
                    nb[pos_c[upd_idx]] = new_nb[upd_idx]
            ins_idx = np.flatnonzero(~np.asarray(is_upd))
            if len(ins_idx):
                ins_h = ch[ins_idx]
                ins_at = np.searchsorted(handles, ins_h)
                handles = np.insert(handles, ins_at, ins_h)
                row_cts = np.insert(row_cts, ins_at, cts[ins_idx])
                if fp is not None:
                    fp = np.insert(fp, ins_at, new_fp[ins_idx])
                    nb = np.insert(nb, ins_at, new_nb[ins_idx])
                for ci in range(len(self.schema)):
                    ivals = np.array(
                        [new_vals[ci][int(i)] for i in ins_idx], dtype=gdata[ci].dtype
                    )
                    gdata[ci] = np.insert(gdata[ci], ins_at, ivals)
                    gnulls[ci] = np.insert(
                        gnulls[ci], ins_at, np.array([new_nulls[ci][int(i)] for i in ins_idx], dtype=bool)
                    )
        self.handles = handles
        self.row_commit_ts = row_cts
        if fp is not None:
            self.row_fp = fp
            self.row_nbytes = nb
            # the repack is already O(n): a vectorized re-fold is simpler
            # than incrementally retiring the deleted rows' contributions
            self._refold()
        # re-chunk into blocks (views over the global arrays) and drop pins
        templates = [blocks[0].cols[ci] if blocks else None for ci in range(len(self.schema))]
        self.block_cache.clear_blocks()  # drops pins WITH accounting
        br = self.block_rows
        n = len(handles)
        for s in range(0, n, br):
            e = min(s + br, n)
            bcols = []
            for ci in range(len(self.schema)):
                t = templates[ci]
                bcols.append(Column(
                    t.eval_type if t is not None else self.schema[ci].ftype.eval_type,
                    gdata[ci][s:e],
                    gnulls[ci][s:e],
                    t.frac if t is not None else self.schema[ci].ftype.decimal,
                    t.dictionary if t is not None else None,
                ))
            self.block_cache.add(bcols, e - s)
        self.block_cache.filled = True
        if self.encode_enabled:
            # structural repacks re-run the stats pass: the rebuilt plain
            # blocks re-encode from fresh value ranges/runs (no KV decode —
            # the repack above already stayed on resident columns)
            self.encodings = _encoding.encode_blocks(self.block_cache, self.schema)
        self.block_cache.drop_device()


class RegionCacheStats:
    __slots__ = ("hits", "misses", "deltas", "delta_rows", "stale",
                 "below_snapshot", "uncacheable",
                 "evictions", "invalidations", "bytes_pinned",
                 "wt_deltas", "wt_rows", "wt_lost")

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
        # the stage is the whole call LESS the lock check and any build or
        # delta repair: those are stages of their own, and suspend this one
        with trace.stage("cache.lookup") as st:
            *out, below = self._serve(snap, context, columns_info, ranges,
                                      start_ts, statistics)
            st.tag(outcome=out[1])
            if below:
                st.tag(below_snapshot=1)
        return tuple(out)

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
        with self._mu:
            if self._wt_token is _TOKEN_UNSET:
                # bind to the engine behind the first served snapshot —
                # from here on, only ITS write-through notifies are accepted.
                # Notifies BEFORE this bind were dropped unseen, so pending
                # creation stays gated until the stream re-anchors.
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
        with self._mu:
            if self._images.get(key) is not img or img.epoch != epoch:
                # raced with an invalidation between lookup and here
                self.stats.uncacheable += 1
                self._count("uncacheable")
                return None, "uncacheable", 0, False
            below = start_ts < img.snapshot_ts
            fresh = self._hit_fresh_locked(img, apply_index, start_ts, snap,
                                           ranges, stats)
            if below:
                self._count_below_snapshot(served=fresh)
            if fresh:
                self.stats.hits += 1
                self._count("hit")
                return img.block_cache, "hit", 0, below
            if below:
                # the image may hold rows this reader must not see: only a
                # fresh scan can answer it
                self.stats.stale += 1
                self._count("stale")
                return None, "stale", 0, True
            pend = img.wt_pending
            if (pend is not None
                    and img.apply_index > apply_index):
                # reader's snapshot predates the image: the scan_delta below
                # would rewind the image under the pending chain's base —
                # keep the pending for current readers, serve this one cold
                self.stats.stale += 1
                self._count("stale")
                return None, "stale", 0, False
            if (pend is not None
                    and apply_index >= pend["apply_index"]
                    and img.apply_index >= pend["base"]
                    and img.max_commit_ts <= img.snapshot_ts
                    and start_ts >= pend["max_ct"]):
                # write-through fast path: every data batch between the
                # image's state and the reader's snapshot is buffered here —
                # fold it in and serve with ZERO CF_WRITE scans.  Locks are
                # the one thing a buffered batch cannot prove absent, so a
                # dirty lock state re-scans CF_LOCK (tiny) first.
                if img.locks_dirty or start_ts > img.snapshot_ts:
                    seen = self._check_locks(img, snap, ranges, start_ts, stats)
                    if seen == 0 and apply_index >= img.locks_dirty_at:
                        img.locks_dirty = False
                n_touch = len(pend["changed"]) + len(pend["deleted"])
                if n_touch == 0:
                    # the batches touched nothing in this image's ranges
                    # (another table/index in the region, lock-only traffic):
                    # advance the version bookkeeping and serve a plain HIT —
                    # no fold, no device re-placement churn
                    img.apply_index = apply_index
                    img.snapshot_ts = max(img.snapshot_ts, start_ts)
                    img.max_commit_ts = max(img.max_commit_ts, pend["max_ct"])
                    img.wt_pending = None
                    self.stats.hits += 1
                    self._count("hit")
                    return img.block_cache, "hit", 0, False
                if img.n_rows and n_touch > _REBUILD_FRACTION * img.n_rows:
                    self._drop(key, reason="delta_too_big")
                    return *self._build(key, epoch, snap, columns_info,
                                        ranges, start_ts, apply_index, stats,
                                        tenant=tenant), False
                handles = np.array(sorted(pend["changed"]), dtype=np.int64)
                delta = {
                    "changed_handles": handles,
                    "changed_values": [pend["changed"][int(h)][0] for h in handles],
                    "changed_commit_ts": np.array(
                        [pend["changed"][int(h)][1] for h in handles], dtype=np.int64),
                    "deleted_handles": np.array(sorted(pend["deleted"]), dtype=np.int64),
                    "max_commit_ts": max(img.max_commit_ts, pend["max_ct"]),
                }
                with trace.stage("cache.fill", kind="wt_delta") as st:
                    n = img.apply_delta(delta, apply_index, start_ts)
                    img.wt_pending = None
                    if self.devices:
                        self._unplace(img)
                        self._place(img)
                    st.tag(rows=n)
                self.stats.wt_deltas += 1
                self.stats.wt_rows += n
                self._count("wt_delta")
                self._count_delta_rows(n)
                self._enforce_budget(keep=key)
                self._gauge_bytes(full=False)
                return img.block_cache, "wt_delta", n, False
            with trace.stage("cache.fill", kind="scan_delta"):
                # lint: allow(lock-blocking-call) -- the fold-in must be atomic
                # with the image version bump (docs: Concurrency); the scan is
                # bounded by the delta size, and cold BUILDS run outside the lock
                delta = scan_delta(snap, start_ts, ranges, img.handles,
                                   img.row_commit_ts, statistics=stats)
            if delta is None:
                self.stats.uncacheable += 1
                self._count("uncacheable")
                self._drop(key, reason="unvectorizable")
                return None, "uncacheable", 0, False
            n_touch = len(delta["changed_handles"]) + len(delta["deleted_handles"])
            if img.n_rows and n_touch > _REBUILD_FRACTION * img.n_rows:
                self._drop(key, reason="delta_too_big")
                return *self._build(key, epoch, snap, columns_info, ranges,
                                    start_ts, apply_index, stats,
                                    tenant=tenant), False
            with trace.stage("cache.fill", kind="delta") as st:
                n = img.apply_delta(delta, apply_index, start_ts)
                st.tag(rows=n)
            if apply_index >= img.locks_dirty_at:
                # scan_delta lock-checked the ranges on a snapshot that
                # contains the dirtying batch
                img.locks_dirty = False
            pend = img.wt_pending
            if pend is not None and (pend["apply_index"] <= img.apply_index
                                     or img.apply_index < pend["base"]):
                # the scan repaired past the pending chain (or rewound under
                # its base): replaying it would regress rows — drop it
                img.wt_pending = None
            if self.devices:
                # a structural repack can change the block count and bytes:
                # refresh the placement so owner_devices stays block-aligned
                with trace.stage("cache.fill", kind="place"):
                    self._unplace(img)
                    self._place(img)
            self.stats.deltas += 1
            self.stats.delta_rows += n
            self._count("delta")
            self._count_delta_rows(n)
            self._enforce_budget(keep=key)
            self._gauge_bytes(full=False)
            return img.block_cache, "delta", n, False

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
        prove absent — a dirty/newer-ts serve re-scans CF_LOCK exactly like
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
        with self._mu:
            for key, img in self._images.items():
                if key[0] != region_id or key[1] != rkey:
                    continue
                if img.epoch != epoch or not img.fp_valid:
                    continue
                img_below = start_ts < img.snapshot_ts
                # the hit path's exact freshness + stale-guard + lock rules
                # (ONE definition — _hit_fresh_locked — so the warm
                # Checksum path can never drift from what a served hit
                # would have answered)
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

    # -- write-through intake (raft apply -> pending deltas) -----------------

    def apply_write(self, region_id: int, parse_once, apply_index: int,
                    token=None) -> None:
        """Buffer a committed batch's row changes on every resident image of
        ``region_id``.  Raft applies a region's entries in order on one
        worker, so notifies arrive in apply-index order per region; an index
        at or below the watermark is a replica's replay of a batch already
        merged (identical ops by raft) and is skipped.  ``parse_once`` is
        the notify's memoized op parser — invoked OUTSIDE the manager lock
        (it may read CF_DEFAULT), at most once across every live cache."""
        with self._mu:
            if self._wt_token is _TOKEN_UNSET or token != self._wt_token:
                return  # not this cache's engine (or cache never served yet)
            prev = self._wt_seen.get(region_id, -1)
            if apply_index <= prev:
                return
            # the watermark advances even with write_through off: flipping
            # it back on must not let a pending start across unseen batches
            self._wt_seen[region_id] = apply_index
            if not self.write_through:
                # an unbuffered batch gaps any surviving chain — drop it,
                # or re-enabling would merge later batches into the gap
                self._drop_pendings_locked(region_id)
                return
            if not any(k[0] == region_id for k in self._images):
                return
        parsed = parse_once()
        with self._mu:
            # images may have churned while parsing: re-list.  A freshly
            # built image already containing this batch just replays it
            # idempotently; the ``prev`` creation check below still blocks
            # any image whose snapshot predates an unbuffered notify.
            imgs = [img for k, img in self._images.items() if k[0] == region_id]
            if not imgs:
                return
            if parsed is None:
                # not expressible as row changes: pendings are now gapped
                for img in imgs:
                    img.wt_pending = None
                self.stats.wt_lost += 1
                self._count_wt_lost()
                return
            writes, lock_keys = parsed
            for img in imgs:
                self._merge_pending(img, writes, lock_keys, prev, apply_index)

    def note_write_lost(self, region_id: int, apply_index: int,
                        token=None) -> None:
        """A data change of unknown content landed (emission off, raft
        snapshot apply, merge catch-up, OR a notify that faulted after the
        watermark already advanced): drop pendings unconditionally — a
        dropped chain only costs a scan_delta repair, while a chain kept
        across an unbuffered batch serves wrong rows forever — and advance
        the watermark so no pending restarts until a read catches the image
        up past ``apply_index``."""
        with self._mu:
            if self._wt_token is _TOKEN_UNSET or token != self._wt_token:
                return
            if apply_index > self._wt_seen.get(region_id, -1):
                self._wt_seen[region_id] = apply_index
            self._drop_pendings_locked(region_id)

    def _drop_pendings_locked(self, region_id: int) -> None:
        dropped = False
        for k, img in self._images.items():
            if k[0] == region_id and img.wt_pending is not None:
                img.wt_pending = None
                dropped = True
        if dropped:
            self.stats.wt_lost += 1
            self._count_wt_lost()

    def _merge_pending(self, img, writes, lock_keys, prev: int,
                       apply_index: int) -> None:
        ranges = img.key[1]
        if any(_in_ranges(rk, ranges) for rk in lock_keys):
            img.locks_dirty = True
            img.locks_dirty_at = max(img.locks_dirty_at, apply_index)
        pend = img.wt_pending
        if pend is None:
            if prev > img.apply_index or apply_index <= img.apply_index:
                # a batch between the image's state and this one was never
                # buffered (image built mid-stream, or emission was off):
                # this image repairs through scan_delta, not a gapped chain
                return
            if self._wt_late_bound and prev < 0:
                # first observed notify for this region on a LATE-bound
                # cache: earlier notifies may have been dropped unseen
                # while unbound, so this chain cannot anchor — the next
                # read repairs via scan_delta, re-anchoring the stream
                return
            pend = img.wt_pending = {
                "base": img.apply_index, "apply_index": apply_index,
                "changed": {}, "deleted": set(), "max_ct": 0,
            }
        else:
            pend["apply_index"] = apply_index
        for raw, cts, v in writes:
            if not _in_ranges(raw, ranges):
                continue
            if len(raw) != 19:
                # non-record key inside a record range: not foldable
                self._drop_pending_img(img)
                return
            try:
                h = int(decode_record_handles([raw])[0])
            except Exception:  # noqa: BLE001
                self._drop_pending_img(img)
                return
            if v is None:
                pend["changed"].pop(h, None)
                pend["deleted"].add(h)
            else:
                pend["deleted"].discard(h)
                pend["changed"][h] = (v, cts)
            pend["max_ct"] = max(pend["max_ct"], cts)
        if len(pend["changed"]) + len(pend["deleted"]) > max(1024, img.n_rows):
            # pending outgrew the image: a rebuild will beat replaying it
            self._drop_pending_img(img)

    def _drop_pending_img(self, img) -> None:
        """Drop ONE image's pending chain, keeping the wt_lost accounting in
        step with every other drop path (the Grafana emission-gap series
        must see these, or a rising scan_delta rate is undiagnosable)."""
        if img.wt_pending is not None:
            img.wt_pending = None
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
            img.fill(handles, values, src.row_commit_ts, src.max_commit_ts,
                     apply_index, start_ts, raw_keys=keys,
                     encode=self.encode_columns)
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
        """ONE definition of hit-path freshness (serve()'s hits AND the
        warm Checksum path): True iff the image may serve ``start_ts``
        as-is at ``apply_index``.  Re-scans CF_LOCK when it must (raising
        on a blocking lock, exactly like the oracle scan would) and
        maintains ``locks_dirty`` / ``snapshot_ts`` like a served hit.
        Caller holds the manager lock."""
        below = start_ts < img.snapshot_ts
        # below: a reader another session overtook.  snapshot_ts was raised
        # past this start_ts, the rows were not: at apply_index the image
        # holds exactly the rows of commits at or below max_commit_ts, so a
        # reader of the same engine state at or above that timestamp sees
        # those rows and no others (docs/region_column_cache.md, "Readers
        # below the snapshot").  Any other reader below the snapshot may be
        # shown rows committed above its timestamp.
        if not (apply_index == img.apply_index and (
                start_ts >= img.max_commit_ts if below
                else (start_ts == img.snapshot_ts
                      or img.max_commit_ts <= img.snapshot_ts))):
            return False
        if start_ts != img.snapshot_ts or img.locks_dirty:
            # at the READER's start_ts, below the snapshot as above it
            seen = self._check_locks(img, snap, ranges, start_ts, stats)
            if seen == 0 and apply_index >= img.locks_dirty_at:
                # this snapshot contains the dirtying batch and the range is
                # lock-free — safe to stop re-scanning.  An OLDER snapshot
                # seeing no locks proves nothing.
                img.locks_dirty = False
            # never lowered: a reader below it leaves it where it was
            img.snapshot_ts = max(img.snapshot_ts, start_ts)
        return True

    def _check_locks(self, img, snap, ranges, ts, stats) -> int:
        """Raise on a blocking lock; return how many locks the ranges hold
        (0 lets callers clear a dirty-lock flag).

        The CF_LOCK scan is skipped (``how=memo``) only where ``snap`` cannot
        differ, in CF_LOCK, from a snapshot whose scan of these ranges met no
        lock: an empty range blocks no reader whatever its timestamp.  The
        witness is the engine's own: ``snap`` reads at sequence S, the memo's
        snapshot read at S0, and no batch has touched CF_LOCK after the older
        of the two (``cf_touched_seq``, read after both were taken, so it can
        only be too high).  A snapshot that cannot say, a dirty image, or
        any CF_LOCK write anywhere in the store since, scans as before."""
        from ..util.metrics import REGISTRY

        seen = 0
        walked0 = stats.lock.next
        with trace.stage("cache.lock_check") as st:
            seq = snap.sequence()
            memo = img.lock_free_seq
            how = "scan"
            if seq is not None and memo is not None and not img.locks_dirty:
                touched = snap.cf_touched_seq(CF_LOCK)
                if touched is not None and touched <= min(seq, memo):
                    how = "memo"
            # tagged and counted before the scan, which may raise
            st.tag(how=how)
            REGISTRY.counter(
                "tikv_coprocessor_region_cache_lock_check_total",
                "Warm-hit lock checks, by how they were answered (memo: the "
                "snapshot proved CF_LOCK unchanged since a lock-free scan)",
            ).inc(how=how)
            if how == "scan":
                for start, end in ranges:
                    enc_start = Key.from_raw(start).encoded
                    enc_end = Key.from_raw(end).encoded
                    for k, v in snap.scan_cf(CF_LOCK, enc_start, enc_end):
                        stats.lock.next += 1
                        seen += 1
                        _check_lock(v, Key.from_encoded(k).to_raw(), ts, frozenset())
                if seen == 0 and seq is not None:
                    # the SNAPSHOT's sequence, never a stamp read after it: a
                    # lock written since is not in what was scanned.  A lock
                    # that did not block this ts may block the next: seen > 0
                    # records nothing
                    img.lock_free_seq = seq if memo is None else max(memo, seq)
            st.tag(locks_seen=seen, keys_walked=stats.lock.next - walked0)
        return seen

    def _drop(self, key, reason: str) -> None:
        img = self._images.pop(key, None)
        if img is None:
            return
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
