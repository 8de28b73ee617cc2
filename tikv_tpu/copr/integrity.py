"""Integrity plane for the derived device serving plane (docs/integrity.md).

Everything the warm path serves since PR 1 is *derived* state — decoded
region column images, wt_delta folds, mesh shards — and until this module
nothing ever verified that derived state against ground truth: the raft
mvcc consistency check covers engine CFs only and the native engine's
CRC32c stops at the WAL.  A silent decode bug, a bad delta fold, or
device-side bit corruption would serve wrong bytes to every warm read
forever.  This module closes that loop with three always-on nets:

1. **Image fingerprints** — every :class:`~.region_cache.RegionImage`
   carries an order-independent content hash computed at build time and
   folded incrementally on every delta apply (write-through or scan_delta),
   so a fingerprint is available at any ``(region_id, epoch, apply_index)``
   without re-reading the image, let alone the engine.  The per-row hash is
   ``crc64(compact(key) + compact(value))`` — byte-for-byte the entry of
   ``analyze.checksum_range`` — so the XOR fold doubles as the coprocessor
   Checksum (tp=105) answer for warm ranges.  A second fold mixes each
   row's ``commit_ts`` through splitmix64 so version drift is visible too.

2. **Background scrubber** — :class:`IntegrityScrubber` walks warm images
   on a cadence, recomputes the oracle hash from an engine snapshot at the
   image's apply point, and on mismatch **quarantines** the image
   (invalidate + ledger entry + ``tikv_coprocessor_integrity_mismatch_total``)
   and eagerly rebuilds it from the engine.  ``deep=True`` additionally
   re-decodes the oracle rows and compares the decoded block columns — the
   net that catches post-decode bit flips the raw-chain hash cannot see.
   The scrubber also rides the raft ``schedule_consistency_check`` round
   (:func:`scrub_region_on_consistency_check`), so every replica verifies
   its derived plane at the exact apply index the mvcc hash is taken at,
   and the leader's ``verify_hash`` entry carries its image fingerprints
   for a literal replica cross-check (:func:`cross_check_image_fps`).

3. **Shadow-read sampling** — :class:`ShadowSampler` deterministically
   picks a configurable fraction of warm device serves (default 1/256,
   ``TIKV_TPU_SHADOW_SAMPLE``) for re-execution on the CPU fallback
   executor and byte comparison (``Endpoint.shadow_compare``).  A mismatch
   quarantines the image and the CPU result serves — a sampled request can
   never return wrong bytes.

``TIKV_TPU_INTEGRITY_FATAL=1`` turns any detected mismatch into a raised
:class:`IntegrityMismatch` (tests, canary stores); the default is
quarantine + rebuild + count, because serving correct bytes off a rebuilt
image beats crashing the store.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

import numpy as np

from ..analysis.sanitizer import make_lock
from ..util import codec, trace
from .analyze import _crc64_table
from .byterows import ByteRows

_CRC64_TABLE = np.array(_crc64_table, dtype=np.uint64)
_MASK64 = np.uint64(0xFFFFFFFFFFFFFFFF)
_PHI = np.uint64(0x9E3779B97F4A7C15)

DEFAULT_SHADOW_EVERY = 256


class IntegrityMismatch(Exception):
    """Raised instead of quarantining when TIKV_TPU_INTEGRITY_FATAL=1."""


def integrity_fatal() -> bool:
    return os.environ.get("TIKV_TPU_INTEGRITY_FATAL", "") == "1"


# ---------------------------------------------------------------------------
# row hashing (vectorized crc64-ECMA, identical to analyze.checksum_range)
# ---------------------------------------------------------------------------

# A row longer than this hashes scalar: the batch advances every row one
# byte a step, so one huge blob among small rows would cost a step a byte
# for itself alone.
_JUMBO_ROW = 4096


def _crc64_advance(crc: np.ndarray, rows: ByteRows) -> np.ndarray:
    """Each row's running crc64-ECMA (the register, before the final
    inversion) advanced over that row's bytes.  Vectorized ACROSS rows: the
    carry chain is sequential within a row, so the loop runs over byte
    positions while each step advances every row still that long.  Rows are
    taken longest first, so the rows a step advances are a prefix and no step
    masks; the transient is a few arrays of one element a row."""
    if len(rows) == 0 or int(rows.lens.max()) == 0:
        return crc
    flat = rows.flat
    order = np.argsort(-rows.lens, kind="stable")
    lens = rows.lens[order]
    pos = rows.at[order]
    reg = crc[order]
    steps = int(lens[0])
    # rows longer than j, for every step j: lens is descending
    alive = np.searchsorted(-lens, -np.arange(steps), side="left")
    low = reg.view(np.uint8)[::8]  # each register's low byte (little-endian)
    eight = np.uint64(8)
    for c in alive.tolist():
        r = reg[:c]
        reg[:c] = _CRC64_TABLE[low[:c] ^ flat[pos[:c]]] ^ (r >> eight)
        pos[:c] += 1
    out = np.empty_like(crc)
    out[order] = reg
    return out


def crc64_batch(rows) -> np.ndarray:
    """crc64-ECMA of every byte string (a list of ``bytes`` or a
    ``ByteRows``).  Bit-identical to :func:`..analyze.crc64` per row; jumbo
    rows take that scalar loop."""
    from .analyze import crc64

    rows = ByteRows.of(rows)
    jumbo = rows.lens > _JUMBO_ROW
    small = np.flatnonzero(~jumbo)
    out = np.empty(len(rows), dtype=np.uint64)
    out[small] = _crc64_advance(
        np.full(len(small), _MASK64, dtype=np.uint64), rows[small]) ^ _MASK64
    for i in np.flatnonzero(jumbo).tolist():
        out[i] = crc64(rows[i])
    return out


def row_checksums(raw_keys, values) -> np.ndarray:
    """Per-row ``crc64(compact(key) + compact(value))`` — EXACTLY the entry
    ``analyze.checksum_range`` folds, so ``fold(row_checksums(...))`` equals
    the coprocessor Checksum of the same rows.  Keys and values are lists of
    ``bytes`` or ``ByteRows``, and the chain is never built: each row's
    register runs over the key's length varint, the key, the value's varint
    and the value, each where it lies."""
    from .analyze import crc64

    keys, vals = ByteRows.of(raw_keys), ByteRows.of(values)
    jumbo = (keys.lens > _JUMBO_ROW) | (vals.lens > _JUMBO_ROW)
    small = np.flatnonzero(~jumbo)
    reg = np.full(len(small), _MASK64, dtype=np.uint64)
    for part in (keys[small], vals[small]):
        head, head_lens = codec.encode_var_i64_batch(part.lens)
        reg = _crc64_advance(reg, ByteRows(
            head.tobytes(), np.cumsum(head_lens) - head_lens, head_lens))
        reg = _crc64_advance(reg, part)
    out = np.empty(len(keys), dtype=np.uint64)
    out[small] = reg ^ _MASK64
    ecb = codec.encode_compact_bytes
    for i in np.flatnonzero(jumbo).tolist():
        out[i] = crc64(ecb(keys[i]) + ecb(vals[i]))
    return out


def mix_fp(row_fp: np.ndarray, commit_ts) -> np.ndarray:
    """Mix each row's content hash with its commit_ts (splitmix64): the
    version-aware fingerprint — XOR-foldable like the content hash, but
    sensitive to a corrupted ``row_commit_ts`` too."""
    x = np.asarray(row_fp, dtype=np.uint64) ^ (
        np.asarray(commit_ts).astype(np.uint64) * _PHI
    )
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def fold(fps) -> int:
    """Order-independent combine (XOR): rows are unique by handle, so the
    fold identifies the row SET regardless of block layout or apply order."""
    a = np.asarray(fps, dtype=np.uint64)
    return int(np.bitwise_xor.reduce(a)) if a.size else 0


def image_key_id(key) -> str:
    """Stable, wire-safe identifier of an image key's (ranges, schema) —
    what replicas use to pair up images for the consistency cross-check
    (the raw key contains bytes and nested tuples; a digest travels)."""
    return hashlib.blake2b(repr((key[1], key[2])).encode(), digest_size=8).hexdigest()


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def count_mismatch(stage: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_integrity_mismatch_total",
        "Derived-state integrity mismatches detected, by detection stage",
    ).inc(stage=stage)


def count_quarantine(stage: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_integrity_quarantine_total",
        "Region images quarantined (invalidated + ledgered) after an "
        "integrity mismatch, by detection stage",
    ).inc(stage=stage)


def count_scrub(outcome: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_integrity_scrub_total",
        "Scrubber image verifications, by outcome",
    ).inc(outcome=outcome)


# ---------------------------------------------------------------------------
# shadow-read sampling
# ---------------------------------------------------------------------------

class ShadowSampler:
    """Deterministic 1-in-N pick of warm device serves for CPU shadow
    re-execution.  Counter-based (not hashed off request identity) so a hot
    identical request cannot land on a permanently-sampled bucket and pay
    the CPU re-execution on EVERY serve; the N-th warm serve per path
    samples, making the steady-state overhead exactly cpu_cost/N.

    ``every=0`` disables sampling; ``every=1`` verifies every warm serve
    (the chaos suite's zero-wrong-bytes mode)."""

    def __init__(self, every: int | None = None):
        if every is None:
            env = os.environ.get("TIKV_TPU_SHADOW_SAMPLE", "")
            every = int(env) if env else DEFAULT_SHADOW_EVERY
        self.every = max(int(every), 0)
        self._mu = make_lock("copr.integrity")
        self._n: dict[str, int] = {}
        self.results: dict[tuple, int] = {}

    def pick(self, path: str) -> bool:
        """Count one warm device serve on ``path``; True when it samples."""
        if self.every == 0:
            return False
        with self._mu:
            n = self._n.get(path, 0) + 1
            self._n[path] = n
        return n % self.every == 0

    def note(self, path: str, result: str) -> None:
        from ..util.metrics import REGISTRY

        with self._mu:
            k = (path, result)
            self.results[k] = self.results.get(k, 0) + 1
        REGISTRY.counter(
            "tikv_coprocessor_shadow_read_total",
            "Warm device serves re-executed on the CPU oracle, by serving "
            "path and comparison result",
        ).inc(path=path, result=result)

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "every": self.every,
                "warm_serves": dict(self._n),
                "results": {f"{p}:{r}": n for (p, r), n in self.results.items()},
            }


# ---------------------------------------------------------------------------
# oracle verification
# ---------------------------------------------------------------------------

def verify_image(cache, key, snap, deep: bool = True, stage: str = "scrub") -> dict:
    """Verify ONE resident image against the engine oracle.

    Recomputes the visible row set of ``key``'s ranges at the image's
    snapshot_ts from ``snap`` and compares: the incremental fingerprint
    folds against their own row arrays (fold drift), the row arrays against
    the oracle (content/version corruption), and — with ``deep`` — the
    decoded block columns against a fresh decode of the oracle rows (the
    post-decode plane that actually serves).  On mismatch the image is
    quarantined through the cache's ledger; callers rebuild.

    Validity: the oracle is only meaningful when the image has folded every
    data batch the snapshot contains — enforced via the snapshot's
    apply_index and the cache's write-through watermark; anything else
    returns ``stale`` and the image is retried on a later round."""
    region_id = key[0]
    with cache._mu:
        img = cache._images.get(key)
        if img is None:
            return {"outcome": "missing"}
        if not img.fp_valid:
            return {"outcome": "unverifiable"}
        a_idx = img.apply_index
        ts = img.snapshot_ts
        schema = list(img.schema)
        wt_seen = cache._wt_seen.get(region_id, -1)
    snap_idx = getattr(snap, "apply_index", None)
    if snap_idx is not None and snap_idx < a_idx:
        return {"outcome": "stale"}  # snapshot predates the image
    if snap_idx is not None and snap_idx != a_idx and a_idx < wt_seen:
        # the engine holds data batches the image has not folded yet — the
        # next warm serve folds them; verify then
        return {"outcome": "stale"}
    from .mvcc_batch import MvccBatchScanSource
    from .table import RowBatchDecoder, decode_record_handles

    # the same three steps as a cold build (region_cache.py:_build), under
    # the same stage names: one split serves both
    src = MvccBatchScanSource(snap, ts, list(key[1]), record_versions=True)
    try:
        with trace.stage("fill.resolve"):
            keys_raw, values = src._resolve_all()
    except Exception as exc:  # noqa: BLE001 — locks, faulting engine
        return {"outcome": "error", "error": repr(exc)}
    if not src.versions_exact:
        return {"outcome": "unverifiable"}
    with trace.stage("fill.fingerprint"):
        o_fp = row_checksums(keys_raw, values)
    o_cts = src.row_commit_ts
    # the deep compare's expensive half — handle decode + a full row decode
    # of the oracle values — runs OUTSIDE the manager lock (it touches only
    # oracle-side locals); under the lock only vectorized compares remain,
    # so concurrent warm serves and the raft apply loop never stall on a
    # scrub's decode
    o_handles = o_cols = None
    if deep:
        try:
            o_handles = decode_record_handles(keys_raw)
            if len(o_handles):
                decoder = RowBatchDecoder(schema)
                with trace.stage("fill.decode") as st:
                    o_cols = decoder.decode(o_handles, values)
                    st.tag(rows=len(values), path=decoder.path)
        except Exception as exc:  # noqa: BLE001 — exotic rows: cannot judge
            return {"outcome": "error", "error": repr(exc)}
    with cache._mu:
        if cache._images.get(key) is not img or img.apply_index != a_idx:
            return {"outcome": "raced"}
        failed: list[str] = []
        if img.fp_value != fold(img.row_fp) or img.fp_integrity != fold(
            mix_fp(img.row_fp, img.row_commit_ts)
        ):
            # the incremental fold diverged from its own arrays: a fold bug
            # or bookkeeping corruption — as quarantine-worthy as content
            failed.append("fold_drift")
        if img.fp_value != fold(o_fp):
            failed.append("content")
        if img.fp_integrity != fold(mix_fp(o_fp, o_cts)):
            failed.append("versions")
        if deep and not failed:
            failed.extend(_deep_compare(img, o_handles, o_cols, o_cts))
        info = {
            "region_id": region_id,
            "key_id": image_key_id(key),
            "epoch": img.epoch,
            "apply_index": a_idx,
            "snapshot_ts": ts,
            "rows": img.n_rows,
            "fingerprint": img.fp_integrity,
        }
        if not failed:
            return {"outcome": "ok", **info}
        schema = list(img.schema)
        cache.quarantine_image(
            key, stage=stage,
            detail={"failed": failed, "oracle_fingerprint": fold(mix_fp(o_fp, o_cts)),
                    "oracle_rows": len(keys_raw)},
        )
    count_mismatch(stage)
    if integrity_fatal():
        raise IntegrityMismatch(
            f"integrity mismatch ({stage}) on region {region_id} "
            f"apply_index {a_idx}: {failed}"
        )
    return {"outcome": "mismatch", "failed": failed, "schema": schema, **info}


def _deep_compare(img, o_handles, o_cols, o_cts) -> list[str]:
    """Compare the image's DECODED plane (what serves) against the
    pre-decoded oracle rows.  Caller holds the cache lock; the oracle-side
    decode already happened outside it — only vectorized compares (plus,
    for compressed-resident columns, a fresh vectorized decode of the
    ENCODED payload: materialized decode caches are purged first, so a
    bit flip in the encoded bytes — the form the device actually serves —
    can never hide behind a stale host decode;
    docs/compressed_columns.md)."""
    from .encoding import EncodedColumn

    def _purge():
        for b in img.block_cache.blocks:
            for c in b.cols:
                if isinstance(c, EncodedColumn):
                    c.purge_decoded()

    _purge()
    try:
        return _deep_compare_inner(img, o_handles, o_cols, o_cts)
    finally:
        # the compare itself re-materialized the caches: drop them again so
        # a scrubbed image resumes costing its ENCODED bytes
        _purge()


def _deep_compare_inner(img, o_handles, o_cols, o_cts) -> list[str]:
    if not np.array_equal(o_handles, img.handles):
        return ["handles"]
    if o_cts is not None and not np.array_equal(
        np.asarray(o_cts, dtype=np.int64), img.row_commit_ts
    ):
        return ["commit_ts"]
    blocks = img.block_cache.blocks
    if sum(b.n_valid for b in blocks) != img.n_rows:
        return ["blocks"]
    if img.n_rows == 0 or o_cols is None:
        return []
    cols = o_cols
    for ci in range(len(img.schema)):
        parts_d, parts_n = [], []
        for b in blocks:
            c = b.cols[ci].decoded()
            parts_d.append(np.asarray(c.data)[: b.n_valid])
            parts_n.append(np.asarray(c.nulls)[: b.n_valid])
        idata = np.concatenate(parts_d)
        inulls = np.concatenate(parts_n)
        oc = cols[ci].decoded()
        odata = np.asarray(oc.data)
        onulls = np.asarray(oc.nulls)
        if not np.array_equal(inulls, onulls):
            return [f"nulls:{ci}"]
        live = ~inulls
        a, b_ = idata[live], odata[live]
        if a.dtype.kind == "f" or b_.dtype.kind == "f":
            same = np.array_equal(a.astype(np.float64), b_.astype(np.float64),
                                  equal_nan=True)
        else:
            same = bool(np.asarray(a == b_).all()) if len(a) else True
        if not same:
            return [f"column:{ci}"]
    return []


# ---------------------------------------------------------------------------
# background scrubber
# ---------------------------------------------------------------------------

class IntegrityScrubber:
    """Cadenced oracle verification of warm images (SDC scrubber).

    ``scrub_once()`` is the synchronous core — a round-robin cursor over
    the cache's resident images verifies up to ``per_round`` of them
    against engine snapshots; mismatches quarantine AND eagerly rebuild
    (the repaired image serves the next warm read with zero cold cost).
    ``start(interval_s)`` runs rounds on a ``util.worker.Worker`` timer —
    the standalone server's always-on mode."""

    def __init__(self, cache, engine, per_round: int = 8, deep: bool = True):
        self.cache = cache
        self.engine = engine
        self.per_round = per_round
        self.deep = deep
        self.interval_s: float | None = None
        self._mu = make_lock("copr.integrity.scrub")
        self._worker = None
        # set by stop(): a cadenced round in flight ends at its next image
        # instead of reading a stopping store's engine to the round's end
        self._halt = threading.Event()
        self._cursor = 0
        # TIKV_TPU_INTEGRITY_FATAL on the cadenced path: the Worker timer
        # swallows exceptions, so the fatal raise is recorded here instead
        # (and further rounds stop) — surfaced via snapshot()/debug RPC
        self.fatal_error: str | None = None
        self.stats = {
            "rounds": 0, "checked": 0, "ok": 0, "mismatch": 0,
            "skipped": 0, "errors": 0, "last_round_unix": 0.0,
        }

    # -- snapshots -----------------------------------------------------------

    def _snapshot_for(self, key):
        """An engine snapshot to verify ``key`` against.  RaftKv exposes a
        protocol-free local snapshot (scrubbing needs a pinned LOCAL apply
        point, not linearizability); plain engines snapshot directly."""
        local = getattr(self.engine, "local_snapshot", None)
        if local is not None:
            return local(key[0])
        return self.engine.snapshot({"region_id": key[0]})

    # -- the scrub core ------------------------------------------------------

    def scrub_once(self, limit: int | None = None, halt=None) -> list[dict]:
        """One round.  ``halt`` (an Event; the cadenced rounds pass theirs)
        ends it at the next image once set."""
        cache = self.cache
        if cache is None:
            return []
        with cache._mu:
            all_keys = list(cache._images.keys())
        if not all_keys:
            return []
        k = min(limit or self.per_round, len(all_keys))
        with self._mu:
            start = self._cursor % len(all_keys)
            self._cursor = start + k
        picked = [all_keys[(start + i) % len(all_keys)] for i in range(k)]
        out = []
        fatal: IntegrityMismatch | None = None
        for key in picked:
            if halt is not None and halt.is_set():
                break
            try:
                snap = self._snapshot_for(key)
            except Exception as exc:  # noqa: BLE001 — peer gone, engine closed
                res = {"outcome": "error", "error": repr(exc)}
            else:
                try:
                    res = verify_image(cache, key, snap, deep=self.deep,
                                       stage="scrub")
                except IntegrityMismatch as exc:
                    # fatal mode: the quarantine + mismatch counts already
                    # happened inside verify_image — finish this round's
                    # bookkeeping (metrics, stats, remaining images) and
                    # re-raise at the end, so fatal never UNDER-reports
                    res = {"outcome": "mismatch", "fatal": True}
                    fatal = fatal or exc
                if res["outcome"] == "mismatch" and "schema" in res:
                    self._rebuild(key, snap, res)
            count_scrub(res["outcome"])
            with self._mu:
                self.stats["checked"] += 1
                if res["outcome"] == "ok":
                    self.stats["ok"] += 1
                elif res["outcome"] == "mismatch":
                    self.stats["mismatch"] += 1
                elif res["outcome"] == "error":
                    self.stats["errors"] += 1
                else:
                    self.stats["skipped"] += 1
            out.append({"region_id": key[0], **res})
        with self._mu:
            self.stats["rounds"] += 1
            self.stats["last_round_unix"] = time.time()
        if fatal is not None:
            raise fatal
        return out

    def _rebuild(self, key, snap, res: dict) -> None:
        """Eager repair: rebuild the quarantined image from the engine so
        the next warm read serves a verified image, not a cold miss."""
        schema = res.get("schema")
        if schema is None:
            return
        ctx = {
            "region_id": key[0],
            "region_epoch": res["epoch"],
            "apply_index": getattr(snap, "apply_index", None) or res["apply_index"],
        }
        try:
            self.cache.serve(snap, ctx, schema, list(key[1]), res["snapshot_ts"])
        except Exception:  # noqa: BLE001 — locks etc: the next read rebuilds
            pass

    # -- cadence -------------------------------------------------------------

    def start(self, interval_s: float = 10.0) -> None:
        if self._worker is not None:
            return
        from ..util.worker import Runnable, Worker

        self.interval_s = interval_s
        self._halt.clear()
        scrubber = self

        class _ScrubRunnable(Runnable):
            def _round(self) -> None:
                if scrubber.fatal_error is not None:
                    return  # fatal mode already fired: no further rounds
                try:
                    scrubber.scrub_once(halt=scrubber._halt)
                except IntegrityMismatch as exc:
                    # the Worker swallows exceptions, so the fatal raise
                    # would otherwise vanish: record + log it loudly and
                    # stop scrubbing (snapshot()/debug_integrity surface it)
                    scrubber.fatal_error = repr(exc)
                    from ..util import logger as _slog

                    _slog.get_logger("integrity").error(
                        "fatal integrity mismatch (scrubber halted)",
                        error=repr(exc),
                    )

            def run(self, task) -> None:
                self._round()

            def on_timeout(self) -> None:
                self._round()

        w = Worker("integrity-scrub", timer_interval=interval_s)
        w.start(_ScrubRunnable())
        self._worker = w

    def stop(self) -> bool:
        """False where a round in flight outlived the worker's join: it goes
        on reading the engine until it ends or the engine is closed."""
        w, self._worker = self._worker, None
        self._halt.set()
        return w.stop() if w is not None else True

    @property
    def running(self) -> bool:
        return self._worker is not None

    def snapshot(self) -> dict:
        with self._mu:
            st = dict(self.stats)
        return {
            "running": self.running,
            "interval_s": self.interval_s,
            "per_round": self.per_round,
            "deep": self.deep,
            "fatal_error": self.fatal_error,
            **st,
        }


# ---------------------------------------------------------------------------
# raft consistency-check ride-along
# ---------------------------------------------------------------------------

def _caches_for(token):
    from .region_cache import _CACHES, _TOKEN_UNSET

    out = []
    for c in list(_CACHES):
        t = c._wt_token
        if t is not _TOKEN_UNSET and t == token:
            out.append(c)
    return out


def scrub_region_on_consistency_check(region_id: int, token, snap,
                                      limit: int = 4) -> list[dict]:
    """Every replica applying a compute_hash entry verifies its OWN derived
    images of the region against its OWN engine at that exact apply point —
    the mvcc hash then cross-checks the engines replica-to-replica, so the
    derived planes are transitively cross-checked too.

    This runs INLINE on the raft apply thread, so the work is bounded:
    hash-level only (``deep=False`` — no full row decode; the decoded
    plane is the budgeted background scrubber's and the shadow reads' job)
    and at most ``limit`` images per apply — comparable to the
    ``_region_hash`` scan the round already pays, never a multiple of it."""
    results = []
    checked = 0
    for cache in _caches_for(token):
        with cache._mu:
            keys = [k for k in cache._images if k[0] == region_id]
        for key in keys:
            if checked >= limit:
                return results
            res = verify_image(cache, key, snap, deep=False,
                               stage="consistency_check")
            results.append(res)
            checked += 1
    return results


def region_image_fingerprints(region_id: int, token) -> dict:
    """{key_id: {"apply_index", "snapshot_ts", "max_commit_ts",
    "fingerprint"}} of this store's verified images of the region — the
    payload the leader attaches to verify_hash so replicas can literally
    compare device-image hashes.  snapshot_ts/max_commit_ts travel so the
    receiver can prove the row sets identical before comparing (see
    :func:`cross_check_image_fps`)."""
    out: dict = {}
    for cache in _caches_for(token):
        with cache._mu:
            for key, img in cache._images.items():
                if key[0] != region_id or not img.fp_valid:
                    continue
                out[image_key_id(key)] = {
                    "apply_index": img.apply_index,
                    "snapshot_ts": img.snapshot_ts,
                    "max_commit_ts": img.max_commit_ts,
                    "fingerprint": img.fp_integrity,
                }
    return out


def cross_check_image_fps(region_id: int, token, leader_fps: dict) -> list[dict]:
    """verify_hash-side replica cross-check: compare local image
    fingerprints against the leader's — but ONLY when the two images
    provably hold the same row set.  Equal apply_index alone is not enough:
    two healthy replicas may have built the same (ranges, schema) image at
    different read timestamps (PR-7 stale reads), seeing different MVCC
    versions.  The row sets are identical iff the apply state is pinned
    equal AND neither image contains a version the other's read point
    missed: ``leader.max_commit_ts <= local.snapshot_ts`` and
    ``local.max_commit_ts <= leader.snapshot_ts`` (a separating version
    with cts between the two read points would raise the later image's
    max_commit_ts above the earlier one's snapshot).  Anything else is
    incomparable and skipped — the local-engine scrub at the compute point
    already covered those images.  Divergence quarantines the LOCAL image:
    the engine mvcc hash decides who is wrong at the region level; the
    derived plane simply rebuilds."""
    quarantined = []
    for cache in _caches_for(token):
        with cache._mu:
            keys = [k for k in cache._images if k[0] == region_id]
            for key in keys:
                img = cache._images.get(key)
                if img is None or not img.fp_valid:
                    continue
                rec = leader_fps.get(image_key_id(key))
                if rec is None or int(rec["apply_index"]) != img.apply_index:
                    continue
                if not (int(rec["max_commit_ts"]) <= img.snapshot_ts
                        and img.max_commit_ts <= int(rec["snapshot_ts"])):
                    continue  # read points may see different version sets
                if int(rec["fingerprint"]) != img.fp_integrity:
                    entry = cache.quarantine_image(
                        key, stage="replica_divergence",
                        detail={"leader_fingerprint": int(rec["fingerprint"])},
                    )
                    quarantined.append(entry)
    for _ in quarantined:
        count_mismatch("replica_divergence")
    return quarantined
