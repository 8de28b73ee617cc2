"""Compressed device-resident columns — encodings, eligibility, rewrites.

HBM capacity is the ceiling on warm serving: the region column cache keeps
ONE decoded image per region under a per-device byte budget, so the number
of regions that stay warm — and therefore hit the vectorized wire path — is
bounded by DECODED size.  Following "GPU Acceleration of SQL Analytics on
Compressed Data" (PAPERS.md), this module makes ENCODED blocks the resident
form and pushes evaluation through the encodings, so the budget buys 3-5×
more warm regions for the same bytes:

* **bitpack** — int-family columns whose value range fits narrow signed
  lanes store ``value - ref`` in int8/int16/int32 (frame-of-reference +
  power-of-two lane widths; numpy has no sub-byte arrays, so 8 bits is the
  floor).  The device program widens in-register (``x.astype(i64) + ref``)
  — HBM holds the narrow lanes, compute sees exact int64.
* **rle** — columns dominated by runs store (run_values, run_ends,
  run_nulls); the device expands rows in-kernel with one ``searchsorted``
  gather per column, so HBM holds runs while predicates/aggregates see the
  logical rows.
* **dict** — BYTES columns already arrive dictionary-coded from the row
  decoders; the codes are additionally NARROWED to the smallest lane that
  holds the dictionary, and equality/IN/range predicates over such columns
  are REWRITTEN into the code space (:func:`rewrite_dag_for_dict`) so
  warm bytes-predicate DAGs run on the device without materializing a
  single string.

Eligibility is centralized HERE (plan-sig × encoding → path decision) so
the serving paths can never disagree about what ships encoded, and every
decline is counted per-cause — never silent:

======== ============ ========== ====== ====== ========= ==========
encoding unary-stacked per-block  zone   fused  xregion   mesh-shard
======== ============ ========== ====== ====== ========= ==========
plain     ✓            ✓          ✓      ✓      ✓         ✓
dict/code ✓ (narrow)   ✓          ✓      ✓      ✓ sig=    ✓ sig=
bitpack   ✓            ✓          (own)  ✓      ✓ sig=    ✓ sig=
rle       ✓            ✓          (own)  ✓      ✓ sig=    decode-ship
======== ============ ========== ====== ====== ========= ==========

"sig=": cross-region programs (vmapped / shard_map) stack per-region pinned
arrays, so every region in the batch must carry the SAME encoding signature
(lane widths, run capacities); a mismatch decode-ships the batch (cause
``enc_mismatch``).  The mesh launcher additionally declines RLE (slab
stacks mix blocks of several regions on one device; run capacities would
have to unify across the whole batch — cause ``rle_sharded``).  "(own)":
the zone-tiled layout re-clusters and re-narrows from the logical rows —
it is its own compressed resident form, not a decline.

Delta semantics (docs/compressed_columns.md): in-place write-through folds
PATCH bitpacked lanes (and dict codes) when the new value still fits;
anything that breaks an encoding — an out-of-range value, any in-place
update to an RLE column — DEMOTES that column image-wide to plain decoded
(counted ``tikv_coprocessor_encoding_demote_total{kind,cause}``), dropping
device pins so the next serve re-pins the decoded form; structural repacks
re-encode from fresh stats.  Byte-identity is non-negotiable: decode() is
exact, null slots normalize to the canonical 0 filler, and the integrity
plane (fingerprints over the LOGICAL rows, deep scrub, shadow reads)
cross-checks encoded and decoded images of the same data.
"""

from __future__ import annotations

import numpy as np

from .datatypes import Column, EvalType

# minimum win before a column trades decode work for bytes: bitpack must
# shed at least half the lanes, RLE must shed at least 3/4 of the slots
_RLE_MAX_RUN_FRACTION = 0.25
_NARROW_DTYPES = (np.int8, np.int16, np.int32)

# device-plan memo: (id(cache), enc_version, ship, nullable) → plan
_PLAN_MEMO: dict = {}
_PLAN_MEMO_MAX = 256

# dictionary → code-map memo for predicate rewrites (id-keyed, bounded; the
# dictionary object is held so the id cannot be recycled under the entry)
_DICT_MAPS: dict = {}
_DICT_MAPS_MAX = 64


# ---------------------------------------------------------------------------
# metrics (every decision observable; declines NEVER silent)
# ---------------------------------------------------------------------------

def count_encoded(kind: str, n: int = 1) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_encoding_total",
        "Columns made device-resident in encoded form at fill, by kind",
    ).inc(n, kind=kind)


def count_demote(kind: str, cause: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_encoding_demote_total",
        "Encoded columns demoted to plain decoded (encoding broken), by cause",
    ).inc(kind=kind, cause=cause)


def count_path(path: str, decision: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_encoded_path_total",
        "Device-path consumption decisions for encoded-resident images",
    ).inc(path=path, decision=decision)


def count_decline(path: str, cause: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_encoded_decline_total",
        "Encoded-path declines (decode-ship / CPU), by path and cause",
    ).inc(path=path, cause=cause)


def count_rewrite(outcome: str) -> None:
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_encoded_rewrite_total",
        "Dict-code-space predicate rewrites of bytes-predicate DAGs",
    ).inc(outcome=outcome)


def count_join(path: str, outcome: str) -> None:
    """Join serving outcomes by path (rank / hash / cpu): served, declined,
    error — the device-join twin of count_rewrite (docs/device_join.md);
    per-cause decline detail rides count_decline(path="join", cause)."""
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_coprocessor_join_total",
        "Coprocessor join serves by path and outcome",
    ).inc(path=path, outcome=outcome)


# ---------------------------------------------------------------------------
# eligibility → candidate set (the cost router's input)
# ---------------------------------------------------------------------------

def candidate_paths(dag, *, device_ok: bool, mesh_ok: bool) -> list[str]:
    """The eligible execution paths for ``dag``, in STATIC-LADDER order —
    head = what today's rules pick, so a cold/killed cost router choosing
    ``candidates[0]`` IS the pre-router behavior (docs/cost_router.md).

    ``device_ok`` is the admission verdict (plan eligibility AND overload
    AND breaker — the endpoint computes it before routing); ``mesh_ok`` is
    whether the sharded mesh launcher would serve this request.  Zone
    stays a *candidate* for any aggregation plan: its evaluator still
    probes data-shape eligibility at run time and falls through to unary,
    so routing to "zone" means "try the zone rung", exactly like the
    static ladder does."""
    if not device_ok:
        return ["cpu"]
    from .dag import Aggregation, Join

    if any(isinstance(e, Join) for e in dag.executors):
        # join plans route among the device-join rung's two kernels and the
        # CPU oracle (docs/device_join.md): rank (sorted-dict code space)
        # leads the static ladder, hash (open-addressing over int lanes)
        # second — each is "try the rung", with per-cause counted declines
        # falling through to the next, exactly like zone/unary
        return ["rank", "hash", "cpu"]
    paths: list[str] = []
    if mesh_ok:
        paths.append("mesh")
    if any(isinstance(e, Aggregation) for e in dag.executors):
        paths.append("zone")
    paths.extend(("unary", "cpu"))
    return paths


# ---------------------------------------------------------------------------
# EncodedColumn — a lazy-decoding Column variant
# ---------------------------------------------------------------------------

class EncodedColumn(Column):
    """A :class:`Column` whose resident payload is encoded.

    ``data``/``nulls`` are PROPERTIES that materialize (and cache) the
    decoded arrays on first touch, so every generic consumer — the CPU
    executors, the response encoder, the deep scrub, the zone layout —
    stays correct without knowing about encodings; the device paths read
    the payload directly and decode in-kernel.  ``take`` is the
    late-materialize gather: only the selected rows decompress."""

    __slots__ = ("kind", "packed", "ref", "run_values", "run_ends",
                 "run_nulls", "k_cap", "n", "_data", "_nulls")

    def __init__(self, eval_type, frac, kind, n, *, packed=None, ref=0,
                 run_values=None, run_ends=None, run_nulls=None, k_cap=0,
                 nulls=None):
        # NOTE: deliberately no super().__init__ — the base slots `data` /
        # `nulls` are shadowed by the properties below
        self.eval_type = eval_type
        self.frac = frac
        self.dictionary = None
        self.kind = kind  # "bp" | "rle"
        self.n = n
        self.packed = packed
        self.ref = int(ref)
        self.run_values = run_values
        self.run_ends = run_ends
        self.run_nulls = run_nulls
        self.k_cap = int(k_cap)
        self._data = None
        self._nulls = nulls  # bp keeps plain bool nulls; rle expands lazily

    # -- logical view -------------------------------------------------------

    def __len__(self) -> int:
        return self.n

    @property
    def data(self):
        if self._data is None:
            self._data = self._decode_rows(None)
        return self._data

    @property
    def nulls(self):
        if self._nulls is None:  # rle only
            idx = self._run_index(np.arange(self.n))
            self._nulls = self.run_nulls[idx]
        return self._nulls

    def purge_decoded(self) -> None:
        """Drop materialized caches so the next touch decodes from the
        payload — the scrub path uses this to verify the ENCODED bytes, not
        a stale decode."""
        self._data = None
        if self.kind == "rle":
            self._nulls = None

    def _run_index(self, rows: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.run_ends, rows, side="right")

    def _decode_rows(self, rows):
        """Decode all rows (rows=None) or just the selected ones.  Null
        slots normalize to the canonical 0 filler (what the row decoders
        and delta cells write), so decode is byte-stable."""
        if self.kind == "bp":
            if rows is None:
                out = self.packed.astype(np.int64)
                out += self.ref
                out[self._nulls] = 0
            else:
                out = self.packed[rows].astype(np.int64)
                out += self.ref
                out[self._nulls[rows]] = 0
            return out
        idx = self._run_index(np.arange(self.n) if rows is None else rows)
        out = self.run_values[idx].astype(np.int64, copy=True)
        out[self.run_nulls[idx]] = 0
        return out

    def take(self, indices: np.ndarray) -> Column:
        """Late materialization: decompress ONLY the surviving rows."""
        indices = np.asarray(indices)
        data = self._decode_rows(indices)
        if self.kind == "bp":
            nulls = self._nulls[indices]
        else:
            nulls = self.run_nulls[self._run_index(indices)]
        return Column(self.eval_type, data, nulls.copy(), self.frac)

    def slice(self, start: int, stop: int) -> Column:
        return self.take(np.arange(start, stop))

    # -- payload accounting / mutation ---------------------------------------

    def encoded_nbytes(self) -> int:
        if self.kind == "bp":
            return self.packed.nbytes + self._nulls.nbytes
        return (self.run_values.nbytes + self.run_ends.nbytes
                + self.run_nulls.nbytes)


def decoded_data(col: Column):
    """The decoded data array WITHOUT populating the column's decode cache
    — decode-ship pin builds (and the zone layout) must not leave a
    permanent host copy the encoded byte budget never accounted for."""
    if isinstance(col, EncodedColumn):
        return col._data if col._data is not None else col._decode_rows(None)
    return col.data


def decoded_nulls(col: Column):
    """Expanded null mask without populating the RLE null cache."""
    if (isinstance(col, EncodedColumn) and col.kind == "rle"
            and col._nulls is None):
        return col.run_nulls[col._run_index(np.arange(col.n))]
    return col.nulls


def decode_column(col: Column) -> Column:
    """A plain decoded Column for ``col`` (identity for unencoded ones)."""
    if isinstance(col, EncodedColumn):
        return Column(col.eval_type, np.asarray(col.data),
                      np.asarray(col.nulls).copy(), col.frac)
    return col


def host_dtype(col: Column):
    """The DECODED host dtype of a column (what delta cells compute in)."""
    if isinstance(col, EncodedColumn):
        return np.dtype(np.int64)
    d = np.asarray(col.data)
    if col.is_dict_encoded and d.dtype != object:
        return np.dtype(np.int64)  # codes widen before delta math
    return d.dtype


# ---------------------------------------------------------------------------
# stats pass + encode / demote / re-encode
# ---------------------------------------------------------------------------

def _narrow_lane(lo: int, hi: int, ref: int):
    for dt in _NARROW_DTYPES:
        info = np.iinfo(dt)
        if info.min <= lo - ref and hi - ref <= info.max:
            return dt
    return None


def _encode_one(col: Column, n_valid: int):
    """Choose and build the encoded form of ONE block column, or None to
    keep it as-is.  Int-family lanes only; REAL/object columns stay plain
    (float ranges don't narrow exactly; BYTES rides the dict path)."""
    data = col.data if not isinstance(col, EncodedColumn) else None
    if data is None or not isinstance(data, np.ndarray) or data.dtype == object:
        return None
    if col.eval_type == EvalType.REAL or data.dtype.kind not in "iu":
        return None
    if col.is_dict_encoded:
        return None  # dict codes narrow through narrow_dict_codes instead
    n = len(data)
    if n == 0:
        return None
    nulls = np.asarray(col.nulls, dtype=bool)
    a = data.astype(np.int64, copy=False)
    # RLE probe: runs over (value, null) pairs
    change = np.empty(n, dtype=bool)
    change[0] = True
    np.not_equal(a[1:], a[:-1], out=change[1:])
    change[1:] |= nulls[1:] != nulls[:-1]
    run_starts = np.flatnonzero(change)
    r = len(run_starts)
    if r <= max(1, int(n * _RLE_MAX_RUN_FRACTION)):
        run_ends = np.empty(r, dtype=np.int64)
        run_ends[:-1] = run_starts[1:]
        run_ends[-1] = n
        return EncodedColumn(
            col.eval_type, col.frac, "rle", n,
            run_values=a[run_starts].copy(), run_ends=run_ends,
            run_nulls=nulls[run_starts].copy(),
        )
    live = ~nulls
    if not live.any():
        lo = hi = 0
    else:
        lo, hi = int(a[live].min()), int(a[live].max())
    ref = lo
    dt = _narrow_lane(lo, hi, ref)
    if dt is None or np.dtype(dt).itemsize * 2 > a.dtype.itemsize:
        return None  # no lane at least halves the bytes
    packed = np.where(live, a - ref, 0).astype(dt)
    return EncodedColumn(col.eval_type, col.frac, "bp", n, packed=packed,
                         ref=ref, nulls=nulls.copy())


def narrow_dict_codes(col: Column) -> Column:
    """Narrow a dictionary-coded column's code lanes in place (int64 codes
    → the smallest lane holding the dictionary, with growth headroom)."""
    d = np.asarray(col.data)
    if (col.dictionary is None or d.dtype == object
            or col.eval_type in (EvalType.ENUM, EvalType.SET)):
        return col
    hi = max(len(col.dictionary), 1)
    dt = _narrow_lane(0, 2 * hi, 0)
    if dt is None or np.dtype(dt).itemsize >= d.dtype.itemsize:
        return col
    col.data = d.astype(dt)
    return col


def encode_blocks(cache, schema) -> dict:
    """The fill-time stats pass: choose ONE encoding per column for the
    whole image (uniform across blocks — cross-block device stacking
    requires one signature) and swap the block columns for their encoded
    forms.  Returns {col_idx: kind} for the columns that changed."""
    blocks = cache.blocks
    if not blocks:
        return {}
    n_cols = len(blocks[0].cols)
    changed: dict[int, str] = {}
    for ci in range(n_cols):
        cols = [b.cols[ci] for b in blocks]
        if any(isinstance(c, EncodedColumn) for c in cols):
            continue
        if cols[0].is_dict_encoded:
            for b in blocks:
                narrow_dict_codes(b.cols[ci])
            d = np.asarray(blocks[0].cols[ci].data)
            if d.dtype != object and d.dtype.itemsize < 8:
                changed[ci] = "code"
                count_encoded("code")
            continue
        d0 = np.asarray(cols[0].data)
        if d0.dtype == object and cols[0].eval_type == EvalType.BYTES:
            # low-cardinality strings become dictionary residents with a
            # SORTED dictionary (order-preserving codes — what lets range
            # predicates rewrite into the code space) shared across blocks
            if _dict_encode_blocks(blocks, ci):
                changed[ci] = "dict"
                count_encoded("dict")
            continue
        encoded = [_encode_one(c, b.n_valid) for c, b in zip(cols, blocks)]
        if any(e is None for e in encoded):
            continue
        kinds = {e.kind for e in encoded}
        kind = kinds.pop() if len(kinds) == 1 else "bp"
        if kind == "bp":
            # bitpack everywhere (also the tie-break for mixed per-block
            # choices) under ONE shared frame of reference — cross-block
            # device stacks ship one dynamic ref per column
            encoded = _unify_bitpack(cols)
            if encoded is None:
                continue
        else:
            k_cap = 1
            while k_cap < max(len(e.run_values) for e in encoded):
                k_cap *= 2
            for e in encoded:
                e.k_cap = k_cap
        for b, e in zip(blocks, encoded):
            b.cols[ci] = e
        changed[ci] = kind
        count_encoded(kind)
    if changed:
        cache.enc_version = getattr(cache, "enc_version", 0) + 1
    # fill/repack-time zone maps (docs/zone_maps.md): the stats pass above
    # already bounded every encoded column, so attaching the prunable
    # per-block zones here is nearly free — and fresh (non-stale) by
    # construction.  Plain images build theirs lazily on first prune.
    from . import zone_maps as _zm

    for b in blocks:
        b.zones = _zm.build_block_zones(b.cols, b.n_valid)
    return changed


_DICT_MAX_CARDINALITY = 65536


def _dict_encode_blocks(blocks, ci: int) -> bool:
    """Dictionary-encode an object BYTES column image-wide: one SORTED
    dictionary object shared by every block (identity-shared — the stable-
    dictionary group paths and the predicate rewrite both key on it),
    narrow code lanes, null slots coded 0 (consumers mask)."""
    parts = [np.asarray(b.cols[ci].data) for b in blocks]
    nullp = [np.asarray(b.cols[ci].nulls) for b in blocks]
    n = sum(len(p) for p in parts)
    if n == 0:
        return False
    cap = min(max(n // 4, 1), _DICT_MAX_CARDINALITY)
    values = set()
    try:
        for p, nl in zip(parts, nullp):
            for v, isnull in zip(p, nl):
                if not isnull:
                    values.add(bytes(v))
            if len(values) > cap:
                # high-cardinality column: stop scanning the moment the cap
                # is exceeded — this runs on the fill/repack path
                return False
    except TypeError:
        return False  # non-bytes payloads: not dictionary material
    if not values or len(values) > cap:
        return False
    uniq = sorted(values)
    dictionary = np.empty(len(uniq), dtype=object)
    for j, v in enumerate(uniq):
        dictionary[j] = v
    dt = _narrow_lane(0, 2 * len(uniq), 0) or np.int64
    for b, p, nl in zip(blocks, parts, nullp):
        codes = np.searchsorted(dictionary, p).astype(dt)
        codes[nl] = 0
        c = b.cols[ci]
        b.cols[ci] = Column(c.eval_type, codes, np.asarray(c.nulls),
                            c.frac, dictionary)
    return True


def _unify_bitpack(cols):
    """Bitpack every block of a column under ONE shared (ref, lane)."""
    lo = hi = None
    for c in cols:
        a = np.asarray(c.data).astype(np.int64, copy=False)
        live = ~np.asarray(c.nulls, dtype=bool)
        if not live.any():
            continue
        clo, chi = int(a[live].min()), int(a[live].max())
        lo = clo if lo is None else min(lo, clo)
        hi = chi if hi is None else max(hi, chi)
    if lo is None:
        lo = hi = 0
    ref = lo
    dt = _narrow_lane(lo, hi, ref)
    if dt is None or np.dtype(dt).itemsize * 2 > 8:
        return None
    out = []
    for c in cols:
        a = np.asarray(c.data).astype(np.int64, copy=False)
        nulls = np.asarray(c.nulls, dtype=bool)
        packed = np.where(~nulls, a - ref, 0).astype(dt)
        out.append(EncodedColumn(c.eval_type, c.frac, "bp", len(a),
                                 packed=packed, ref=ref, nulls=nulls.copy()))
    return out


def demote_column(cache, ci: int, cause: str) -> None:
    """Replace an encoded column with its plain decoded form IMAGE-WIDE
    (every block — cross-block signatures must stay uniform) and drop
    device pins; the next serve re-pins decoded.  This is the
    "decode-on-next-serve" rung for updates that break an encoding."""
    kind = None
    for b in cache.blocks:
        c = b.cols[ci]
        if isinstance(c, EncodedColumn):
            kind = c.kind
            b.cols[ci] = decode_column(c)
    if kind is not None:
        count_demote(kind, cause)
        cache.enc_version = getattr(cache, "enc_version", 0) + 1
        cache.drop_device()


# ---------------------------------------------------------------------------
# byte accounting
# ---------------------------------------------------------------------------

def column_nbytes(col: Column) -> int:
    """Resident (encoded) bytes of one block column — THE figure budgets
    and gauges use.  Matches the historical formula exactly for plain
    columns so unencoded caches account identically to before."""
    if isinstance(col, EncodedColumn):
        return col.encoded_nbytes()
    data = np.asarray(col.data)
    total = data.nbytes if data.dtype != object else 32 * len(data)
    total += np.asarray(col.nulls).nbytes
    if col.dictionary is not None:
        total += 64 * len(col.dictionary)
    return total


def column_decoded_nbytes(col: Column) -> int:
    """What the column WOULD cost decoded (int64 lanes + bool nulls) — the
    numerator of the compression-ratio gauge."""
    if isinstance(col, EncodedColumn):
        return col.n * 8 + col.n * 1
    data = np.asarray(col.data)
    if col.dictionary is not None and data.dtype != object and data.dtype.kind in "iu":
        return len(data) * 8 + np.asarray(col.nulls).nbytes + 64 * len(col.dictionary)
    return column_nbytes(col)


# ---------------------------------------------------------------------------
# device consumption plans (per path, per-cause declines)
# ---------------------------------------------------------------------------

class DevicePlan:
    """How one image's columns ship to the device for a (ship, nullable)
    set: per-slot static descriptors (the jit/pin cache key), the dynamic
    frame-of-reference vector, and payload builders."""

    __slots__ = ("sig", "null_sig", "refs")

    def __init__(self, sig, null_sig, refs):
        self.sig = sig            # tuple per ship col (static, hashable)
        self.null_sig = null_sig  # tuple per nullable col
        self.refs = refs          # np.ndarray (n_ship,) int64

    @property
    def encoded(self) -> bool:
        return any(d[0] != "plain" for d in self.sig)


def _col_desc(col: Column):
    if isinstance(col, EncodedColumn):
        if col.kind == "bp":
            return ("bp", col.packed.dtype.str), col.ref
        return ("rle", col.k_cap, col.run_values.dtype.str), 0
    d = np.asarray(col.data)
    if (col.dictionary is not None and d.dtype != object
            and d.dtype.kind in "iu" and d.dtype.itemsize < 8):
        return ("code", d.dtype.str), 0
    return ("plain",), 0


def device_plan(cache, ship_cols, nullable_cols) -> "DevicePlan | None":
    """The consumption plan for ``cache``'s blocks, or None when every
    shipped column is plain (callers keep the legacy pin signatures — an
    unencoded image behaves bit-for-bit as before this module existed).
    Memoized per (cache, enc_version, ship, nullable)."""
    blocks = cache.blocks
    if not blocks:
        return None
    import weakref

    key = (id(cache), getattr(cache, "enc_version", 0),
           tuple(ship_cols), tuple(nullable_cols))
    hit = _PLAN_MEMO.get(key)
    if hit is not None and hit[0]() is cache:
        # the weakref guards id reuse: a dead cache's id may be recycled,
        # but its entry's referent is gone, so a recycled id recomputes
        return hit[1]
    sig, refs = [], []
    for i in ship_cols:
        desc, ref = _col_desc(blocks[0].cols[i])
        sig.append(desc)
        refs.append(ref)
    null_sig = []
    for i in nullable_cols:
        c = blocks[0].cols[i]
        null_sig.append(("rle", c.k_cap) if isinstance(c, EncodedColumn)
                        and c.kind == "rle" else ("plain",))
    plan = DevicePlan(tuple(sig), tuple(null_sig),
                      np.asarray(refs, dtype=np.int64))
    if not plan.encoded:
        plan = None
    _PLAN_MEMO[key] = (weakref.ref(cache), plan)
    while len(_PLAN_MEMO) > _PLAN_MEMO_MAX:
        _PLAN_MEMO.pop(next(iter(_PLAN_MEMO)))
    return plan


def block_payload(col: Column, pad_rows: int, k_cap_pad: int | None = None):
    """The host array(s) to pin for one block column under its descriptor:
    plain/bp/code → the (narrow) row array padded to ``pad_rows``; rle →
    (run_values, run_ends) padded to the column's k_cap (ends padded with
    ``pad_rows`` so padding rows land in an inert pad run)."""
    if isinstance(col, EncodedColumn) and col.kind == "rle":
        k = k_cap_pad or col.k_cap
        rv = np.zeros(k, dtype=col.run_values.dtype)
        rv[: len(col.run_values)] = col.run_values
        re_ = np.full(k, pad_rows, dtype=np.int64)
        re_[: len(col.run_ends)] = col.run_ends
        return rv, re_
    arr = col.packed if isinstance(col, EncodedColumn) else col.data
    arr = np.asarray(arr)
    if len(arr) == pad_rows:
        return arr
    if arr.dtype == object:
        ext = np.empty(pad_rows - len(arr), dtype=object)
        ext[:] = b""
        return np.concatenate([arr, ext])
    return np.concatenate([arr, np.zeros(pad_rows - len(arr), dtype=arr.dtype)])


def block_null_payload(col: Column, pad_rows: int):
    """Null payload: run-shaped for rle columns, padded bool otherwise."""
    if isinstance(col, EncodedColumn) and col.kind == "rle":
        rn = np.ones(col.k_cap, dtype=bool)
        rn[: len(col.run_nulls)] = col.run_nulls
        return rn
    nulls = np.asarray(col.nulls if not isinstance(col, EncodedColumn)
                       else col._nulls)
    if len(nulls) == pad_rows:
        return nulls
    return np.concatenate([nulls, np.ones(pad_rows - len(nulls), dtype=bool)])


def stack_block_payloads(blocks, ship_cols, nullable_cols, plan,
                         pad_rows: int):
    """THE stacked payload assembly shared by every multi-block pin builder
    (``jax_eval._stacked_device`` and the mesh slab pins): per ship col a
    ``(B, rows)`` narrow array — or an ``((B, k), (B, k))`` run pair for
    rle — plus padded null payloads and the frame-of-reference vector.
    Host-side numpy; callers move the leaves to their device."""
    data = []
    for j, i in enumerate(ship_cols):
        payloads = [block_payload(b.cols[i], pad_rows) for b in blocks]
        if plan.sig[j][0] == "rle":
            data.append((np.stack([p[0] for p in payloads]),
                         np.stack([p[1] for p in payloads])))
        else:
            data.append(np.stack([np.asarray(p) for p in payloads]))
    nulls = [
        np.stack([block_null_payload(b.cols[i], pad_rows) for b in blocks])
        for i in nullable_cols
    ]
    return data, nulls, np.asarray(plan.refs)


def batch_plan(caches, ship_cols, nullable_cols, path: str,
               allow_rle: bool = True):
    """Cross-region consumption decision: ONE plan for every cache in the
    batch, or None to decode-ship (counted per-cause — a batch is only as
    encodable as its least compatible region)."""
    plans = [device_plan(c, ship_cols, nullable_cols) for c in caches]
    if all(p is None for p in plans):
        return None  # nothing encoded anywhere: legacy path, not a decline
    if any(p is None for p in plans):
        count_decline(path, "enc_mismatch")
        count_path(path, "decoded_ship")
        return None
    sigs = {(p.sig, p.null_sig) for p in plans}
    if len(sigs) != 1:
        count_decline(path, "enc_mismatch")
        count_path(path, "decoded_ship")
        return None
    if not allow_rle and any(d[0] == "rle" for d in plans[0].sig):
        count_decline(path, "rle_sharded")
        count_path(path, "decoded_ship")
        return None
    count_path(path, "encoded")
    return plans


def late_materialize_chunk(columns, logical):
    """Selection-output late materialization: when any output column is
    encoded, gather the surviving rows THROUGH the encodings (each
    EncodedColumn decodes only its selected rows) instead of letting the
    response encoder materialize whole columns.  Returns (columns,
    logical_rows) — unchanged for fully-plain blocks."""
    if not any(isinstance(c, EncodedColumn) for c in columns):
        return columns, logical
    taken = [c.take(logical) for c in columns]
    return taken, np.arange(len(logical))


# ---------------------------------------------------------------------------
# dictionary code-space predicate rewriting (unary warm path)
# ---------------------------------------------------------------------------

_CMP_OPS = {"eq", "ne", "lt", "le", "gt", "ge"}
_FLIP = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}


def dict_rewrite_probe(dag) -> bool:
    """Cheap pre-filter: a TableScan DAG whose selection compares BYTES
    columns against bytes constants MIGHT rewrite into code space.  No
    dictionary inspection here — the endpoint calls this on every CPU-bound
    request, so it must stay allocation-light."""
    from .dag import Aggregation, Selection, TableScan, TopN

    execs = list(getattr(dag, "executors", ()) or ())
    if not execs or type(execs[0]) is not TableScan:
        return False
    sel = next((e for e in execs[1:] if isinstance(e, Selection)), None)
    if sel is None:
        return False
    has_agg = any(isinstance(e, Aggregation) for e in execs[1:])
    if not has_agg and any(isinstance(e, TopN) for e in execs[1:]):
        return False  # raw TopN ships every column as payload (typed)
    bytes_cols = {
        i for i, c in enumerate(execs[0].columns_info)
        if c.ftype.eval_type == EvalType.BYTES
    }
    if not bytes_cols:
        return False
    return any(_rewritable_cond(c, bytes_cols) is not None
               for c in sel.conditions)


def _rewritable_cond(cond, bytes_cols):
    """(col_index, op, consts, flipped) for ``cmp(col, const)`` /
    ``cmp(const, col)`` / ``in(col, consts...)`` over a BYTES column."""
    from .rpn import ColumnRef, Constant, FuncCall

    if not isinstance(cond, FuncCall):
        return None
    ch = cond.children
    def _bytes_const(c):
        return (isinstance(c, Constant)
                and (c.value is None or c.eval_type == EvalType.BYTES))

    if cond.op == "in" and len(ch) >= 2 and isinstance(ch[0], ColumnRef) \
            and ch[0].index in bytes_cols \
            and all(_bytes_const(c) for c in ch[1:]):
        return ch[0].index, "in", [c.value for c in ch[1:]], False
    if cond.op in _CMP_OPS and len(ch) == 2:
        a, b = ch
        if isinstance(a, ColumnRef) and _bytes_const(b) \
                and a.index in bytes_cols:
            return a.index, cond.op, [b.value], False
        if _bytes_const(a) and isinstance(b, ColumnRef) \
                and b.index in bytes_cols:
            return b.index, _FLIP[cond.op], [a.value], True
    return None


def _expr_refs(expr, out: set) -> None:
    """Collect every column index referenced anywhere in an expression."""
    from .rpn import ColumnRef, FuncCall

    if isinstance(expr, ColumnRef):
        out.add(expr.index)
    elif isinstance(expr, FuncCall):
        for c in expr.children:
            _expr_refs(c, out)


def _dict_map_for(dictionary) -> tuple[dict, bool]:
    """(bytes→code map, is_sorted) for a dictionary object, memoized by
    identity (``_code_of`` mutation replaces the object, so a stale entry
    can never serve)."""
    key = id(dictionary)
    hit = _DICT_MAPS.get(key)
    if hit is not None and hit[0] is dictionary:
        return hit[1], hit[2]
    m = {bytes(v): j for j, v in enumerate(dictionary)}
    vals = [bytes(v) for v in dictionary]
    is_sorted = all(vals[j] < vals[j + 1] for j in range(len(vals) - 1))
    _DICT_MAPS[key] = (dictionary, m, is_sorted)
    while len(_DICT_MAPS) > _DICT_MAPS_MAX:
        _DICT_MAPS.pop(next(iter(_DICT_MAPS)))
    return m, is_sorted


def rewrite_dag_for_dict(dag, blocks):
    """Rewrite ``dag``'s bytes predicates into the dictionary code space of
    a WARM image's blocks: the BYTES column's schema entry becomes INT (the
    evaluator then ships codes — already resident — and compares integer
    lanes), equality/IN constants map through the dictionary (absent value
    → code -1, which no row carries), and range constants become
    ``searchsorted`` ranks when the dictionary is SORTED (an unsorted or
    delta-grown dictionary declines range ops — cause ``dict_unsorted``).

    Returns (rewritten DagRequest, rewritten col set) or (None, cause)."""
    from .dag import DagRequest, Selection, TableScan
    from .datatypes import ColumnInfo, FieldType, FieldTypeTp
    from .rpn import ColumnRef, Constant, FuncCall

    from .dag import Aggregation, TopN

    execs = list(dag.executors)
    scan = execs[0]
    bytes_cols = {
        i for i, c in enumerate(scan.columns_info)
        if c.ftype.eval_type == EvalType.BYTES
    }
    sel_pos = next((k for k, e in enumerate(execs) if isinstance(e, Selection)), None)
    if sel_pos is None:
        return None, "no_selection"
    sel = execs[sel_pos]
    if (any(isinstance(e, TopN) for e in execs[1:])
            and not any(isinstance(e, Aggregation) for e in execs[1:])):
        # raw TopN ships EVERY schema column as typed payload — a rewritten
        # column would finalize as integers (probe blocks this too; kept
        # here so direct callers can't serve codes)
        return None, "topn_payload"

    candidates: set[int] = set()
    for cond in sel.conditions:
        rec = _rewritable_cond(cond, bytes_cols)
        if rec is not None:
            candidates.add(rec[0])
    if not candidates:
        return None, "no_rewritable_predicate"

    # a rewritten column's schema entry becomes INT, so ANY reference to it
    # outside its rewritten conjuncts — an aggregate argument, a group-by
    # key, a TopN order, an unrewritable condition — would evaluate (and
    # SERVE) raw dictionary codes instead of the strings.  Those references
    # type-check fine after the flip, so jax_eval.supports cannot catch
    # them: decline here, before any evaluator exists.
    outside: set[int] = set()
    for cond in sel.conditions:
        rec = _rewritable_cond(cond, bytes_cols)
        if rec is None or rec[0] not in candidates:
            _expr_refs(cond, outside)
    for e in execs[1:]:
        if isinstance(e, Aggregation):
            for g in e.group_by:
                _expr_refs(g, outside)
            for a in e.agg_funcs:
                if getattr(a, "expr", None) is not None:
                    _expr_refs(a.expr, outside)
        elif isinstance(e, TopN):
            for expr, _desc in e.order_by:
                _expr_refs(expr, outside)
    candidates -= outside
    if not candidates:
        return None, "outside_reference"

    new_conds = []
    rewritten: set[int] = set()
    for cond in sel.conditions:
        rec = _rewritable_cond(cond, bytes_cols)
        if rec is None or rec[0] not in candidates:
            new_conds.append(cond)
            continue
        ci, op, consts, _flipped = rec
        col0 = blocks[0].cols[ci]
        if col0.dictionary is None or np.asarray(col0.data).dtype == object:
            return None, "not_dict_resident"
        for b in blocks[1:]:
            if b.cols[ci].dictionary is not col0.dictionary:
                return None, "unstable_dictionary"
        cmap, is_sorted = _dict_map_for(col0.dictionary)
        if op in ("eq", "ne"):
            c = consts[0]
            code = None if c is None else cmap.get(bytes(c), -1)
            new_conds.append(FuncCall(op, [ColumnRef(ci),
                                           Constant(code, EvalType.INT)]))
        elif op == "in":
            kept: list[int] = []
            has_null_literal = False
            for orig in consts:
                if orig is None:
                    has_null_literal = True  # keeps IN three-valued
                    continue
                code = cmap.get(bytes(orig))
                if code is not None:
                    kept.append(code)
            if not kept:
                kept.append(-1)  # no row carries code -1
            in_args = [Constant(c, EvalType.INT) for c in kept]
            if has_null_literal:
                in_args.append(Constant(None, EvalType.INT))
            new_conds.append(FuncCall("in", [ColumnRef(ci)] + in_args))
        else:  # lt / le / gt / ge need an ORDER-preserving code space
            if not is_sorted:
                # the endpoint counts every decline once from the returned
                # cause — counting here too would double this one cause
                return None, "dict_unsorted"
            c = consts[0]
            if c is None:
                new_conds.append(FuncCall(op, [ColumnRef(ci),
                                               Constant(None, EvalType.INT)]))
            else:
                vals = [bytes(v) for v in col0.dictionary]
                p_left = int(np.searchsorted(np.array(vals, dtype=object), bytes(c), side="left"))
                p_right = int(np.searchsorted(np.array(vals, dtype=object), bytes(c), side="right"))
                if op == "lt":
                    node = FuncCall("lt", [ColumnRef(ci), Constant(p_left, EvalType.INT)])
                elif op == "le":
                    node = FuncCall("lt", [ColumnRef(ci), Constant(p_right, EvalType.INT)])
                elif op == "gt":
                    node = FuncCall("ge", [ColumnRef(ci), Constant(p_right, EvalType.INT)])
                else:  # ge
                    node = FuncCall("ge", [ColumnRef(ci), Constant(p_left, EvalType.INT)])
                new_conds.append(node)
        rewritten.add(ci)

    new_cols = []
    for i, info in enumerate(scan.columns_info):
        if i in rewritten:
            ft = FieldType(FieldTypeTp.LONGLONG, info.ftype.flag)
            new_cols.append(ColumnInfo(info.col_id, ft, info.is_pk_handle,
                                       info.default_value))
        else:
            new_cols.append(info)
    new_scan = TableScan(scan.table_id, new_cols)
    new_execs = [new_scan] + execs[1:]
    new_execs[sel_pos] = Selection(new_conds)
    # deliberately NOT propagating encode_type: the rewritten plan's STATIC
    # schema lies about the runtime columns (a rewritten bytes column is
    # declared LONGLONG while the served column still materializes bytes
    # through its dictionary), which the value-driven datum encoder never
    # reads but the schema-driven chunk encoder would — so the rewrite rung
    # is datum-only and the endpoint declines it for chunk-negotiated
    # requests (endpoint._try_dict_rewrite)
    return DagRequest(
        executors=new_execs,
        output_offsets=dag.output_offsets,
        chunk_rows=dag.chunk_rows,
    ), rewritten
