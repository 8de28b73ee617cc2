"""Row format v2: id-indexed compact row encoding + batch columnar decode.

Re-expression of ``tidb_query_datatype/src/codec/row/v2/`` (row_slice.rs:30
header layout, compat_v1.rs:13 cell encodings).  Layout per row:

    [128][flags][non_null_cnt u16 LE][null_cnt u16 LE]
    [non-null ids asc][null ids asc][end-offsets][cell values]

ids/offsets are u8/u16 in the small form, u32/u32 when any id > 255 or the
value section exceeds 64KiB (flags bit 0 = big).  NULL columns store no value
at all; absent columns fall back to schema defaults — both reasons v2 rows
are much smaller than datum (v1) rows for wide sparse schemas.

Cell encodings (compat_v1.rs write_v2_as_datum):

* INT family / YEAR: little-endian minimal width (1/2/4/8), sign-extended
* DATETIME / ENUM / SET and unsigned ints: LE minimal width, zero-extended
* DURATION: signed LE minimal width
* REAL: this framework's 8-byte memcomparable f64 (util.codec.encode_f64)
* BYTES: raw; JSON: binary JSON (self-delimiting)
* DECIMAL: ``[prec][frac][MySQL bin decimal]`` (mydecimal.encode_bin).  The
  stored cell covers the full 81-digit envelope; the *columnar* decode bridges
  to the device's scaled-int64 form (≤18 digits) and rejects wider values
  with a pointer to ``decode_cell_wide`` for host-side access.

TPU-first: the batch decoder recognises blocks whose rows share one byte
layout (same ids, same offsets — the steady state for fixed-width schemas)
and decodes each column with one numpy reshape+slice over the whole block,
the same trick ``RowBatchDecoder._try_fast_decode`` plays for v1 rows.
"""

from __future__ import annotations

import numpy as np

from ..util import codec
from ..util.metrics import REGISTRY
from .byterows import ByteRows
from .datatypes import Column, ColumnInfo, EvalType, attach_schema_dictionary, typed_column
from .mydecimal import DIGITS_PER_WORD, DecimalOverflow, MyDecimal

CODEC_VERSION = 128
FLAG_BIG = 1

_DEFAULT_PREC = 65  # MySQL max precision, used when the schema has no flen


# ---------------------------------------------------------------------------
# Encoding
# ---------------------------------------------------------------------------

def _enc_i64_le(v: int) -> bytes:
    """Signed LE minimal width (1/2/4/8)."""
    for w in (1, 2, 4, 8):
        if -(1 << (8 * w - 1)) <= v < 1 << (8 * w - 1):
            return int(v).to_bytes(w, "little", signed=True)
    raise OverflowError(v)


def _enc_u64_le(v: int) -> bytes:
    for w in (1, 2, 4, 8):
        if v < 1 << (8 * w):
            return int(v).to_bytes(w, "little")
    raise OverflowError(v)


def _decimal_prec(info: ColumnInfo) -> int:
    return info.ftype.flen if info.ftype.flen and info.ftype.flen > 0 else _DEFAULT_PREC


def _encode_cell(info: ColumnInfo, v) -> bytes:
    et = info.ftype.eval_type
    if et == EvalType.INT:
        if info.ftype.is_unsigned:
            return _enc_u64_le(int(v) & ((1 << 64) - 1))
        return _enc_i64_le(int(v))
    if et in (EvalType.DATETIME, EvalType.ENUM, EvalType.SET):
        return _enc_u64_le(int(v))
    if et == EvalType.DURATION:
        return _enc_i64_le(int(v))
    if et == EvalType.REAL:
        return codec.encode_f64(float(v))
    if et == EvalType.BYTES:
        return bytes(v)
    if et == EvalType.JSON:
        return bytes(v)
    if et == EvalType.DECIMAL:
        frac = info.ftype.decimal
        prec = _decimal_prec(info)
        if isinstance(v, MyDecimal):
            d = v
        else:
            d = MyDecimal.from_i64_scaled(int(v), frac)
        return bytes([prec, frac]) + d.encode_bin(prec, frac)
    raise ValueError(f"unsupported eval type {et}")


def encode_row_v2(columns: list[ColumnInfo], values: list) -> bytes:
    """Encode one row. ``values`` align with ``columns``; None ⇒ NULL."""
    cells: list[tuple[int, bytes]] = []
    null_ids: list[int] = []
    for info, v in zip(columns, values):
        if v is None:
            null_ids.append(info.col_id)
        else:
            cells.append((info.col_id, _encode_cell(info, v)))
    cells.sort()
    null_ids.sort()

    value_len = sum(len(c) for _, c in cells)
    big = (
        any(cid > 255 for cid, _ in cells)
        or any(cid > 255 for cid in null_ids)
        or value_len > 0xFFFF
    )
    id_w, off_w = (4, 4) if big else (1, 2)

    out = bytearray([CODEC_VERSION, FLAG_BIG if big else 0])
    out += len(cells).to_bytes(2, "little")
    out += len(null_ids).to_bytes(2, "little")
    for cid, _ in cells:
        out += cid.to_bytes(id_w, "little")
    for cid in null_ids:
        out += cid.to_bytes(id_w, "little")
    end = 0
    for _, c in cells:
        end += len(c)
        out += end.to_bytes(off_w, "little")
    for _, c in cells:
        out += c
    return bytes(out)


# ---------------------------------------------------------------------------
# Per-row slice (row_slice.rs RowSlice)
# ---------------------------------------------------------------------------

class RowSliceV2:
    """Parsed header over one encoded row; cell lookup by column id."""

    __slots__ = ("raw", "non_null_ids", "null_ids", "offsets", "values_start")

    def __init__(self, raw: bytes):
        if not raw or raw[0] != CODEC_VERSION:
            raise ValueError("not a v2 row")
        if len(raw) < 6:
            raise ValueError("truncated v2 row")
        big = bool(raw[1] & FLAG_BIG)
        nn = int.from_bytes(raw[2:4], "little")
        nl = int.from_bytes(raw[4:6], "little")
        id_w = 4 if big else 1
        off_w = 4 if big else 2
        pos = 6
        self.raw = raw
        self.non_null_ids = [
            int.from_bytes(raw[pos + i * id_w : pos + (i + 1) * id_w], "little")
            for i in range(nn)
        ]
        pos += nn * id_w
        self.null_ids = [
            int.from_bytes(raw[pos + i * id_w : pos + (i + 1) * id_w], "little")
            for i in range(nl)
        ]
        pos += nl * id_w
        self.offsets = [
            int.from_bytes(raw[pos + i * off_w : pos + (i + 1) * off_w], "little")
            for i in range(nn)
        ]
        pos += nn * off_w
        self.values_start = pos
        # Truncation check (row_slice.rs returns Error::corrupted on short
        # input): every header int above decoded from a short slice as 0, so
        # without this a truncated row yields garbage cells instead of failing.
        if pos > len(raw) or (self.offsets and pos + self.offsets[-1] > len(raw)):
            raise ValueError("truncated v2 row")

    def header_len(self) -> int:
        return self.values_start

    def get(self, col_id: int):
        """cell bytes | None (NULL) — raises KeyError when the id is absent."""
        lo, hi = 0, len(self.non_null_ids)
        while lo < hi:
            mid = (lo + hi) // 2
            if self.non_null_ids[mid] < col_id:
                lo = mid + 1
            else:
                hi = mid
        if lo < len(self.non_null_ids) and self.non_null_ids[lo] == col_id:
            start = self.offsets[lo - 1] if lo else 0
            # lint: allow(view-escape) -- self.raw is bytes (immutable): the
            # slice is a copy by construction, no aliasing view can escape
            return self.raw[self.values_start + start : self.values_start + self.offsets[lo]]
        if col_id in self.null_ids:
            return None
        raise KeyError(col_id)


def _dec_i64_le(cell: bytes) -> int:
    return int.from_bytes(cell, "little", signed=True)


def _dec_u64_le(cell: bytes) -> int:
    return int.from_bytes(cell, "little")


def decode_cell(info: ColumnInfo, cell: bytes):
    """One cell → the column's stored Python value (scaled int for DECIMAL)."""
    et = info.ftype.eval_type
    if et == EvalType.INT:
        if info.ftype.is_unsigned:
            v = _dec_u64_le(cell)
            return v - (1 << 64) if v >= 1 << 63 else v  # int64 view
        return _dec_i64_le(cell)
    if et in (EvalType.DATETIME, EvalType.ENUM, EvalType.SET):
        return _dec_u64_le(cell)
    if et == EvalType.DURATION:
        return _dec_i64_le(cell)
    if et == EvalType.REAL:
        return codec.decode_f64(cell)
    if et in (EvalType.BYTES, EvalType.JSON):
        return bytes(cell)
    if et == EvalType.DECIMAL:
        d = decode_cell_wide(cell)
        try:
            return d.round(info.ftype.decimal).to_i64_scaled()[0]
        except DecimalOverflow as e:
            raise ValueError(
                f"decimal {d} exceeds the columnar scaled-int64 form "
                f"(≤18 digits); read it through RowSliceV2.get + "
                f"decode_cell_wide instead"
            ) from e
    raise ValueError(f"unsupported eval type {et}")


def decode_cell_wide(cell: bytes) -> MyDecimal:
    """Full-envelope (81-digit) decode of a DECIMAL cell."""
    prec, frac = cell[0], cell[1]
    d, _ = MyDecimal.decode_bin(cell[2:], prec, frac)
    return d


# ---------------------------------------------------------------------------
# Batch decode
# ---------------------------------------------------------------------------

def is_v2_row(raw: bytes) -> bool:
    return bool(raw) and raw[0] == CODEC_VERSION


def v2_rows(row_values) -> np.ndarray:
    """``is_v2_row`` of every row of a block, as a mask."""
    if isinstance(row_values, ByteRows):
        lead = np.zeros(len(row_values), dtype=np.uint8)
        held = row_values.lens > 0
        lead[held] = row_values.flat[row_values.at[held]]
        return lead == CODEC_VERSION
    return np.fromiter(map(is_v2_row, row_values), dtype=bool, count=len(row_values))


# A mixed block of fewer rows than this takes the per-row walk: the arrays
# cost a few dozen numpy calls a column whatever the block holds (~500 us a
# block of seven columns, ~1 ms of sixteen), the walk 20-35 us a row
# (measured once, on LINEITEM's rows: the two cross at 20-30 rows).
_VECTOR_MIN_ROWS = 24
# Rows of one header shape (flags, non-null count, null count) decode as one
# rectangle; a shape fewer rows than this share walks, so a block of one
# shape a row costs what it did.
_MIN_GROUP_ROWS = 8

_DECODED_ROWS = REGISTRY.counter(
    "tikv_coprocessor_rowv2_decode_rows_total",
    "Row-format-v2 rows decoded into columns, by path: uniform (one layout, "
    "a reshape), vector (mixed layouts, arrays), walk (per row)")
_ROWS_UNIFORM = _DECODED_ROWS.labels(path="uniform")
_ROWS_VECTOR = _DECODED_ROWS.labels(path="vector")
_ROWS_WALK = _DECODED_ROWS.labels(path="walk")


def decode_rows_v2(schema: list[ColumnInfo], row_values) -> list[Column]:
    """Decode a block of v2 rows into Columns (handle columns left zeroed)."""
    return decode_block(schema, row_values)[0]


def decode_block(schema: list[ColumnInfo], row_values) -> tuple[list[Column], str]:
    """``decode_rows_v2`` and the path the block took, as the counter
    ``tikv_coprocessor_rowv2_decode_rows_total{path}`` names it.

    The block (a list of ``bytes``, or the ``ByteRows`` a cold fill hands
    over) is read as one flat byte buffer with the rows' places in it.
    Rows that all share the first row's exact header bytes (ids + offsets)
    put each cell at one fixed [start, end) ⇒ a reshape (``_fast_decode``,
    ``uniform``).  Mixed layouts — TiDB's steady state: integers at their
    least width, strings at their own length — find each cell from the rows'
    own offset tables, a shape of header at a time (``_vector_decode``,
    ``vector``).  Either way a column's cells decode as arrays
    (``_decode_cells``).  Only what the arrays cannot judge, and blocks too
    small for them to pay, take the per-row walk (``_slow_decode``,
    ``walk``), which is also the reference of the other two.
    """
    rows = ByteRows.of(row_values)
    n = len(rows)
    first = RowSliceV2(rows[0])
    mat = rows.matrix()
    if mat is not None:
        h = first.header_len()
        if (mat[:, :h] == mat[0, :h]).all():
            return _fast_decode(schema, first, rows), "uniform"
    if n < _VECTOR_MIN_ROWS:
        return _slow_decode(schema, rows, n), "walk"
    return _vector_decode(schema, rows), "vector"


def _zero_handle(n: int) -> Column:
    return Column(EvalType.INT, np.zeros(n, dtype=np.int64), np.zeros(n, dtype=bool))


def _absent_column(info: ColumnInfo, n: int, null: bool) -> Column:
    """The column of ``n`` rows that do not carry ``info``'s id: NULL where
    the row lists it as NULL or the schema has no default, else the default."""
    v = None if null else info.default_value
    return typed_column(info, [v] * n)


def _fast_decode(schema, first: RowSliceV2, rows: ByteRows) -> list[Column]:
    n = len(rows)
    _ROWS_UNIFORM.inc(n)
    base = first.values_start
    cell_pos = {}
    for i, cid in enumerate(first.non_null_ids):
        start = first.offsets[i - 1] if i else 0
        cell_pos[cid] = (base + start, base + first.offsets[i])
    null_ids = set(first.null_ids)

    out: list[Column] = []
    for info in schema:
        if info.is_pk_handle:
            out.append(_zero_handle(n))
            continue
        span = cell_pos.get(info.col_id)
        if span is None:
            out.append(_absent_column(info, n, info.col_id in null_ids))
            continue
        s, e = span
        at = rows.at + s
        data, bad = _decode_cells(info, rows, at, np.full(n, e - s, dtype=np.int64))
        for r in np.flatnonzero(bad).tolist():
            data[r] = decode_cell(info, rows.raw[at[r] : at[r] + e - s])
        out.append(_column(info, data, np.zeros(n, dtype=bool)))
    return out


def _column(info: ColumnInfo, data: np.ndarray, nulls: np.ndarray) -> Column:
    col = Column(info.ftype.eval_type, data, nulls, info.ftype.decimal)
    return attach_schema_dictionary(info, col)


def _read_le(flat: np.ndarray, at: np.ndarray, w: int) -> np.ndarray:
    """Unsigned little-endian ints of ``w`` (1, 2, 4) bytes at ``at``."""
    v = flat[at].astype(np.int64)
    for b in range(1, w):
        v |= flat[at + b].astype(np.int64) << (8 * b)
    return v


def _read_be(m: np.ndarray, pos: int, nbytes: int) -> np.ndarray:
    """Unsigned big-endian ints of columns [pos, pos + nbytes) of ``m``."""
    v = np.zeros(len(m), dtype=np.int64)
    for b in range(nbytes):
        v = (v << 8) | m[:, pos + b]
    return v


def _vector_decode(schema, block: ByteRows) -> list[Column]:
    """Mixed layouts, no Python object per row or per cell.

    Rows group by header shape (flags, non-null count, null count), which a
    table gives a handful of values; within a shape the ids and the offset
    table sit at fixed positions behind the row's start, so each wanted
    column's cell start and width come out of one gather a shape.  The cells
    of a column then decode across the whole block (``_decode_cells``).  Rows
    the arrays cannot judge — not v2, truncated, ids out of order, offsets
    running backwards, a decimal too wide for int64 — take ``_slow_decode``
    and raise what it raises."""
    flat, row_at, lens = block.flat, block.at, block.lens
    n = len(lens)
    wanted = [i for i, info in enumerate(schema) if not info.is_pk_handle]
    col_ids = [schema[i].col_id for i in wanted]
    # per wanted column and row: the cell's start in ``flat``, its width, and
    # whether the row holds it (1), lists it as NULL (2) or lacks it (0)
    cell_at = np.zeros((len(wanted), n), dtype=np.int64)
    cell_w = np.zeros((len(wanted), n), dtype=np.int64)
    state = np.zeros((len(wanted), n), dtype=np.int8)

    walk = np.ones(n, dtype=bool)  # until a shape claims the row
    live = np.flatnonzero(lens >= 6)
    live = live[flat[row_at[live]] == CODEC_VERSION]
    at = row_at[live]
    shape = (
        ((flat[at + 1] & FLAG_BIG).astype(np.int64) << 32)
        | (_read_le(flat, at + 2, 2) << 16)
        | _read_le(flat, at + 4, 2)
    )
    order = np.argsort(shape, kind="stable")
    uniq, starts = np.unique(shape[order], return_index=True)
    bounds = np.append(starts, len(order))
    for g, code in enumerate(uniq.tolist()):
        sel = order[bounds[g] : bounds[g + 1]]
        if len(sel) < _MIN_GROUP_ROWS:
            continue
        big, nn, nl = code >> 32, (code >> 16) & 0xFFFF, code & 0xFFFF
        id_w, off_w = (4, 4) if big else (1, 2)
        off_at = 6 + (nn + nl) * id_w
        vals_at = off_at + nn * off_w
        rows, g_at = live[sel], at[sel]
        fits = lens[rows] >= vals_at
        rows, g_at = rows[fits], g_at[fits]
        span = np.arange(nn, dtype=np.int64)
        ids = _read_le(flat, g_at[:, None] + (6 + span * id_w), id_w)
        ends = _read_le(flat, g_at[:, None] + (off_at + span * off_w), off_w)
        null_ids = _read_le(
            flat, g_at[:, None] + (6 + (nn + np.arange(nl, dtype=np.int64)) * id_w), id_w)
        # what RowSliceV2 checks (the values fit the row) and what its binary
        # search assumes (ids ascending); offsets running backwards would
        # slice nothing there and must not index backwards here
        good = np.ones(len(rows), dtype=bool)
        if nn:
            good &= vals_at + ends[:, -1] <= lens[rows]
            good &= (ids[:, 1:] > ids[:, :-1]).all(axis=1)
            good &= (ends[:, 1:] >= ends[:, :-1]).all(axis=1)
        rows, g_at, ids, ends, null_ids = (
            rows[good], g_at[good], ids[good], ends[good], null_ids[good])
        walk[rows] = False
        begins = np.concatenate(
            [np.zeros((len(rows), 1), dtype=np.int64), ends[:, :-1]], axis=1)
        each = np.arange(len(rows))
        for c, cid in enumerate(col_ids):
            st = np.where((null_ids == cid).any(axis=1), 2, 0)
            if nn:
                hit = ids == cid
                pos = hit.argmax(axis=1)
                cell_at[c, rows] = g_at + vals_at + begins[each, pos]
                cell_w[c, rows] = ends[each, pos] - begins[each, pos]
                st = np.where(hit.any(axis=1), 1, st)
            state[c, rows] = st

    out: list[Column] = [None] * len(schema)
    for c, i in enumerate(wanted):
        info = schema[i]
        held = np.flatnonzero((state[c] == 1) & ~walk)
        vals, bad = _decode_cells(info, block, cell_at[c, held], cell_w[c, held])
        walk[held[bad]] = True
        # a row without the id reads the schema's default, NULL if none
        dflt = _absent_column(info, 1, False)
        data = np.full(n, dflt.data[0], dtype=dflt.data.dtype)
        data[held] = vals
        nulls = state[c] == 2
        data[nulls] = _absent_column(info, 1, True).data[0]
        if dflt.nulls[0]:
            nulls |= state[c] == 0
        out[i] = _column(info, data, nulls)
    for i, info in enumerate(schema):
        if info.is_pk_handle:
            out[i] = _zero_handle(n)
    widx = np.flatnonzero(walk)
    if len(widx):
        walked = _slow_decode(schema, block[widx], len(widx))
        for col, w in zip(out, walked):
            col.data[widx] = w.data
            col.nulls[widx] = w.nulls
    _ROWS_VECTOR.inc(n - len(widx))
    return out


def _decode_cells(info: ColumnInfo, rows: ByteRows, at: np.ndarray, width: np.ndarray):
    """The cells of one column, at ``[at, at + width)`` of ``rows``' buffer,
    decoded as ``decode_cell`` decodes each: ``(values, bad)``, ``bad``
    marking the cells the arrays cannot judge (their values are left zero)."""
    et = info.ftype.eval_type
    flat, raw = rows.flat, rows.raw
    k = len(at)
    if et in (EvalType.BYTES, EvalType.JSON):
        vals = np.empty(k, dtype=object)
        vals[:] = [raw[a:b] for a, b in zip(at.tolist(), (at + width).tolist())]
        return vals, np.zeros(k, dtype=bool)
    if et == EvalType.DECIMAL:
        return _decimal_cells(flat, at, width, info.ftype.decimal)
    if et == EvalType.REAL:
        bad = width < 8
        vals = np.zeros(k, dtype=np.float64)
        ok = np.flatnonzero(~bad)
        vals[ok] = codec.decode_f64_batch(flat[at[ok, None] + np.arange(8)])
        return vals, bad
    if et not in (EvalType.INT, EvalType.DATETIME, EvalType.DURATION,
                  EvalType.ENUM, EvalType.SET):
        raise ValueError(f"unsupported eval type {et}")
    # little-endian at the least width: gather each width's cells into 8 bytes
    signed = et == EvalType.DURATION or (et == EvalType.INT and not info.ftype.is_unsigned)
    vals = np.zeros(k, dtype=np.int64)
    bad = width > 8
    for w in np.unique(width[~bad]).tolist():
        if w == 0:
            continue
        sel = np.flatnonzero(width == w)
        cells = flat[at[sel, None] + np.arange(w)]
        vals[sel] = (_le_signed_batch(cells, w) if signed
                     else _le_unsigned_batch(cells, w).view(np.int64))
    if et == EvalType.SET:
        return vals.view(np.uint64), bad
    if not signed and et != EvalType.INT:
        bad |= vals < 0  # 2^63 and over: the walk's int64 column refuses it
    return vals, bad


def _decimal_cells(flat: np.ndarray, at: np.ndarray, width: np.ndarray, target: int):
    """DECIMAL cells ``[prec][frac][MySQL binary decimal]`` into int64 scaled
    by ``10^target``, rounded half away from zero as ``MyDecimal.round``
    rounds.  Cells group by (prec, frac): a group's words sit at fixed
    offsets.  ``bad`` where the value may not fit 18 digits at the target
    scale (whatever the precision declares: DECIMAL(65, 2) cells of small
    values decode here), the cell is short, or its fraction's words exceed
    their digits."""
    k = len(at)
    vals = np.zeros(k, dtype=np.int64)
    bad = width < 2
    if not 0 <= target <= 18:
        bad[:] = True
        return vals, bad
    word = 10**DIGITS_PER_WORD
    ok = np.flatnonzero(~bad)
    pf = (flat[at[ok]].astype(np.int64) << 8) | flat[at[ok] + 1]
    for code in np.unique(pf).tolist():
        prec, frac = divmod(code, 256)
        sel = ok[pf == code]
        int_cnt = prec - frac
        size = MyDecimal.bin_size(prec, frac) if int_cnt >= 0 else 0
        if size == 0 or frac > 18:
            bad[sel] = True
            continue
        short = width[sel] - 2 < size
        bad[sel[short]] = True
        sel = sel[~short]
        m = flat[at[sel, None] + (2 + np.arange(size))]
        neg = m[:, 0] < 0x80
        m[neg] ^= 0xFF
        m[:, 0] ^= 0x80
        # the integer digits' leftover group leads, the fraction's trails
        int_full, int_left = divmod(int_cnt, DIGITS_PER_WORD)
        frac_full, frac_left = divmod(frac, DIGITS_PER_WORD)
        lead, trail = MyDecimal.bin_size(int_left, 0), MyDecimal.bin_size(frac_left, 0)
        words = [_read_be(m, 0, lead)] + [
            _read_be(m, lead + 4 * i, 4) for i in range(int_full)]
        # the two lowest words hold 18 digits; anything above them is too wide
        wild = np.zeros(len(sel), dtype=bool)
        for w in words[:-2]:
            wild |= w != 0
        ip = words[-1] if len(words) == 1 else words[-2] * word + words[-1]
        wild |= ip >= 10 ** (18 - target)
        pos = lead + 4 * int_full
        fp = np.zeros(len(sel), dtype=np.int64)
        for _ in range(frac_full):
            fp = fp * word + _read_be(m, pos, 4)
            pos += 4
        fp = fp * 10**frac_left + _read_be(m, pos, trail)
        wild |= fp >= 10**frac
        bad[sel[wild]] = True
        if frac <= target:
            fp = fp * 10 ** (target - frac)
        else:
            base = 10 ** (frac - target)
            fp = (fp + base // 2) // base
        mag = np.where(wild, 0, ip * 10**target + fp)
        vals[sel] = np.where(neg, -mag, mag)
    return vals, bad


def _le_unsigned_batch(raw: np.ndarray, w: int) -> np.ndarray:
    padded = np.zeros((len(raw), 8), dtype=np.uint8)
    padded[:, :w] = raw
    return padded.view(np.uint64).reshape(len(raw))


def _le_signed_batch(raw: np.ndarray, w: int) -> np.ndarray:
    u = _le_unsigned_batch(raw, w)
    if w == 8:
        return u.view(np.int64)
    sign = 1 << (8 * w - 1)
    return np.where(u >= sign, u.astype(np.int64) - (1 << (8 * w)), u.astype(np.int64))


def _slow_decode(schema, row_values, n) -> list[Column]:
    _ROWS_WALK.inc(n)
    slices = [RowSliceV2(rv) for rv in row_values]
    out: list[Column] = []
    for info in schema:
        if info.is_pk_handle:
            out.append(_zero_handle(n))
            continue
        vals = []
        for sl in slices:
            try:
                cell = sl.get(info.col_id)
            except KeyError:
                vals.append(info.default_value)
                continue
            vals.append(None if cell is None else decode_cell(info, cell))
        out.append(typed_column(info, vals))
    return out
