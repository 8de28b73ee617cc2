"""The table every configuration here loads: TPC-H LINEITEM, all sixteen
columns, as TiDB 5.1 stores it in TiKV.

Values follow dbgen (TPC-H v3, cl. 4.2.3): sparse order keys (8 of every 32),
1-7 lines an order, part and supplier keys and the retail-price formula at the
configuration's scale factor, ship, commit and receipt dates hung on the
order's date, return flag and line status from those dates against 1995-06-17,
and a comment of 10-43 characters cut from a pool of text.  The pool is made
here from a short word list, not from dbgen's grammar (the configuration's
``assumed``).

Stored as TiDB's DDL for TPC-H declares it (BIGINT keys, DECIMAL(15,2),
CHAR(1), DATE, CHAR(25), CHAR(10), VARCHAR(44)) in row format 2, TiDB's default
since 4.0: integers at their least width, decimals in MySQL's binary form,
dates as packed times, strings at their own length.  The primary key
(l_orderkey, l_linenumber) is not clustered (TiDB 5.1's default for a composite
key), so the handle is ``_tidb_rowid``: row ``i`` has handle ``i + 1``.

``Table`` is the plain columnar form the reference reads; it never passes
through the program.  Rows are encoded here with numpy, so that no later PR to
the program can move the yardstick; ``selfcheck`` holds one block of them to
the program's own encoder and decoder.
"""

from __future__ import annotations

import datetime
import functools
from dataclasses import dataclass, fields

import numpy as np

# name, TiDB column type, kind
LINEITEM = (
    ("orderkey", "BIGINT", "int"), ("partkey", "BIGINT", "int"),
    ("suppkey", "BIGINT", "int"), ("linenumber", "BIGINT", "int"),
    ("quantity", "DECIMAL(15,2)", "dec"), ("extendedprice", "DECIMAL(15,2)", "dec"),
    ("discount", "DECIMAL(15,2)", "dec"), ("tax", "DECIMAL(15,2)", "dec"),
    ("returnflag", "CHAR(1)", "code"), ("linestatus", "CHAR(1)", "code"),
    ("shipdate", "DATE", "date"), ("commitdate", "DATE", "date"),
    ("receiptdate", "DATE", "date"), ("shipinstruct", "CHAR(25)", "code"),
    ("shipmode", "CHAR(10)", "code"), ("comment", "VARCHAR(44)", "text"),
)
COLUMN_ID = {name: i + 1 for i, (name, _t, _k) in enumerate(LINEITEM)}
WORDS = {
    "returnflag": (b"A", b"N", b"R"),
    "linestatus": (b"F", b"O"),
    "shipinstruct": (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN"),
    "shipmode": (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB"),
}
DECIMAL_PREC, DECIMAL_FRAC = 15, 2
KEY_BYTES = 19
SF1_ROWS = 6_001_215

EPOCH = datetime.date(1970, 1, 1)
START_DAY = (datetime.date(1992, 1, 1) - EPOCH).days
CURRENT_DAY = (datetime.date(1995, 6, 17) - EPOCH).days
END_DAY = (datetime.date(1998, 12, 31) - EPOCH).days

_POOL_WORDS = (
    "furiously quickly carefully blithely slyly fluffily final regular special "
    "ironic express bold pending unusual even silent daring packages requests "
    "accounts deposits foxes ideas theodolites pinto beans instructions "
    "dependencies excuses platelets asymptotes courts dolphins sleep wake are "
    "cajole haggle nag use boost affix detect integrate about above across "
    "after against along among around the of to").split()


@functools.lru_cache(maxsize=1)
def text_pool(size: int = 1 << 20) -> np.ndarray:
    """The text comments are cut from: the same for every seed (read-only,
    made once a process)."""
    rng = np.random.default_rng(19920101)
    words = rng.choice(np.array(_POOL_WORDS), size=size // 5)
    return np.frombuffer(" ".join(words).encode()[:size], dtype=np.uint8)


def day(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - EPOCH).days


def pack_days(days) -> np.ndarray:
    """Days since 1970-01-01 as TiDB's packed time with no time of day:
    ``((year * 13 + month) << 46) | (day << 41)``."""
    d = np.asarray(days, dtype="int64").astype("datetime64[D]")
    y = d.astype("datetime64[Y]").astype(np.int64) + 1970
    m = d.astype("datetime64[M]").astype(np.int64) % 12 + 1
    dom = (d - d.astype("datetime64[M]")).astype(np.int64) + 1
    return ((y * 13 + m) << 46) | (dom << 41)


@dataclass
class Table:
    """Columns of one set of rows, in handle order.  Decimals are unscaled
    (two places), dates are days since 1970-01-01, the four word columns are
    indexes into ``WORDS``, a comment is ``comment_len`` bytes of the pool
    from ``comment_at``."""

    handle: np.ndarray
    orderkey: np.ndarray
    partkey: np.ndarray
    suppkey: np.ndarray
    linenumber: np.ndarray
    quantity: np.ndarray
    extendedprice: np.ndarray
    discount: np.ndarray
    tax: np.ndarray
    returnflag: np.ndarray
    linestatus: np.ndarray
    shipdate: np.ndarray
    commitdate: np.ndarray
    receiptdate: np.ndarray
    shipinstruct: np.ndarray
    shipmode: np.ndarray
    comment_at: np.ndarray
    comment_len: np.ndarray

    def __len__(self) -> int:
        return len(self.handle)

    def take(self, idx) -> "Table":
        return Table(*(getattr(self, f.name)[idx] for f in fields(self)))

    @staticmethod
    def concat(parts: list["Table"]) -> "Table":
        return Table(*(np.concatenate([getattr(p, f.name) for p in parts])
                       for f in fields(Table)))


def order_key(order, slot: int = 0) -> np.ndarray:
    """dbgen's sparse order keys (cl. 4.2.3): of every 32 keys the load uses
    the first 8 (``slot`` 0); slots 1-3 are the gaps kept free for the update
    sets' new orders (RF1)."""
    order = np.asarray(order, dtype=np.int64)
    return (order // 8) * 32 + 8 * slot + order % 8 + 1


def order_of_key(key) -> np.ndarray:
    """The order's place in its slot's sequence: ``order_key``'s inverse."""
    key = np.asarray(key, dtype=np.int64) - 1
    return (key // 32) * 8 + key % 8


def build_table(n: int, seed: int, scale_factor: float | None = None) -> Table:
    """``n`` rows of LINEITEM as dbgen shapes them at ``scale_factor`` (by
    default the one at which the table has ``n`` rows)."""
    rng = np.random.default_rng([int(seed), 0])
    sf = scale_factor if scale_factor is not None else n / SF1_ROWS
    lines = _draw_lines(rng, n)
    return order_lines(rng, lines, np.arange(len(lines)), 0, 1, sf, n)


def _draw_lines(rng, n: int) -> np.ndarray:
    n_orders = n // 4 + 64
    lines = rng.integers(1, 8, n_orders)
    while lines.sum() < n:
        lines = np.concatenate([lines, rng.integers(1, 8, n_orders)])
    return lines


def order_starts(n: int, seed: int) -> np.ndarray:
    """The row at which each order of ``build_table(n, seed)`` starts, and
    ``n`` after the last: its line counts alone, without building the rows."""
    cum = np.concatenate([[0], np.cumsum(_draw_lines(np.random.default_rng([int(seed), 0]), n))])
    cum = np.minimum(cum, n)
    return cum[:int(np.searchsorted(cum, n)) + 1]


def order_lines(rng, lines, order_ids, slot: int, first_handle: int,
                sf: float, n: int | None = None) -> Table:
    """The lines of orders ``order_ids`` (``lines[i]`` of order i, the first
    ``n`` lines in all where ``n`` is given) by dbgen's rules, each order's
    date and every line's values drawn from ``rng`` in this order; handles
    count up from ``first_handle``."""
    parts = max(1, round(sf * 200_000))
    supps = max(4, round(sf * 10_000))
    n = int(lines.sum()) if n is None else n
    order = np.repeat(np.arange(len(lines)), lines)[:n]
    first = np.concatenate([[0], np.cumsum(lines)[:-1]])
    orderdate = rng.integers(START_DAY, END_DAY - 151 + 1, len(lines))[order]
    partkey = rng.integers(1, parts + 1, n)
    quantity = rng.integers(1, 51, n)
    retail = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    shipdate = orderdate + rng.integers(1, 122, n)
    receiptdate = shipdate + rng.integers(1, 31, n)
    returned = rng.integers(0, 2, n) * 2           # "A" or "R"
    return Table(
        handle=np.arange(first_handle, first_handle + n, dtype=np.int64),
        orderkey=order_key(np.asarray(order_ids)[order], slot),
        partkey=partkey,
        suppkey=(partkey + rng.integers(0, 4, n)
                 * (supps // 4 + (partkey - 1) // supps)) % supps + 1,
        linenumber=np.arange(n) - first[order] + 1,
        quantity=quantity * 100,
        extendedprice=quantity * retail,
        discount=rng.integers(0, 11, n),
        tax=rng.integers(0, 9, n),
        returnflag=np.where(receiptdate <= CURRENT_DAY, returned, 1),
        linestatus=(shipdate > CURRENT_DAY).astype(np.int64),
        shipdate=shipdate,
        commitdate=orderdate + rng.integers(30, 91, n),
        receiptdate=receiptdate,
        shipinstruct=rng.integers(0, 4, n),
        shipmode=rng.integers(0, 7, n),
        comment_at=rng.integers(0, (1 << 20) - 64, n),
        comment_len=rng.integers(10, 44, n),
    )


def schema(names=None):
    """The program's column descriptions of ``names`` (all sixteen by default):
    what a pushed-down TableScan lists."""
    from tikv_tpu.copr.datatypes import NOT_NULL_FLAG, ColumnInfo, FieldType, FieldTypeTp

    tp = {"int": FieldTypeTp.LONGLONG, "dec": FieldTypeTp.NEW_DECIMAL,
          "date": FieldTypeTp.DATE, "code": FieldTypeTp.STRING,
          "text": FieldTypeTp.VAR_STRING}
    out = []
    for name, _sql, kind in LINEITEM:
        if names is not None and name not in names:
            continue
        ft = FieldType(tp[kind], flag=NOT_NULL_FLAG)  # every column is NOT NULL
        if kind == "dec":
            ft.flen, ft.decimal = DECIMAL_PREC, DECIMAL_FRAC
        out.append(ColumnInfo(COLUMN_ID[name], ft))
    return out


def _least_width_cells(values: np.ndarray, signed: bool):
    """Little-endian at the least of 1, 2, 4, 8 bytes that holds each value."""
    v = values.astype(np.int64)
    raw = v.astype("<i8").view(np.uint8).reshape(len(v), 8)
    width = np.full(len(v), 8, dtype=np.int64)
    for w in (4, 2, 1):
        lim = 1 << (8 * w - 1) if signed else 1 << (8 * w)
        width[(v < lim) & (v >= (-lim if signed else 0))] = w
    return raw, width


def _decimal_cells(unscaled: np.ndarray) -> np.ndarray:
    """DECIMAL(15,2) cells: precision, scale, then MySQL's binary decimal (4
    leading digits in 2 bytes, 9 digits in 4, 2 decimals in 1; the first
    byte's top bit set for a value that is not negative)."""
    v = unscaled.astype(np.int64)
    if (v < 0).any() or (v >= 10 ** DECIMAL_PREC).any():
        raise ValueError("decimal outside DECIMAL(15,2)'s non-negative range")
    whole, frac = v // 100, v % 100
    out = np.empty((len(v), 9), dtype=np.uint8)
    out[:, 0], out[:, 1] = DECIMAL_PREC, DECIMAL_FRAC
    out[:, 2:4] = (whole // 10 ** 9).astype(">u2").view(np.uint8).reshape(-1, 2)
    out[:, 4:8] = (whole % 10 ** 9).astype(">u4").view(np.uint8).reshape(-1, 4)
    out[:, 8] = frac
    out[:, 2] ^= 0x80
    return out


def encode_values(t: Table) -> list[bytes]:
    """Row values in TiDB's row format 2: ``[128][0][16 u16][0 u16]``, the
    sixteen column ids, sixteen end offsets (u16), then the cells."""
    n = len(t)
    pool = text_pool()
    cells = []   # per column: (bytes matrix, width per row)
    for name, _sql, kind in LINEITEM:
        if kind == "int":
            cells.append(_least_width_cells(getattr(t, name), signed=True))
        elif kind == "dec":
            cells.append((_decimal_cells(getattr(t, name)), np.full(n, 9)))
        elif kind == "date":
            cells.append(_least_width_cells(pack_days(getattr(t, name)), signed=False))
        elif kind == "code":
            words = WORDS[name]
            mat = np.zeros((len(words), max(map(len, words))), dtype=np.uint8)
            for i, w in enumerate(words):
                mat[i, :len(w)] = np.frombuffer(w, dtype=np.uint8)
            code = getattr(t, name)
            cells.append((mat[code], np.array([len(w) for w in words])[code]))
        else:
            idx = t.comment_at[:, None] + np.arange(43)[None, :]
            cells.append((pool[idx], t.comment_len))
    widths = np.stack([w for _m, w in cells], axis=1)
    ends = np.cumsum(widths, axis=1)
    k = len(LINEITEM)
    head = 6 + k + 2 * k
    row_len = head + ends[:, -1]
    row_at = np.concatenate([[0], np.cumsum(row_len)])
    buf = np.zeros(int(row_at[-1]), dtype=np.uint8)
    fixed = np.zeros((n, head), dtype=np.uint8)
    fixed[:, 0] = 128
    fixed[:, 2] = k
    fixed[:, 6:6 + k] = np.arange(1, k + 1)
    fixed[:, 6 + k:] = ends.astype("<u2").view(np.uint8).reshape(n, 2 * k)
    buf[row_at[:-1, None] + np.arange(head)[None, :]] = fixed
    starts = row_at[:-1, None] + head + ends - widths
    for c, (mat, width) in enumerate(cells):
        for w in np.unique(width):
            rows = np.flatnonzero(width == w)
            buf[starts[rows, c][:, None] + np.arange(w)[None, :]] = mat[rows, :w]
    raw = buf.tobytes()
    return [raw[a:b] for a, b in zip(row_at[:-1].tolist(), row_at[1:].tolist())]


def encode_kvs(table_id: int, t: Table) -> list[tuple[bytes, bytes]]:
    """Record keys and row values of ``t`` as the store takes them."""
    from tikv_tpu.copr.table import record_key
    from tikv_tpu.util.codec import encode_i64_batch

    kmat = np.tile(np.frombuffer(record_key(table_id, 0), dtype=np.uint8), (len(t), 1))
    kmat[:, 11:19] = encode_i64_batch(t.handle.astype(np.int64))
    keys = [r.tobytes() for r in kmat]
    if keys and len(keys[0]) != KEY_BYTES:
        raise RuntimeError(f"key shape drifted: {len(keys[0])}-byte keys")
    return list(zip(keys, encode_values(t)))


def stored(t: Table, name: str) -> list:
    """Column ``name`` as a client reads it back from the program's decoder."""
    kind = next(k for n, _s, k in LINEITEM if n == name)
    if kind == "code":
        return [WORDS[name][i] for i in getattr(t, name)]
    if kind == "text":
        pool = text_pool().tobytes()
        return [pool[a:a + n] for a, n in zip(t.comment_at.tolist(), t.comment_len.tolist())]
    if kind == "date":
        return pack_days(getattr(t, name)).tolist()
    return getattr(t, name).tolist()


def selfcheck(table_id: int, n: int = 4096, seed: int = 0) -> None:
    """One block of generated rows is held to the program's own row encoder,
    byte for byte, and goes through the program's decoder, where it must come
    back as the columns it was made from: a drift between these rows and the
    store's decode fails the run here, not the comparison later."""
    from tikv_tpu.copr.rowv2 import encode_row_v2
    from tikv_tpu.copr.table import RowBatchDecoder, decode_record_handles

    t = build_table(n, seed, scale_factor=1.0)
    kvs = encode_kvs(table_id, t)
    sch = schema()
    want = {name: stored(t, name) for name, _s, _k in LINEITEM}
    for i in range(0, n, max(1, n // 64)):
        mine = encode_row_v2(sch, [want[name][i] for name, _s, _k in LINEITEM])
        if mine != kvs[i][1]:
            raise RuntimeError(f"row {i} is not what the program's encoder writes")
    handles = decode_record_handles([k for k, _ in kvs])
    if not np.array_equal(handles, t.handle):
        raise RuntimeError("keys decode to other handles than written")
    cols = RowBatchDecoder(sch).decode(handles, [v for _, v in kvs])
    for c, (name, _s, _k) in zip(cols, LINEITEM):
        got = c.decoded().to_values() if c.is_dict_encoded else c.to_values()
        got = [bytes(g) if isinstance(g, (bytes, bytearray, memoryview)) else int(g)
               for g in got]
        if got != want[name]:
            raise RuntimeError(f"column {name} decodes to other values than written")
