"""The array decoder of mixed-layout v2 blocks against the per-row walk.

``rowv2.decode_rows_v2`` sends a block of one layout through ``_fast_decode``
(counter path ``uniform``), a mixed block through ``_vector_decode``
(``vector``), and what the arrays cannot judge, or a block of a few rows,
through ``_slow_decode`` (``walk``).  The walk is the reference: every case
here builds a block, decodes it both ways and holds the two to the same
Columns: values, dtypes, null masks, scale, dictionary, row order.
"""

import random

import numpy as np
import pytest

from copr_fixtures import rowv2_rows_decoded as moved

from tikv_tpu.copr import rowv2
from tikv_tpu.copr.datatypes import (
    ColumnInfo,
    FieldType,
    FieldTypeTp,
)
from tikv_tpu.copr.mydecimal import MyDecimal
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import RowBatchDecoder, encode_row


def dec(frac=2, flen=15):
    return FieldType(FieldTypeTp.NEW_DECIMAL, flen=flen, decimal=frac)


def lineitem():
    """LINEITEM as TiDB declares it: BIGINT keys, DECIMAL(15,2), CHAR(1),
    DATE, VARCHAR(44)."""
    date = FieldType(FieldTypeTp.DATE)
    char = FieldType(FieldTypeTp.STRING)
    kinds = [FieldType.int64()] * 4 + [dec()] * 4 + [char, char] + [date] * 3 + [
        char, char, FieldType.varchar()]
    return [ColumnInfo(i + 1, ft) for i, ft in enumerate(kinds)]


def lineitem_rows(n, seed=0):
    rng = random.Random(seed)
    words = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE", b"TAKE BACK RETURN")
    out = []
    for i in range(n):
        qty = rng.randrange(1, 51)
        day = (1992 * 13 + rng.randrange(1, 13)) << 46 | rng.randrange(1, 29) << 41
        out.append([
            rng.randrange(1, 6_000_000), rng.randrange(1, 200_000),
            rng.randrange(1, 10_000), rng.randrange(1, 8),
            qty * 100, qty * rng.randrange(90_000, 200_000),
            rng.randrange(0, 11), rng.randrange(0, 9),
            rng.choice((b"A", b"N", b"R")), rng.choice((b"F", b"O")),
            day, day + (3 << 41), day + (5 << 41),
            rng.choice(words), rng.choice((b"AIR", b"TRUCK", b"REG AIR")),
            bytes(rng.randrange(97, 123) for _ in range(rng.randrange(10, 44))),
        ])
    return out


def wide():
    """One column of every kind the row format stores."""
    return [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.int64(unsigned=True)),
        ColumnInfo(4, FieldType.double()),
        ColumnInfo(5, FieldType.varchar()),
        ColumnInfo(6, dec()),
        ColumnInfo(7, FieldType.enum_type([b"on", b"off", b"auto"])),
        ColumnInfo(8, FieldType.set_type([b"s%d" % k for k in range(64)])),
        ColumnInfo(9, FieldType(FieldTypeTp.DATETIME)),
        ColumnInfo(10, FieldType(FieldTypeTp.DURATION)),
        ColumnInfo(11, FieldType(FieldTypeTp.JSON)),
    ]


def wide_row(rng):
    return [
        rng.choice((0, -1, 127, -128, 1 << 20, -(1 << 40), (1 << 63) - 1, -(1 << 63))),
        rng.choice((0, 255, 1 << 16, 1 << 40, (1 << 63) - 1)),
        rng.choice((0.0, -2.25, 1e300, -1e-300, 3.5)),
        bytes(rng.randrange(256) for _ in range(rng.randrange(0, 20))),
        rng.randrange(-(10**15) + 1, 10**15),
        rng.randrange(0, 4),
        rng.getrandbits(64),
        rng.randrange(0, 1 << 62),
        rng.randrange(-(1 << 50), 1 << 50),
        bytes(rng.randrange(256) for _ in range(rng.randrange(1, 9))),
    ]


def encode(schema, rows):
    cols = [c for c in schema if not c.is_pk_handle]
    return [encode_row_v2(cols, r) for r in rows]


def same_columns(got, want):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.eval_type == w.eval_type, i
        assert g.frac == w.frac, i
        assert g.data.dtype == w.data.dtype, i
        assert np.array_equal(g.nulls, w.nulls), i
        assert g.nulls.dtype == w.nulls.dtype, i
        if g.data.dtype == object:
            assert [type(v) for v in g.data] == [type(v) for v in w.data], i
            assert list(g.data) == list(w.data), i
        else:
            # bit for bit: -0.0 and NaN payloads included
            assert g.data.tobytes() == w.data.tobytes(), i
        assert (g.dictionary is None) == (w.dictionary is None), i
        if g.dictionary is not None:
            assert list(g.dictionary) == list(w.dictionary), i


# -- the blocks ---------------------------------------------------------------

def block_lineitem():
    schema = lineitem()
    return schema, encode(schema, lineitem_rows(600))


def block_nulls():
    rng = random.Random(1)
    schema = wide()
    rows = []
    for _ in range(400):
        r = wide_row(rng)
        for j in rng.sample(range(len(r)), rng.randrange(0, 5)):
            r[j] = None
        rows.append(r)
    return schema, encode(schema, rows)


def block_absent_ids():
    """Rows written before columns 5, 6 and 8 existed; 6 and 8 have defaults."""
    rng = random.Random(2)
    schema = wide()
    schema[5] = ColumnInfo(6, dec(), default_value=12345)
    schema[7] = ColumnInfo(8, schema[7].ftype, default_value=5)
    schema[4] = ColumnInfo(5, FieldType.varchar())  # no default: NULL
    old = [c for c in schema if c.col_id not in (1, 5, 6, 8)]
    new = [c for c in schema if not c.is_pk_handle]
    enc = []
    for i in range(300):
        r = wide_row(rng)
        if i % 3:
            enc.append(encode_row_v2(new, r))
        else:
            enc.append(encode_row_v2(old, [v for c, v in zip(new, r) if c in old]))
    return schema, enc


def block_bytes_default():
    """A BYTES default fills an object column."""
    schema = [ColumnInfo(2, FieldType.int64()),
              ColumnInfo(3, FieldType.varchar(), default_value=b"dflt")]
    enc = [encode_row_v2(schema[:1 + i % 2], [i * 300, b"x" * (i % 5)][:1 + i % 2])
           for i in range(64)]
    return schema, enc


def block_big_ids():
    rng = random.Random(3)
    schema = [ColumnInfo(2, FieldType.int64()), ColumnInfo(300, FieldType.varchar()),
              ColumnInfo(70000, dec()), ColumnInfo(301, FieldType.int64())]
    rows = [[rng.randrange(-(1 << 40), 1 << 40), b"w" * rng.randrange(0, 9),
             rng.randrange(-10**9, 10**9), None if i % 7 == 0 else i]
            for i in range(200)]
    enc = encode(schema, rows)
    assert all(e[1] == 1 for e in enc)  # the big flag
    # small-form rows of the low id alone in the same block
    enc[::5] = [encode_row_v2(schema[:1], [i]) for i in range(len(enc[::5]))]
    return schema, enc


def block_unsigned_top_bit():
    schema = [ColumnInfo(2, FieldType.int64(unsigned=True)),
              ColumnInfo(3, FieldType.set_type([b"s%d" % k for k in range(64)])),
              ColumnInfo(4, FieldType.int64())]
    edge = [(1 << 63) - 1, 1 << 63, (1 << 63) + 1, (1 << 64) - 1, 0, 255, 256, 1 << 32]
    rows = [[edge[i % len(edge)], edge[(i + 3) % len(edge)], i - 32] for i in range(64)]
    return schema, encode(schema, rows)


def block_decimal_edges():
    schema = [ColumnInfo(2, dec()), ColumnInfo(3, FieldType.varchar())]
    top = 10**15 - 1
    edge = [0, 1, -1, 99, -99, 100, -100, top, -top, 10**9, -(10**9), 10**11 - 1,
            123456789012345, -123456789012345]
    rows = [[edge[i % len(edge)], b"p" * (i % 4)] for i in range(70)]
    return schema, encode(schema, rows)


HALVES = ["1.2350", "-1.2350", "1.2349", "-1.2349", "1.2351", "0.0050", "-0.0050",
          "0.0049", "-0.0049", "999999999999.9950", "-999999999999.9950", "0.9950",
          "7.0000", "-0.0001"]


def block_decimal_rounding():
    """Cells written at another (prec, frac) than the column's: DECIMAL(20,4)
    and DECIMAL(12,0) cells under a DECIMAL(15,2) column, the halves on both
    sides of zero among them."""
    read = [ColumnInfo(2, dec()), ColumnInfo(3, FieldType.int64())]
    wrote4 = [ColumnInfo(2, dec(frac=4, flen=16)), read[1]]
    wrote0 = [ColumnInfo(2, dec(frac=0, flen=12)), read[1]]
    enc = []
    for i in range(84):
        if i % 3 == 2:
            enc.append(encode_row_v2(wrote0, [MyDecimal.from_str(str(i * 1234567 - 40)), i]))
        else:
            enc.append(encode_row_v2(
                wrote4, [MyDecimal.from_str(HALVES[i % len(HALVES)]), i * 1000]))
    return read, enc


def block_decimal_declared_wide():
    """DECIMAL(65,2) cells (a schema with no flen) holding everyday values:
    what bounds the arrays is the value, not the precision declared."""
    schema = [ColumnInfo(2, FieldType.decimal_type(2)), ColumnInfo(3, FieldType.varchar())]
    rows = [[(-1) ** i * i * 12345678901, b"p" * (i % 4)] for i in range(70)]
    rows[9][0] = 10**16 - 1  # the widest the arrays take at two places
    return schema, encode(schema, rows)


def block_one_shape_a_row():
    """Every row of another header shape: nothing for the arrays to group."""
    schema = [ColumnInfo(i + 2, FieldType.int64()) for i in range(40)]
    enc = [encode_row_v2(schema[:i + 1], list(range(i + 1))) for i in range(40)]
    return schema, enc


def block_with_strays():
    """A mixed block in which a few rows are of shapes of their own."""
    schema, enc = block_lineitem()
    enc[17] = encode_row_v2(schema[:3], [1, 2, 3])
    enc[311] = encode_row_v2(schema[:5], [1, None, 3, None, 77])
    return schema, enc


BLOCKS = {
    "lineitem_mixed_widths": block_lineitem,
    "nulls_in_random_columns": block_nulls,
    "absent_ids_with_and_without_default": block_absent_ids,
    "bytes_default": block_bytes_default,
    "big_flag_rows": block_big_ids,
    "unsigned_at_and_over_2_63": block_unsigned_top_bit,
    "decimal_negative_zero_maximal": block_decimal_edges,
    "decimal_rounding_and_halves": block_decimal_rounding,
    "decimal_declared_wide_values_small": block_decimal_declared_wide,
    "one_shape_a_row": block_one_shape_a_row,
    "strays_among_a_mixed_block": block_with_strays,
}


@pytest.mark.parametrize("name", sorted(BLOCKS))
def test_mixed_block_equals_the_walk(name):
    schema, enc = BLOCKS[name]()
    want = rowv2._slow_decode(schema, enc, len(enc))
    got, rows = moved(lambda: rowv2.decode_rows_v2(schema, enc))
    same_columns(got, want)
    assert rows["uniform"] == 0
    assert rows["vector"] + rows["walk"] == len(enc)
    if name == "one_shape_a_row":
        assert rows["vector"] == 0
    elif name == "strays_among_a_mixed_block":
        assert rows["walk"] == 2
    else:
        assert rows["walk"] == 0
    # any slice of the block, the small ones (which walk) included
    for lo, hi in ((0, 1), (3, 10), (5, 5 + rowv2._VECTOR_MIN_ROWS), (len(enc) // 2, len(enc))):
        same_columns(rowv2.decode_rows_v2(schema, enc[lo:hi]),
                     rowv2._slow_decode(schema, enc[lo:hi], hi - lo))


def test_rounding_case_reads_what_mysql_rounds_to():
    """The halves go away from zero: the walk agrees by the test above, and
    this holds the two of them to ``decimal``'s ROUND_HALF_UP."""
    import decimal

    schema, enc = block_decimal_rounding()
    col = rowv2.decode_rows_v2(schema, enc)[0]
    cent = decimal.Decimal("0.01")
    for i in range(84):
        if i % 3 == 2:
            assert col.data[i] == (i * 1234567 - 40) * 100
        else:
            d = decimal.Decimal(HALVES[i % len(HALVES)])
            assert col.data[i] == int(d.quantize(cent, decimal.ROUND_HALF_UP) * 100), i
    assert col.data[0] == 124 and col.data[1] == -124 and col.data[3] == -123


@pytest.mark.parametrize("n", [1, 2, 23, 24, 25])
def test_small_blocks(n):
    schema = lineitem()
    enc = encode(schema, lineitem_rows(n, seed=n))
    want = rowv2._slow_decode(schema, enc, n)
    got, rows = moved(lambda: rowv2.decode_rows_v2(schema, enc))
    same_columns(got, want)
    if n == 1:
        assert rows == {"uniform": 1, "vector": 0, "walk": 0}
    elif n < rowv2._VECTOR_MIN_ROWS:
        assert rows == {"uniform": 0, "vector": 0, "walk": n}
    else:
        assert rows == {"uniform": 0, "vector": n, "walk": 0}


def test_one_layout_counts_as_uniform():
    schema = wide()
    rng = random.Random(5)
    base = wide_row(rng)
    rows = []
    for i in range(100):
        r = list(base)
        r[0], r[4], r[3] = -(1 << 40) - i, 10**14 + i * 977, b"k%02d" % i
        rows.append(r)
    enc = encode(schema, rows)
    want = rowv2._slow_decode(schema, enc, len(enc))
    got, moved_rows = moved(lambda: rowv2.decode_rows_v2(schema, enc))
    same_columns(got, want)
    assert moved_rows == {"uniform": 100, "vector": 0, "walk": 0}


def wide_decimal_block():
    info = [ColumnInfo(2, dec(frac=2, flen=30)), ColumnInfo(3, FieldType.varchar())]
    enc = [encode_row_v2(info, [i * 31, b"c" * (i % 6)]) for i in range(40)]
    enc[23] = encode_row_v2(info, [MyDecimal.from_str("12345678901234567890.12"), b"c"])
    return info, enc


def truncated_block():
    schema, enc = block_lineitem()
    enc = enc[:60]
    enc[41] = enc[41][:-3]
    return schema, enc


def truncated_header_block():
    schema, enc = block_lineitem()
    enc = enc[:60]
    enc[9] = enc[9][:20]
    return schema, enc


def short_decimal_cell_block():
    """A decimal cell whose bytes end before its (prec, frac) says."""
    info = [ColumnInfo(2, dec()), ColumnInfo(3, FieldType.varchar())]
    enc = [encode_row_v2(info, [i * 31, b"c" * (i % 6)]) for i in range(40)]
    as_bytes = [ColumnInfo(2, FieldType.varchar()), info[1]]
    enc[7] = encode_row_v2(as_bytes, [bytes([15, 2, 0x80, 0]), b"c"])
    return info, enc


@pytest.mark.parametrize("block,match", [
    (wide_decimal_block, "columnar"),
    (truncated_block, "truncated v2 row"),
    (truncated_header_block, "truncated v2 row"),
    (short_decimal_cell_block, "decimal bin truncated"),
])
def test_raises_what_the_walk_raises(block, match):
    schema, enc = block()
    with pytest.raises(ValueError, match=match) as want:
        rowv2._slow_decode(schema, enc, len(enc))
    with pytest.raises(ValueError, match=match) as got:
        rowv2.decode_rows_v2(schema, enc)
    assert str(got.value) == str(want.value)


def test_wide_decimal_in_a_uniform_block_raises_too():
    info = [ColumnInfo(2, dec(frac=2, flen=30))]
    enc = [encode_row_v2(info, [MyDecimal.from_str("12345678901234567890.12")])] * 20
    with pytest.raises(ValueError, match="columnar"):
        rowv2.decode_rows_v2(info, enc)


def test_nineteen_digits_that_fit_int64_still_decode():
    """The bound is int64, not 18 digits: the walk decodes these, so the
    arrays hand them to it."""
    info = [ColumnInfo(2, dec(frac=2, flen=19)), ColumnInfo(3, FieldType.varchar())]
    enc = [encode_row_v2(info, [9 * 10**18 + i, b"c" * (i % 6)]) for i in range(40)]
    want = rowv2._slow_decode(info, enc, len(enc))
    got, rows = moved(lambda: rowv2.decode_rows_v2(info, enc))
    same_columns(got, want)
    assert got[0].data[3] == 9 * 10**18 + 3
    assert rows["walk"] == 40


def test_mixed_v1_v2_block_through_the_batch_decoder():
    schema = wide()
    rng = random.Random(7)
    cols = [c for c in schema if not c.is_pk_handle]
    v1_cols = [c for c in cols if c.col_id in (2, 4, 5)]
    rows, want2, want5 = [], [], []
    for i in range(90):
        r = wide_row(rng)
        if i % 4 == 0:
            rows.append(encode_row(v1_cols, [r[0], r[2], r[3]]))
        else:
            rows.append(encode_row_v2(cols, r))
        want2.append(r[0])
        want5.append(r[3])
    dec_ = RowBatchDecoder(schema)
    got = dec_.decode(np.arange(90), rows)
    assert dec_.path == "mixed"
    assert got[0].to_values() == list(range(90))
    assert got[1].to_values() == want2
    assert got[4].to_values() == want5
    v2 = [r for i, r in enumerate(rows) if i % 4]
    same_columns([c.take(np.array([i for i in range(90) if i % 4]))
                  for c in got][1:],
                 rowv2._slow_decode(schema, v2, len(v2))[1:])


def test_no_row_object_per_row(monkeypatch):
    """20,000 rows of mixed layout construct a handful of ``RowSliceV2``, not
    one a row."""
    schema = lineitem()
    enc = encode(schema, lineitem_rows(2000, seed=9)) * 10
    made = []
    init = rowv2.RowSliceV2.__init__

    def counting(self, raw):
        made.append(1)
        init(self, raw)

    monkeypatch.setattr(rowv2.RowSliceV2, "__init__", counting)
    dec_ = RowBatchDecoder(schema)
    cols = dec_.decode(np.arange(len(enc)), enc)
    assert dec_.path == "vector"
    assert len(cols[0]) == 20000
    assert len(made) <= 4
