"""Row format v2 codec (codec/row/v2/row_slice.rs, compat_v1.rs parity)."""

import numpy as np
import pytest

from tikv_tpu.copr.datatypes import (
    ColumnInfo,
    EvalType,
    FieldType,
    enum_names,
    set_names,
)
from tikv_tpu.copr.mydecimal import MyDecimal
from tikv_tpu.copr.rowv2 import (
    CODEC_VERSION,
    RowSliceV2,
    decode_rows_v2,
    encode_row_v2,
    is_v2_row,
)
from tikv_tpu.copr.table import RowBatchDecoder


def _schema():
    return [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.double()),
        ColumnInfo(4, FieldType.varchar()),
        ColumnInfo(5, FieldType.decimal_type(2)),
        ColumnInfo(6, FieldType.enum_type([b"on", b"off"])),
    ]


def test_header_layout():
    raw = encode_row_v2(_schema()[1:], [7, 1.5, b"xy", 1234, 2])
    assert raw[0] == CODEC_VERSION
    assert raw[1] == 0  # small form
    sl = RowSliceV2(raw)
    assert sl.non_null_ids == [2, 3, 4, 5, 6]
    assert sl.null_ids == []
    assert sl.offsets == sorted(sl.offsets)


def test_roundtrip_with_nulls_and_defaults():
    schema = _schema()
    rows = [
        encode_row_v2(schema[1:], [7, 1.5, b"xy", 1234, 2]),
        encode_row_v2(schema[1:], [None, -2.25, b"", None, 1]),
        # column 4 and 6 absent entirely (schema evolution)
        encode_row_v2([schema[1], schema[2]], [-1, 0.0]),
    ]
    cols = decode_rows_v2(schema, rows)
    assert cols[1].to_values() == [7, None, -1]
    assert cols[2].to_values() == [1.5, -2.25, 0.0]
    assert cols[3].to_values() == [b"xy", b"", None]
    assert cols[4].to_values() == [1234, None, None]
    assert enum_names(cols[5]).to_values() == [b"off", b"on", None]


def test_fast_path_identical_layout():
    schema = _schema()[:3]
    rows = [encode_row_v2(schema[1:], [i * 1000, i * 0.5]) for i in range(100)]
    cols = decode_rows_v2(schema, rows)
    assert cols[1].to_values() == [i * 1000 for i in range(100)]
    assert cols[2].to_values() == [i * 0.5 for i in range(100)]


def test_signed_widths():
    schema = [ColumnInfo(2, FieldType.int64())]
    for v in (0, -1, 127, -128, 128, -32768, 1 << 30, -(1 << 40), (1 << 62)):
        raw = encode_row_v2(schema, [v])
        assert decode_rows_v2(schema, [raw])[0].to_values() == [v]


def test_minimal_width_encoding():
    schema = [ColumnInfo(2, FieldType.int64())]
    small = encode_row_v2(schema, [3])
    large = encode_row_v2(schema, [1 << 40])
    assert len(small) < len(large)
    sl = RowSliceV2(small)
    assert sl.get(2) == b"\x03"


def test_big_form_column_ids():
    schema = [ColumnInfo(300, FieldType.int64()), ColumnInfo(301, FieldType.varchar())]
    raw = encode_row_v2(schema, [5, b"wide"])
    assert raw[1] == 1  # big flag
    sl = RowSliceV2(raw)
    assert sl.non_null_ids == [300, 301]
    cols = decode_rows_v2(schema, [raw])
    assert cols[0].to_values() == [5]
    assert cols[1].to_values() == [b"wide"]


def test_decimal_cell_is_wide_format():
    info = ColumnInfo(2, FieldType.decimal_type(2))
    raw = encode_row_v2([info], [-12345])  # scaled: -123.45
    sl = RowSliceV2(raw)
    cell = sl.get(2)
    prec, frac = cell[0], cell[1]
    d, _ = MyDecimal.decode_bin(cell[2:], prec, frac)
    assert d.to_string() == "-123.45"
    cols = decode_rows_v2([info], [raw])
    assert cols[0].to_values() == [-12345]
    assert cols[0].frac == 2


def test_set_bit63_roundtrip():
    info = ColumnInfo(2, FieldType.set_type([b"x%d" % k for k in range(64)]))
    raw = encode_row_v2([info], [1 << 63])
    cols = decode_rows_v2([info], [raw])
    assert set_names(cols[0]).to_values() == [b"x63"]


def test_row_batch_decoder_dispatches_v2():
    schema = _schema()
    dec = RowBatchDecoder(schema)
    rows = [encode_row_v2(schema[1:], [i, 0.5, b"a", 100, 1]) for i in range(4)]
    cols = dec.decode(np.arange(4), rows)
    assert cols[0].to_values() == [0, 1, 2, 3]  # handle column
    assert cols[1].to_values() == [0, 1, 2, 3]
    assert enum_names(cols[5]).to_values() == [b"on"] * 4


def test_mixed_v1_v2_block():
    from tikv_tpu.copr.table import encode_row

    schema = _schema()
    dec = RowBatchDecoder(schema)
    v1 = encode_row(schema[1:], [10, 1.0, b"v1", 500, 1])
    v2 = encode_row_v2(schema[1:], [20, 2.0, b"v2", 600, 2])
    assert not is_v2_row(v1) and is_v2_row(v2)
    cols = dec.decode(np.array([1, 2, 3]), [v1, v2, v1])
    assert cols[1].to_values() == [10, 20, 10]
    assert cols[3].to_values() == [b"v1", b"v2", b"v1"]
    assert cols[4].to_values() == [500, 600, 500]
    assert enum_names(cols[5]).to_values() == [b"on", b"off", b"on"]


def test_value_section_over_64k_uses_big():
    info = [ColumnInfo(2, FieldType.varchar())]
    raw = encode_row_v2(info, [b"z" * 70000])
    assert raw[1] == 1
    cols = decode_rows_v2(info, [raw])
    assert cols[0].to_values() == [b"z" * 70000]


def test_wide_decimal_cell_roundtrip_via_wide_api():
    from tikv_tpu.copr.rowv2 import decode_cell_wide

    info = ColumnInfo(2, FieldType.decimal_type(2))
    info.ftype.flen = 30
    wide = MyDecimal.from_str("12345678901234567890.12")
    raw = encode_row_v2([info], [wide])
    cell = RowSliceV2(raw).get(2)
    assert decode_cell_wide(cell) == wide
    # the columnar bridge rejects it with a descriptive error
    with pytest.raises(ValueError, match="columnar"):
        decode_rows_v2([info], [raw])


def test_encode_bin_clamps_when_widening_overflows():
    from tikv_tpu.copr.mydecimal import MAX_DIGITS

    d = MyDecimal.from_str("9" * 80)
    raw = d.encode_bin(65, 2)  # widening to frac=2 would need 82 digits
    back, _ = MyDecimal.decode_bin(raw, 65, 2)
    assert back.to_string() == "9" * 63 + "." + "99"


# ---------------------------------------------------------------------------
# Mixed-layout batch decode (the array decoder since PR 33: more of it in
# test_rowv2_vector_decode.py)
# ---------------------------------------------------------------------------


def _col_values(cols, schema):
    out = []
    n = len(cols[0])
    for r in range(n):
        row = []
        for ci, info in enumerate(schema):
            c = cols[ci]
            if c.nulls[r]:
                row.append(None)
            elif c.is_dict_encoded:
                row.append(c.dictionary[c.data[r]])
            else:
                row.append(c.data[r])
        out.append(row)
    return out


@pytest.mark.parametrize("times,path", [(1, "walk"), (8, "vector")])
def test_grouped_decode_mixed_layouts_matches_per_row(times, path):
    """Rows with different layouts (NULL patterns, value widths, varchar
    lengths) must decode identically to the per-row walk, in row order: a
    handful of layouts, as a block of a few rows (which walks) and as one
    large enough for the arrays."""
    from tikv_tpu.copr.rowv2 import decode_block

    schema = _schema()
    rows = [
        [7, 1.5, b"xy", 1234, 2],
        [1 << 40, 2.5, b"longer-string", 5678, 1],  # wider int, longer bytes
        [None, 3.5, b"xy", 91, 2],                  # NULL int
        [7, 1.5, b"xy", 1234, 2],                   # same layout as row 0
        [3, None, None, None, 1],                   # mostly NULL
        [1 << 40, 2.5, b"longer-string", 5678, 1],  # same layout as row 1
    ] * times
    encoded = [encode_row_v2(schema[1:], r) for r in rows]
    cols, took = decode_block(schema, encoded)
    assert took == path
    per_row = [decode_rows_v2(schema, [e]) for e in encoded]
    for r, cols1 in enumerate(per_row):
        got = _col_values(cols, schema)[r]
        want = _col_values(cols1, schema)[0]
        assert got[1:] == want[1:], f"row {r}"


def test_grouped_decode_layout_explosion_falls_back():
    """One distinct layout per row must still decode correctly: the rows
    share one header shape (two columns, none NULL), so the arrays take all
    forty whatever their offsets, with no limit on the layouts."""
    schema = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.varchar()),
        ColumnInfo(3, FieldType.int64()),
    ]
    rows = [[b"x" * (i + 1), i] for i in range(40)]
    encoded = [encode_row_v2(schema[1:], r) for r in rows]
    assert len({(len(e), e[:12]) for e in encoded}) == 40
    cols = decode_rows_v2(schema, encoded)
    vals = _col_values(cols, schema)
    for i in range(40):
        assert vals[i][1] == b"x" * (i + 1)
        assert vals[i][2] == i
    from tikv_tpu.copr import rowv2

    assert rowv2.decode_block(schema, encoded)[1] == "vector"
    walked = _col_values(rowv2._slow_decode(schema, encoded, 40), schema)
    assert [v[1:] for v in vals] == [v[1:] for v in walked]
