"""Coprocessor fixtures (reference: components/test_coprocessor ProductTable).

A "product" table: id (pk handle), name (varchar), count (int), price
(decimal(2)).  Helpers build it either as raw fixture KVs (no MVCC) or as
committed MVCC data inside a BTreeEngine.
"""

import numpy as np

from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.storage.btree_engine import BTreeEngine

from fixtures import put_committed

TABLE_ID = 42

PRODUCT_COLUMNS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),
    ColumnInfo(3, FieldType.int64()),
    ColumnInfo(4, FieldType.decimal_type(2)),
]

# (id, name, count, price_scaled_by_100)
PRODUCT_ROWS = [
    (1, b"apple", 10, 150),
    (2, b"banana", 20, 75),
    (3, b"cherry", 30, 1250),
    (4, None, 5, 200),
    (5, b"apple", 15, 150),
    (6, b"banana", 8, None),
]


def product_kvs(rows=PRODUCT_ROWS, table_id=TABLE_ID):
    non_handle = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
    out = []
    for rid, name, count, price in rows:
        key = record_key(table_id, rid)
        val = encode_row(non_handle, [name, count, price])
        out.append((key, val))
    return out


def product_engine(rows=PRODUCT_ROWS, table_id=TABLE_ID, commit_ts=100):
    eng = BTreeEngine()
    for i, (key, val) in enumerate(product_kvs(rows, table_id)):
        put_committed(eng, key, val, commit_ts - 10, commit_ts)
    return eng


def numeric_table_kvs(n, table_id=TABLE_ID, seed=0):
    """Large all-numeric table for perf-shaped tests: id, a int, b int, c decimal(2)."""
    rng = np.random.default_rng(seed)
    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.int64()),
        ColumnInfo(4, FieldType.decimal_type(2)),
    ]
    a = rng.integers(0, 1000, n)
    b = rng.integers(0, 100, n)
    c = rng.integers(0, 100000, n)
    non_handle = cols[1:]
    kvs = []
    for i in range(n):
        kvs.append((record_key(table_id, i), encode_row(non_handle, [int(a[i]), int(b[i]), int(c[i])])))
    return cols, kvs, (a, b, c)


def rowv2_rows_decoded(fn):
    """``fn()`` and the rows it moved
    ``tikv_coprocessor_rowv2_decode_rows_total`` by, per path."""
    from tikv_tpu.util.metrics import REGISTRY

    c = REGISTRY.counter("tikv_coprocessor_rowv2_decode_rows_total")
    before = {p: c.get(path=p) for p in ("uniform", "vector", "walk")}
    out = fn()
    return out, {p: c.get(path=p) - before[p] for p in before}
