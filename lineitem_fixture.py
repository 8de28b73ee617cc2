"""The lineitem-shaped fixture that ``chip_smoke.py`` and the tests share:
seven NOT NULL columns in one fixed row layout, made from a seed, as real KV
bytes (``build_kvs``) or as the decoded image of the same rows
(``build_cache``), with the Q1- and Q6-shaped plans over it and the CPU
pipeline that every device answer is compared with byte for byte.

The benchmark's full-width TPC-H table is ``benchmark/table.py``; this one is
narrow and cheap on purpose.
"""

import numpy as np

TABLE_ID = 101


def _lineitem():
    from tikv_tpu.copr.datatypes import NOT_NULL_FLAG, ColumnInfo, FieldType

    def nn(ft):
        # TPC-H lineitem columns are all NOT NULL; declaring it lets both
        # pipelines skip null-mask work honestly
        ft.flag |= NOT_NULL_FLAG
        return ft

    return [
        ColumnInfo(1, nn(FieldType.int64()), is_pk_handle=True),
        ColumnInfo(2, nn(FieldType.int64())),  # l_quantity
        ColumnInfo(3, nn(FieldType.decimal_type(2))),  # l_extendedprice
        ColumnInfo(4, nn(FieldType.decimal_type(2))),  # l_discount
        ColumnInfo(5, nn(FieldType.int64())),  # l_shipdate (days)
        ColumnInfo(6, nn(FieldType.varchar())),  # l_returnflag
        ColumnInfo(7, nn(FieldType.varchar())),  # l_linestatus
    ]


def build_arrays(n: int, seed: int = 0) -> dict:
    """The raw column draws — the single source of randomness, shared by the
    KV-bytes fixture and the columnar fixture so both hold the same table
    for a given (n, seed)."""
    rng = np.random.default_rng(seed)
    return {
        "qty": rng.integers(1, 51, n),
        "price": rng.integers(90000, 10500000, n),  # 900.00 .. 105000.00
        "disc": rng.integers(0, 11, n),  # 0.00 .. 0.10
        "ship": rng.integers(8400, 10600, n),
        "rf": rng.integers(0, 3, n),
        "ls": rng.integers(0, 2, n),
    }


def build_kvs(n: int, seed: int = 0):
    """Vectorized KV fixture: rows share one fixed layout, so the whole
    table is a byte matrix filled by batch codecs.  ``[(key, value)]`` in
    ascending handle order, handles ``0..n-1``."""
    from tikv_tpu.copr.table import RowBatchDecoder, encode_row, record_key
    from tikv_tpu.util.codec import encode_i64_batch

    a = build_arrays(n, seed)
    schema = _lineitem()
    flags = np.frombuffer(b"ANR", dtype=np.uint8)
    stats = np.frombuffer(b"FO", dtype=np.uint8)
    non_handle = schema[1:]
    row0 = encode_row(non_handle, [1, 1, 1, 1, b"A", b"F"])
    layout = RowBatchDecoder(schema)._parse_layout(row0)
    mat = np.tile(np.frombuffer(row0, dtype=np.uint8), (n, 1))
    for col_id, arr in ((2, a["qty"]), (3, a["price"]), (4, a["disc"]), (5, a["ship"])):
        _kind, off = layout["cols"][col_id]
        mat[:, off : off + 8] = encode_i64_batch(arr)
    _k, off_rf = layout["cols"][6]
    _k, off_ls = layout["cols"][7]
    mat[:, off_rf] = flags[a["rf"]]
    mat[:, off_ls] = stats[a["ls"]]
    values = [r.tobytes() for r in mat]
    kmat = np.tile(np.frombuffer(record_key(TABLE_ID, 0), dtype=np.uint8), (n, 1))
    kmat[:, 11:19] = encode_i64_batch(np.arange(n, dtype=np.int64))
    keys = [r.tobytes() for r in kmat]
    return list(zip(keys, values))


def build_cache(n: int, block_rows: int, seed: int = 0):
    """The decoded-column image of build_kvs(n, seed) as a filled
    ColumnBlockCache, WITHOUT materializing n Python byte objects.  Layout
    must match RowBatchDecoder exactly (tests/test_lineitem_fixture.py holds
    it to a real decode, column for column): ints/decimals as int64 data,
    varchar as dictionary codes with ONE shared dictionary object across
    blocks (the decoder's per-column dict cache does the same — the device
    group-by fast path keys on identity)."""
    from tikv_tpu.copr.cache import ColumnBlockCache
    from tikv_tpu.copr.datatypes import Column, EvalType

    a = build_arrays(n, seed)
    # sorted unique byte values, as the decoder's np.unique produces them
    dict_rf = np.empty(3, dtype=object)
    dict_rf[:] = [b"A", b"N", b"R"]
    dict_ls = np.empty(2, dtype=object)
    dict_ls[:] = [b"F", b"O"]
    handles = np.arange(n, dtype=np.int64)
    cache = ColumnBlockCache()
    for s in range(0, n, block_rows):
        e = min(s + block_rows, n)
        m = e - s
        nz = [np.zeros(m, dtype=bool) for _ in range(7)]
        cols = [
            Column(EvalType.INT, handles[s:e], nz[0]),
            Column(EvalType.INT, a["qty"][s:e], nz[1]),
            Column(EvalType.DECIMAL, a["price"][s:e], nz[2], 2),
            Column(EvalType.DECIMAL, a["disc"][s:e], nz[3], 2),
            Column(EvalType.INT, a["ship"][s:e], nz[4]),
            Column(EvalType.BYTES, a["rf"][s:e], nz[5], 0, dict_rf),
            Column(EvalType.BYTES, a["ls"][s:e], nz[6], 0, dict_ls),
        ]
        cache.add(cols, m)
    cache.filled = True
    return cache


def q6_dag():
    # sum(l_extendedprice * l_discount) where shipdate in [y, y+365) and
    # discount between 0.02 and 0.04 and quantity < 24
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
    from tikv_tpu.copr.rpn import call, col, const_decimal, const_int

    conds = [
        call("ge", col(4), const_int(9000)),
        call("lt", col(4), const_int(9365)),
        call("ge", col(3), const_decimal(2, 2)),
        call("le", col(3), const_decimal(4, 2)),
        call("lt", col(1), const_int(24)),
    ]
    aggs = [AggDescriptor("sum", call("multiply", col(2), col(3)))]
    return DagRequest(
        executors=[TableScan(TABLE_ID, _lineitem()), Selection(conds), Aggregation([], aggs)]
    )


def q1_dag():
    # group by returnflag, linestatus: sum(qty), sum(price), avg(price),
    # avg(disc), count(*) where shipdate <= cutoff
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
    from tikv_tpu.copr.rpn import call, col, const_int

    conds = [call("le", col(4), const_int(10500))]
    aggs = [
        AggDescriptor("sum", col(1)),
        AggDescriptor("sum", col(2)),
        AggDescriptor("avg", col(2)),
        AggDescriptor("avg", col(3)),
        AggDescriptor("count", None),
    ]
    return DagRequest(
        executors=[
            TableScan(TABLE_ID, _lineitem()),
            Selection(conds),
            Aggregation([col(5), col(6)], aggs),
        ]
    )


def run_cpu(dag, kvs=None, cache=None):
    """The CPU read-pool pipeline (BatchExecutorsRunner) over either real KV
    bytes or the shared block cache."""
    from tikv_tpu.copr.dag import BatchExecutorsRunner
    from tikv_tpu.copr.executors import CachedBlocksExecutor, FixtureScanSource

    leaf = CachedBlocksExecutor(cache, _lineitem()) if cache is not None else None
    src = None if cache is not None else FixtureScanSource(kvs)
    return BatchExecutorsRunner(dag, src, leaf=leaf).handle_request()


def _topn_endpoint(n: int, enable_device: bool):
    """ONE definition of the TopN fixture and plan over a real MVCC region,
    shared by the device endpoint and the CPU one so they can never drift
    apart: ``(endpoint, dag(), request())``."""
    from tikv_tpu.copr.dag import DagRequest, Selection, TableScan, TopN
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.rpn import call, col, const_int
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.engine import CF_WRITE
    from tikv_tpu.storage.kv import LocalEngine
    from tikv_tpu.storage.txn_types import Key, Write, WriteType

    eng = BTreeEngine()
    eng.bulk_load(CF_WRITE, [
        (Key.from_raw(rk).append_ts(20).encoded,
         Write(WriteType.PUT, 10, short_value=v).to_bytes())
        for rk, v in build_kvs(n, seed=7)])
    schema = _lineitem()

    def dag():
        return DagRequest(
            executors=[
                TableScan(TABLE_ID, schema[:5]),
                Selection([call("le", col(4), const_int(10500))]),
                TopN([(col(2), True), (col(1), False)], 100),
            ]
        )

    ep = Endpoint(LocalEngine(eng), enable_device=enable_device)
    return ep, dag, lambda: CoprRequest(103, dag(), [record_range(TABLE_ID)], 100)


def _filter_dag(kind: str, limit: int = 100_000):
    """A pure table scan (``kind="scan"``) or a 3-predicate selection, each
    under a Limit that bounds the response."""
    from tikv_tpu.copr.dag import DagRequest, Limit, Selection, TableScan
    from tikv_tpu.copr.rpn import call, col, const_int

    if kind == "scan":
        return DagRequest(executors=[
            TableScan(TABLE_ID, _lineitem()), Limit(limit),
        ])
    return DagRequest(executors=[
        TableScan(TABLE_ID, _lineitem()),
        Selection([
            call("lt", col(4), const_int(10500)),
            call("gt", col(1), const_int(5)),
            call("ge", col(2), const_int(100000)),
        ]),
        Limit(limit),
    ])
