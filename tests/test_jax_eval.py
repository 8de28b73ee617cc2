"""Differential tests: JAX device path vs CPU oracle path.

The contract: for every eligible DAG, the device path's encoded
SelectResponse must equal the CPU pipeline's bytes exactly (int/decimal
pipelines; REAL aggregates are float-rounding-exempt).
"""

import numpy as np
import pytest

from tikv_tpu.copr import jax_eval
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import (
    Aggregation,
    BatchExecutorsRunner,
    DagRequest,
    Limit,
    Selection,
    TableScan,
    TopN,
)
from tikv_tpu.copr.executors import FixtureScanSource
from tikv_tpu.copr.jax_eval import JaxDagEvaluator, supports
from tikv_tpu.copr.rpn import call, col, const_bytes, const_decimal, const_int

from copr_fixtures import (
    PRODUCT_COLUMNS,
    TABLE_ID,
    numeric_table_kvs,
    product_kvs,
)


def run_both(executors, kvs, block_rows=256, output_offsets=None):
    dag = DagRequest(executors=executors, output_offsets=output_offsets)
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=block_rows)
    dev = ev.run(FixtureScanSource(kvs))
    return cpu, dev


NUMERIC_COLS, NUMERIC_KVS, (A, B, C) = numeric_table_kvs(5000)


def test_supports_routing():
    assert supports(DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS)]))
    assert supports(
        DagRequest(
            executors=[
                TableScan(TABLE_ID, NUMERIC_COLS),
                Selection([call("lt", col(1), const_int(10))]),
                Aggregation(group_by=[], agg_funcs=[AggDescriptor("count", None)]),
            ]
        )
    )
    # raw TopN over numeric schemas IS device-routable (running top-K merge)
    assert supports(
        DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS), TopN([(col(1), False)], 5)])
    )
    # …but not with bytes payload columns or oversized K
    assert not supports(
        DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS), TopN([(col(1), False)], 100000)])
    )
    # bytes PAYLOAD columns now ride as dictionary codes (round 5) — but a
    # bytes sort KEY still routes to CPU
    assert supports(
        DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), TopN([(col(0), False)], 5)])
    )
    assert not supports(
        DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), TopN([(col(1), False)], 5)])
    )
    # bytes predicate stays on CPU
    assert not supports(
        DagRequest(
            executors=[
                TableScan(TABLE_ID, PRODUCT_COLUMNS),
                Selection([call("eq", col(1), const_bytes(b"apple"))]),
            ]
        )
    )
    # bytes group-by IS eligible (host dictionary encoding)
    assert supports(
        DagRequest(
            executors=[
                TableScan(TABLE_ID, PRODUCT_COLUMNS),
                Aggregation(group_by=[col(1)], agg_funcs=[AggDescriptor("count", None)]),
            ]
        )
    )


def test_scan_only_identical():
    cpu, dev = run_both([TableScan(TABLE_ID, NUMERIC_COLS)], NUMERIC_KVS)
    assert cpu.encode() == dev.encode()


def test_selection_identical():
    cond = call(
        "and",
        call("lt", col(1), const_int(500)),
        call("gt", col(2), const_int(20)),
    )
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([cond])], NUMERIC_KVS
    )
    assert cpu.encode() == dev.encode()
    assert len(cpu.iter_rows()) > 0


def test_selection_three_predicates_identical():
    # lt/gt/eq conjunction
    conds = [
        call("lt", col(1), const_int(800)),
        call("gt", col(2), const_int(10)),
        call("ne", col(3), const_decimal(0, 2)),
    ]
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection(conds)], NUMERIC_KVS
    )
    assert cpu.encode() == dev.encode()


def test_selection_with_limit_identical():
    cond = call("lt", col(1), const_int(500))
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([cond]), Limit(37)], NUMERIC_KVS
    )
    assert cpu.encode() == dev.encode()
    assert len(cpu.iter_rows()) == 37


def test_simple_agg_identical():
    # Q6 shape: filtered sum/count/avg over decimal
    aggs = [
        AggDescriptor("count", None),
        AggDescriptor("sum", col(3)),
        AggDescriptor("avg", col(3)),
        AggDescriptor("min", col(1)),
        AggDescriptor("max", col(3)),
    ]
    cond = call("lt", col(1), const_int(500))
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([cond]), Aggregation([], aggs)],
        NUMERIC_KVS,
    )
    assert cpu.encode() == dev.encode()


def test_simple_agg_empty_result_identical():
    aggs = [AggDescriptor("count", None), AggDescriptor("sum", col(3)), AggDescriptor("min", col(1))]
    cond = call("lt", col(1), const_int(-1))  # nothing passes
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([cond]), Aggregation([], aggs)],
        NUMERIC_KVS,
    )
    assert cpu.encode() == dev.encode()


def test_decimal_arith_agg_identical():
    # sum(c * c) — decimal multiply, frac adds
    aggs = [AggDescriptor("sum", call("multiply", col(3), col(3)))]
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Aggregation([], aggs)], NUMERIC_KVS
    )
    assert cpu.encode() == dev.encode()


def test_hash_agg_int_key_identical():
    aggs = [AggDescriptor("count", None), AggDescriptor("sum", col(3))]
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Aggregation([col(2)], aggs)], NUMERIC_KVS
    )
    assert cpu.encode() == dev.encode()


def test_hash_agg_group_capacity_growth():
    # group key with 1000 distinct values over small capacity start
    aggs = [AggDescriptor("count", None)]
    dag_execs = [TableScan(TABLE_ID, NUMERIC_COLS), Aggregation([col(1)], aggs)]
    dag = DagRequest(executors=dag_execs)
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(NUMERIC_KVS)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=128)
    jax_eval._GROUP_CAPACITY_START = 16  # force growth path
    try:
        ev._capacity = 16
        dev = ev.run(FixtureScanSource(NUMERIC_KVS))
    finally:
        jax_eval._GROUP_CAPACITY_START = 1024
    assert cpu.encode() == dev.encode()


def test_hash_agg_bytes_key_identical():
    # Q1 shape: group by varchar, sum decimals
    kvs = product_kvs()
    aggs = [AggDescriptor("count", None), AggDescriptor("sum", col(2)), AggDescriptor("avg", col(3))]
    cpu, dev = run_both(
        [TableScan(TABLE_ID, PRODUCT_COLUMNS), Aggregation([col(1)], aggs)], kvs, block_rows=4
    )
    assert cpu.encode() == dev.encode()


def test_hash_agg_topn_identical():
    aggs = [AggDescriptor("sum", col(3))]
    cpu, dev = run_both(
        [
            TableScan(TABLE_ID, NUMERIC_COLS),
            Aggregation([col(2)], aggs),
            TopN([(col(0), True)], 10),
        ],
        NUMERIC_KVS,
    )
    assert cpu.encode() == dev.encode()
    assert len(cpu.iter_rows()) == 10


def test_output_offsets_identical():
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS)], NUMERIC_KVS, output_offsets=[3, 0]
    )
    assert cpu.encode() == dev.encode()


def test_real_agg_close():
    cols, kvs, _ = numeric_table_kvs(500)
    # cast-free real column doesn't exist in numeric fixture; divide produces real
    aggs = [AggDescriptor("sum", call("divide_real", col(2), const_int(7)))]
    dag = DagRequest(executors=[TableScan(TABLE_ID, cols), Aggregation([], aggs)])
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
    dev = JaxDagEvaluator(dag, block_rows=64).run(FixtureScanSource(kvs))
    (c,) = cpu.iter_rows()
    (d,) = dev.iter_rows()
    assert c[0] == pytest.approx(d[0], rel=1e-12)


def test_selection_then_group_by_identical():
    """Groups existing only in filtered-out rows must not be emitted."""
    aggs = [AggDescriptor("count", None), AggDescriptor("sum", col(3))]
    cond = call("lt", col(1), const_int(50))  # most groups of col(2) survive partially
    cpu, dev = run_both(
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([cond]), Aggregation([col(2)], aggs)],
        NUMERIC_KVS,
    )
    assert cpu.encode() == dev.encode()
    assert 0 < len(cpu.iter_rows()) < 100


def test_supports_does_not_leak_valueerror():
    assert not supports(
        DagRequest(
            executors=[
                TableScan(TABLE_ID, NUMERIC_COLS),
                Selection([call("no_such_fn", col(1))]),
            ]
        )
    )
    assert not supports(
        DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS), Selection([call("lt", col(1))])])
    )


def test_warm_cache_paths_identical():
    """All three warm-cache modes (simple, stable-dict coded, general gids)
    must match the CPU path byte-for-byte, and repeated cached runs agree."""
    from tikv_tpu.copr.cache import ColumnBlockCache

    cases = [
        # simple agg (no groups)
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([call("lt", col(1), const_int(500))]),
         Aggregation([], [AggDescriptor("count", None), AggDescriptor("sum", col(3))])],
        # general gids path (int group key is not dict-encoded)
        [TableScan(TABLE_ID, NUMERIC_COLS), Selection([call("lt", col(1), const_int(500))]),
         Aggregation([col(2)], [AggDescriptor("count", None), AggDescriptor("sum", col(3))])],
    ]
    for execs in cases:
        dag = DagRequest(executors=execs)
        cpu = BatchExecutorsRunner(dag, FixtureScanSource(NUMERIC_KVS)).handle_request()
        ev = JaxDagEvaluator(dag, block_rows=256)
        cache = ColumnBlockCache()
        first = ev.run(FixtureScanSource(NUMERIC_KVS), cache=cache)  # fills
        assert cache.filled
        warm1 = ev.run(None, cache=cache)
        warm2 = ev.run(None, cache=cache)
        assert first.encode() == cpu.encode()
        assert warm1.encode() == cpu.encode()
        assert warm2.encode() == cpu.encode()


def test_warm_cache_stable_dict_group():
    """Q1 shape through the on-device group-id (stable dictionary) path."""
    from tikv_tpu.copr.cache import ColumnBlockCache

    kvs = product_kvs([(i, [b"apple", b"banana", b"cherry"][i % 3], i % 7, i * 3) for i in range(1, 900)])
    aggs = [AggDescriptor("count", None), AggDescriptor("sum", col(2)), AggDescriptor("avg", col(3))]
    execs = [
        TableScan(TABLE_ID, PRODUCT_COLUMNS),
        Selection([call("gt", col(2), const_int(1))]),
        Aggregation([col(1)], aggs),
    ]
    dag = DagRequest(executors=execs)
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=128)
    cache = ColumnBlockCache()
    ev.run(FixtureScanSource(kvs), cache=cache)
    warm = ev.run(None, cache=cache)
    assert warm.encode() == cpu.encode()
    # a second evaluator over the same cache also agrees (shared HBM arrays)
    ev2 = JaxDagEvaluator(dag, block_rows=128)
    assert ev2.run(None, cache=cache).encode() == cpu.encode()


def test_group_keys_with_trailing_nul_stay_distinct():
    """numpy 'S' arrays equate b'a' and b'a\\x00' — group keys must not."""
    from tikv_tpu.copr.groupby import GroupDict

    data = np.array([b"a", b"a\x00", b"a", b"b"], dtype=object)
    nulls = np.zeros(4, dtype=bool)
    gd = GroupDict()
    gids = gd.assign([(data, nulls)])
    assert len(gd) == 3
    assert gids[0] == gids[2] and gids[0] != gids[1]
    assert gd.rows[gids[1]][0] == b"a\x00"


def test_batch_respects_other_evaluators_null_masks():
    """A nullable column referenced only by a non-base evaluator must keep
    its null mask in the fused batch program."""
    from tikv_tpu.copr.cache import ColumnBlockCache
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType, NOT_NULL_FLAG
    from tikv_tpu.copr.jax_eval import run_batch_cached
    from tikv_tpu.copr.table import encode_row, record_key

    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),  # nullable
        ColumnInfo(3, FieldType.int64()),  # nullable
    ]
    kvs = [
        (record_key(7, i), encode_row(cols[1:], [None if i % 3 == 0 else i, i]))
        for i in range(300)
    ]
    # base evaluator references only column 2 (never null); column 1 (which
    # HAS nulls) is referenced only by the second evaluator — its null mask
    # must still ship in the fused program
    dag_a = DagRequest(executors=[TableScan(7, cols), Aggregation([], [AggDescriptor("sum", col(2))])])
    dag_b = DagRequest(executors=[TableScan(7, cols), Aggregation([], [AggDescriptor("count", col(1)), AggDescriptor("sum", col(1))])])
    ev_a = JaxDagEvaluator(dag_a, block_rows=64)
    ev_b = JaxDagEvaluator(dag_b, block_rows=64)
    cache = ColumnBlockCache()
    ev_a.run(FixtureScanSource(kvs), cache=cache)
    ra, rb = run_batch_cached([ev_a, ev_b], cache)
    cpu_a = BatchExecutorsRunner(dag_a, FixtureScanSource(kvs)).handle_request()
    cpu_b = BatchExecutorsRunner(dag_b, FixtureScanSource(kvs)).handle_request()
    assert ra.encode() == cpu_a.encode()
    assert rb.encode() == cpu_b.encode()


def test_limb_matmul_seg_sum_exact():
    """Int64 segment sums via f32 limb matmuls must be bit-exact for the
    full int64 range, including negatives and wraparound-prone magnitudes."""
    import jax.numpy as jnp
    import numpy as np

    from tikv_tpu.copr.jax_eval import _limb_matmul_seg_sum, _seg_sum

    rng = np.random.default_rng(7)
    n, cap = 1024, 1024
    gids = rng.integers(0, 777, size=n)
    vals = np.concatenate(
        [
            rng.integers(-(2**62), 2**62, size=n - 6),
            np.array([2**63 - 1, -(2**63), -1, 0, 10**18, -(10**18)]),
        ]
    ).astype(np.int64)
    expect = np.zeros(cap, dtype=np.int64)
    np.add.at(expect, gids, vals)
    got = np.asarray(_limb_matmul_seg_sum(jnp.asarray(vals), jnp.asarray(gids), cap))
    np.testing.assert_array_equal(got, expect)
    # the dispatcher routes 64 < C <= 4096 int sums through the matmul path
    got2 = np.asarray(_seg_sum(jnp.asarray(vals), jnp.asarray(gids), cap))
    np.testing.assert_array_equal(got2, expect)
    # larger blocks shrink the limb width but stay exact
    n2 = 8192
    gids2 = rng.integers(0, 100, size=n2)
    vals2 = rng.integers(-(2**62), 2**62, size=n2).astype(np.int64)
    expect2 = np.zeros(128, dtype=np.int64)
    np.add.at(expect2, gids2, vals2)
    got3 = np.asarray(_limb_matmul_seg_sum(jnp.asarray(vals2), jnp.asarray(gids2), 128))
    np.testing.assert_array_equal(got3, expect2)


def test_raw_topn_identical():
    """Device running top-K merge vs CPU BatchTopNExecutor — byte identity
    across asc/desc, multi-key, selection, ties, and K > matching rows."""
    for order_by, sel, k in [
        ([(col(1), False)], None, 10),  # asc int
        ([(col(1), True)], None, 10),  # desc int
        ([(col(3), False)], None, 25),  # asc decimal
        ([(col(2), False), (col(1), True)], None, 50),  # multi-key w/ ties
        ([(col(1), False)], call("lt", col(2), const_int(30)), 20),  # + filter
        ([(col(1), False)], call("lt", col(1), const_int(3)), 500),  # K > rows
        ([(call("mod", col(1), const_int(7)), False)], None, 40),  # expr key
    ]:
        execs = [TableScan(TABLE_ID, NUMERIC_COLS)]
        if sel is not None:
            execs.append(Selection([sel]))
        execs.append(TopN(order_by, k))
        cpu, dev = run_both(execs, NUMERIC_KVS, block_rows=256)
        assert cpu.encode() == dev.encode(), (order_by, sel, k)
        if sel is None:
            assert len(cpu.iter_rows()) == min(k, 5000)


def test_raw_topn_with_nulls_identical():
    """NULLs first ascending / last descending, matching the CPU comparator,
    with ties among NULLs resolved in stream order."""
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType, FieldTypeTp
    from tikv_tpu.copr.table import encode_row, record_key

    cols = [
        ColumnInfo(col_id=1, ftype=FieldType.int64(), is_pk_handle=True),
        ColumnInfo(col_id=2, ftype=FieldType(FieldTypeTp.LONGLONG)),
        ColumnInfo(col_id=3, ftype=FieldType(FieldTypeTp.DOUBLE)),
    ]
    rng = np.random.default_rng(11)
    kvs = []
    for h in range(300):
        iv = None if h % 7 == 0 else int(rng.integers(-50, 50))
        fv = None if h % 11 == 0 else float(rng.normal())
        kvs.append((record_key(TABLE_ID, h + 1), encode_row(cols[1:], [iv, fv])))
    for order_by in [
        [(col(1), False)],
        [(col(1), True)],
        [(col(2), False)],  # real key with nulls
        [(col(2), True)],
        [(col(1), False), (col(2), True)],
    ]:
        cpu, dev = run_both(
            [TableScan(TABLE_ID, cols), TopN(order_by, 37)], kvs, block_rows=64
        )
        assert cpu.encode() == dev.encode(), order_by


def test_raw_topn_extreme_values_identical():
    """±inf / huge int64 keys survive the monotone sort-key encoding."""
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType, FieldTypeTp
    from tikv_tpu.copr.table import encode_row, record_key

    cols = [
        ColumnInfo(col_id=1, ftype=FieldType.int64(), is_pk_handle=True),
        ColumnInfo(col_id=2, ftype=FieldType(FieldTypeTp.LONGLONG)),
        ColumnInfo(col_id=3, ftype=FieldType(FieldTypeTp.DOUBLE)),
    ]
    vals = [
        (2**63 - 1, float("inf")),
        (-(2**63), float("-inf")),
        (0, 0.0),
        (1, 1.5),
        (-1, -1.5),
        (2**62, 1e308),
        (-(2**62), -1e308),
    ]
    kvs = [
        (record_key(TABLE_ID, h + 1), encode_row(cols[1:], [iv, fv]))
        for h, (iv, fv) in enumerate(vals)
    ]
    for order_by in [[(col(1), False)], [(col(1), True)], [(col(2), False)], [(col(2), True)]]:
        cpu, dev = run_both([TableScan(TABLE_ID, cols), TopN(order_by, 5)], kvs, block_rows=4)
        assert cpu.encode() == dev.encode(), order_by


def test_endpoint_topn_stays_on_device_with_zero_fallbacks():
    """Eligible TopN/agg plans driven through Endpoint.handle_request must run
    on the device path — a silent permanent fallback (device_fallbacks > 0 or
    from_device=False) would still produce correct bytes, so only this
    assertion catches a broken device route (endpoint.rs:392 analog)."""
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.kv import LocalEngine
    from tikv_tpu.storage.engine import WriteBatch
    from tikv_tpu.storage.txn_types import Key, Write, WriteType

    eng = BTreeEngine()
    wb = WriteBatch()
    for rk, val in NUMERIC_KVS[:500]:
        wb.put_cf("write", Key.from_raw(rk).append_ts(11).encoded,
                  Write(WriteType.PUT, 10, short_value=val).to_bytes())
    eng.write(wb)
    ep = Endpoint(LocalEngine(eng), enable_device=True)
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    plans = [
        [TableScan(TABLE_ID, NUMERIC_COLS), TopN([(col(1), True)], 7)],
        [TableScan(TABLE_ID, NUMERIC_COLS),
         Selection([call("lt", col(2), const_int(40))]),
         TopN([(col(2), False), (col(1), True)], 5)],
        [TableScan(TABLE_ID, NUMERIC_COLS),
         Aggregation([col(2)], [AggDescriptor("sum", col(1)), AggDescriptor("count", None)])],
    ]
    for execs in plans:
        req = lambda: CoprRequest(103, DagRequest(executors=execs), [record_range(TABLE_ID)], 100, context={})
        r_dev = ep.handle_request(req())
        r_cpu = ep_cpu.handle_request(req())
        assert r_dev.from_device, f"plan {execs} fell off the device path: {ep.last_device_error}"
        assert r_dev.data == r_cpu.data
    assert ep.device_fallbacks == 0, ep.last_device_error


def test_endpoint_falls_back_to_cpu_on_device_failure(monkeypatch):
    """A device-path runtime failure (compiler, runtime, OOM) must re-run on
    the CPU oracle, not surface an accelerator error to the client."""
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.kv import LocalEngine
    from tikv_tpu.storage.engine import WriteBatch
    from tikv_tpu.storage.txn_types import Key, Write, WriteType

    eng = BTreeEngine()
    wb = WriteBatch()
    for rk, val in NUMERIC_KVS[:50]:
        wb.put_cf("write", Key.from_raw(rk).append_ts(11).encoded,
                  Write(WriteType.PUT, 10, short_value=val).to_bytes())
    eng.write(wb)
    ep = Endpoint(LocalEngine(eng), enable_device=True)
    dag = DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS), TopN([(col(1), False)], 5)])
    req = lambda: CoprRequest(103, DagRequest(executors=dag.executors), [record_range(TABLE_ID)], 100, context={})
    monkeypatch.setattr(
        JaxDagEvaluator, "run", lambda self, src, cache=None, params=(): (_ for _ in ()).throw(RuntimeError("device lost"))
    )
    r = ep.handle_request(req())
    assert not r.from_device
    assert len(r.data) > 0


def test_device_failure_does_not_poison_block_cache(monkeypatch):
    """A transient failure during cache fill must invalidate the partial
    cache — retrying used to double-append blocks and serve wrong data."""
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.kv import LocalEngine
    from tikv_tpu.storage.engine import WriteBatch
    from tikv_tpu.storage.txn_types import Key, Write, WriteType
    from tikv_tpu.copr.aggr import AggDescriptor

    eng = BTreeEngine()
    wb = WriteBatch()
    for rk, val in NUMERIC_KVS[:500]:
        wb.put_cf("write", Key.from_raw(rk).append_ts(11).encoded,
                  Write(WriteType.PUT, 10, short_value=val).to_bytes())
    eng.write(wb)
    ep = Endpoint(LocalEngine(eng), enable_device=True)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, NUMERIC_COLS),
        Aggregation([], [AggDescriptor("count", None), AggDescriptor("sum", col(1))]),
    ])
    ctx = {"region_id": 1, "cache_version": 7}
    req = lambda: CoprRequest(103, DagRequest(executors=dag.executors), [record_range(TABLE_ID)], 100, context=ctx)
    # fail mid-fill: the evaluator dies after the cache got partial blocks
    orig_run = JaxDagEvaluator.run

    def failing_run(self, src, cache=None, params=()):
        if cache is not None:
            cache.add([None], 1)  # simulate partial fill before the fault
        raise RuntimeError("transient device fault")

    monkeypatch.setattr(JaxDagEvaluator, "run", failing_run)
    r1 = ep.handle_request(req())
    assert not r1.from_device
    assert ep.device_fallbacks == 1 and "transient" in ep.last_device_error
    monkeypatch.setattr(JaxDagEvaluator, "run", orig_run)
    r2 = ep.handle_request(req())  # refills the cache from scratch
    r3 = ep.handle_request(req())  # served from the (clean) cache
    cpu = Endpoint(LocalEngine(eng), enable_device=False).handle_request(req())
    assert r2.data == r3.data == cpu.data == r1.data


def test_float_sums_beyond_onehot_window():
    """REAL sums with hundreds of groups ride the blocked mask-reduce (not
    scatter) and match the CPU oracle within float rounding."""
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
    from tikv_tpu.copr.table import encode_row, record_key

    rng = np.random.default_rng(3)
    n, n_groups = 4000, 500
    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.double()),
    ]
    g = rng.integers(0, n_groups, n)
    x = rng.normal(size=n) * 100
    kvs = [
        (record_key(TABLE_ID, i), encode_row(cols[1:], [int(g[i]), float(x[i])]))
        for i in range(n)
    ]
    aggs = [AggDescriptor("sum", col(2)), AggDescriptor("count", None)]
    cpu, dev = run_both(
        [TableScan(TABLE_ID, cols), Aggregation([col(1)], aggs)], kvs, block_rows=512
    )
    crows = sorted(cpu.iter_rows(), key=lambda r: r[-1])
    drows = sorted(dev.iter_rows(), key=lambda r: r[-1])
    assert len(crows) == n_groups == len(drows)
    for c, d in zip(crows, drows):
        assert c[-1] == d[-1] and c[1] == d[1]  # key + count exact
        assert c[0] == pytest.approx(d[0], rel=1e-9)


# ---------------------------------------------------------------------------
# Round-5 eligibility widening: first/bit_* aggregates, dict-coded varchar
# TopN payloads, index-scan leaves (VERDICT r4 item 6)
# ---------------------------------------------------------------------------


def test_first_and_bit_aggs_device():
    """first/bit_and/bit_or/bit_xor ride the device path and match CPU."""
    execs = [
        TableScan(TABLE_ID, NUMERIC_COLS),
        Selection([call("lt", col(1), const_int(800))]),
        Aggregation(
            group_by=[col(2)],
            agg_funcs=[
                AggDescriptor("first", col(1)),
                AggDescriptor("bit_and", col(1)),
                AggDescriptor("bit_or", col(1)),
                AggDescriptor("bit_xor", col(1)),
                AggDescriptor("count", None),
            ],
        ),
    ]
    assert supports(DagRequest(executors=execs))
    cpu, dev = run_both(execs, NUMERIC_KVS)
    assert dev.encode() == cpu.encode()


def test_first_bit_aggs_ungrouped_device():
    execs = [
        TableScan(TABLE_ID, NUMERIC_COLS),
        Aggregation(
            group_by=[],
            agg_funcs=[
                AggDescriptor("first", col(1)),
                AggDescriptor("bit_xor", col(2)),
                AggDescriptor("bit_and", col(2)),
            ],
        ),
    ]
    assert supports(DagRequest(executors=execs))
    cpu, dev = run_both(execs, NUMERIC_KVS)
    assert dev.encode() == cpu.encode()


def test_first_agg_with_nulls_device():
    """first skips NULLs (CPU semantics); all-NULL groups output NULL."""
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
    from tikv_tpu.copr.table import encode_row, record_key

    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.int64()),
    ]
    rng = np.random.default_rng(3)
    kvs = []
    for i in range(4000):
        v = None if rng.random() < 0.3 else int(rng.integers(0, 50))
        g = int(rng.integers(0, 5))
        kvs.append((record_key(TABLE_ID, i), encode_row(cols[1:], [v, g])))
    execs = [
        TableScan(TABLE_ID, cols),
        Aggregation(group_by=[col(2)], agg_funcs=[AggDescriptor("first", col(1))]),
    ]
    cpu, dev = run_both(execs, kvs)
    assert dev.encode() == cpu.encode()


def test_topn_varchar_payload_device():
    """Dict-coded varchar payload columns ship as codes through the device
    top-K merge and decode back byte-identically."""
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
    from tikv_tpu.copr.table import encode_row, record_key

    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.varchar()),   # payload, never a sort key
        ColumnInfo(4, FieldType.int64()),
    ]
    tags = [b"aaaa", b"bbbb", b"cccc", b"dddd", b"eeee"]  # fixed-length rows
    rng = np.random.default_rng(5)
    kvs = []
    for i in range(5000):
        kvs.append((record_key(TABLE_ID, i), encode_row(cols[1:], [
            int(rng.integers(0, 10_000)), tags[int(rng.integers(0, 5))],
            int(rng.integers(-100, 100)),
        ])))
    execs = [
        TableScan(TABLE_ID, cols),
        Selection([call("lt", col(1), const_int(9000))]),
        TopN([(col(1), True), (col(3), False)], 40),
    ]
    assert supports(DagRequest(executors=execs))
    cpu, dev = run_both(execs, kvs)
    assert dev.encode() == cpu.encode()


def _index_fixture(n=6000, seed=9):
    """Two-column index (a, b) with handle; entries sorted in index order."""
    from tikv_tpu.copr import datum as datum_mod
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
    from tikv_tpu.copr.table import index_key
    from tikv_tpu.util import codec

    rng = np.random.default_rng(seed)
    a = rng.integers(0, 8, n)
    b = rng.integers(0, 10_000, n)
    cols = [
        ColumnInfo(1, FieldType.int64()),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.int64(), is_pk_handle=True),
    ]
    kvs = []
    for i in range(n):
        k = index_key(TABLE_ID, 7, [
            (datum_mod.INT_FLAG, int(a[i])), (datum_mod.INT_FLAG, int(b[i])),
        ]) + codec.encode_i64(i)  # unique suffix keeps keys distinct
        kvs.append((k, codec.encode_u64(i)))
    kvs.sort(key=lambda kv: kv[0])
    return cols, kvs


def test_index_scan_leaf_device():
    from tikv_tpu.copr.dag import IndexScan

    cols, kvs = _index_fixture()
    execs = [
        IndexScan(TABLE_ID, 7, cols),
        Selection([call("lt", col(1), const_int(9000))]),
        Aggregation(
            group_by=[col(0)],
            agg_funcs=[AggDescriptor("sum", col(1)), AggDescriptor("count", None)],
        ),
    ]
    assert supports(DagRequest(executors=execs))
    cpu, dev = run_both(execs, kvs, block_rows=512)
    assert dev.encode() == cpu.encode()


def test_index_scan_streamed_prefix_device():
    """Stream agg grouped on the index-column prefix: scan order sorts by it,
    so the device hash output equals the CPU stream executor's."""
    from tikv_tpu.copr.dag import IndexScan

    cols, kvs = _index_fixture()
    execs = [
        IndexScan(TABLE_ID, 7, cols),
        Aggregation(
            group_by=[col(0)],
            agg_funcs=[AggDescriptor("sum", col(1)), AggDescriptor("max", col(1))],
            streamed=True,
        ),
    ]
    assert supports(DagRequest(executors=execs))
    cpu, dev = run_both(execs, kvs, block_rows=512)
    assert dev.encode() == cpu.encode()


def test_index_scan_bytes_column_stays_cpu():
    from tikv_tpu.copr.dag import IndexScan
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType

    cols = [
        ColumnInfo(1, FieldType.varchar()),
        ColumnInfo(2, FieldType.int64(), is_pk_handle=True),
    ]
    dag = DagRequest(executors=[
        IndexScan(TABLE_ID, 7, cols),
        Aggregation(group_by=[], agg_funcs=[AggDescriptor("count", None)]),
    ])
    assert not supports(dag)


def test_one_evaluator_serves_two_caches_at_once():
    """An endpoint keeps ONE evaluator per plan, and the same plan's requests
    for different regions run at once on the server's connection threads.
    The block cache of a run is therefore per thread: held on the instance,
    region B's run swapped region A's cache out from under it (concurrent
    TopN tasks answered with each other's rows on the first chip_smoke
    rehearsal)."""
    import threading

    from tikv_tpu.copr.cache import ColumnBlockCache
    from tikv_tpu.copr.table import decode_record_handles

    dag = DagRequest(executors=[TableScan(TABLE_ID, NUMERIC_COLS),
                                TopN([(col(1), True)], 4)])
    ev = JaxDagEvaluator(dag, block_rows=64)

    def filled(kvs):
        cache = ColumnBlockCache()
        cols = ev.decoder.decode(decode_record_handles([k for k, _ in kvs]),
                                 [v for _, v in kvs])
        cache.add([c.slice(0, len(kvs)) for c in cols], len(kvs))
        cache.filled = True
        return cache

    cache_a, cache_b = filled(NUMERIC_KVS[:40]), filled(NUMERIC_KVS[40:80])
    want_a = ev.run(None, cache=cache_a).encode()
    want_b = ev.run(None, cache=cache_b).encode()
    assert want_a != want_b

    a_inside, b_done = threading.Event(), threading.Event()
    real = ev._prune_keep

    def prune_keep(cache, path):
        # run A has read its cache once and will read it again per block
        if cache is cache_a and not a_inside.is_set():
            a_inside.set()
            assert b_done.wait(30)
        return real(cache, path)

    ev._prune_keep = prune_keep
    got: dict = {}
    ta = threading.Thread(
        target=lambda: got.__setitem__("a", ev.run(None, cache=cache_a).encode()))
    ta.start()
    assert a_inside.wait(30)
    got["b"] = ev.run(None, cache=cache_b).encode()
    b_done.set()
    ta.join(30)
    assert got == {"a": want_a, "b": want_b}


@pytest.mark.parametrize("k,order", [
    (100, [(2, True), (1, False)]),   # 2,048-row sorts, 1,948 rows a chunk
    (1500, [(1, False)]),             # heavy ties; the last chunk overlaps
    (2048, [(3, True), (4, True)]),   # the device TopN's largest K
])
def test_topn_merges_a_block_in_chunks_byte_identically(k, order):
    """At the default block the running top-K merges ~2,000 rows per sort
    (the TPU compiler's time for one 65,636-row sort is minutes): ties keep
    global stream order across chunks and blocks, and the last, overlapping
    chunk counts no row twice."""
    import lineitem_fixture as fx

    kvs = fx.build_kvs(jax_eval.DEFAULT_BLOCK_ROWS + 4500, seed=5)
    dag = DagRequest(executors=[
        TableScan(fx.TABLE_ID, fx._lineitem()),
        Selection([call("le", col(4), const_int(10500))]),
        TopN([(col(i), desc) for i, desc in order], k),
    ])
    rows = jax_eval._topn_chunk_rows(k, jax_eval.DEFAULT_BLOCK_ROWS)
    assert rows < jax_eval.DEFAULT_BLOCK_ROWS and (k + rows) & (k + rows - 1) == 0
    want = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request().encode()
    assert JaxDagEvaluator(dag).run(FixtureScanSource(kvs)).encode() == want


# ---------------------------------------------------------------------------
# programs are named after their timed_jit site (docs/tracing.md): the XLA
# module's name is what the device profiler's trace tells programs apart by
# ---------------------------------------------------------------------------


def _timed_jit_sites() -> dict:
    """Every ``timed_jit(...)`` site string in the program's source, by file."""
    import ast
    import os

    import tikv_tpu

    root = os.path.dirname(tikv_tpu.__file__)
    found: dict = {}
    for rel in ("copr/jax_eval.py", "copr/jax_zone.py", "copr/jax_join.py",
                "parallel/mesh.py"):
        tree = ast.parse(open(os.path.join(root, rel)).read())
        for call_ in ast.walk(tree):
            if not (isinstance(call_, ast.Call)
                    and getattr(call_.func, "attr", "") == "timed_jit"):
                continue
            site = call_.args[1]
            if isinstance(site, ast.Constant):
                found.setdefault(rel, set()).add(site.value)
            else:  # f"jax_join.{path}": the literal prefix
                found.setdefault(rel, set()).add(site.values[0].value)
    return found


@pytest.fixture
def dispatched(monkeypatch):
    """{site: (jitted fn, argument shapes)} of every program dispatched
    through ``timed_jit``'s wrapper while the fixture is live."""
    import jax

    from tikv_tpu.copr import observatory

    seen: dict = {}
    real = observatory._TimedJit.__call__

    def spy(self, *args):
        seen.setdefault(self.site, (self.fn, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
            if hasattr(a, "dtype") else a, args)))
        return real(self, *args)

    monkeypatch.setattr(observatory._TimedJit, "__call__", spy)
    return seen


def _run_jax_eval_family():
    cols, kvs, _ = numeric_table_kvs(600)
    run_both([TableScan(TABLE_ID, cols),
              Selection([call("lt", col(1), const_int(500))]),
              Aggregation([], [AggDescriptor("sum", col(2))])], kvs)
    run_both([TableScan(TABLE_ID, cols),
              Selection([call("lt", col(1), const_int(500))])], kvs)


def _run_jax_zone_family():
    import test_jax_zone as tz
    from tikv_tpu.copr import jax_zone

    old, jax_zone.TILE_ROWS = jax_zone.TILE_ROWS, 64
    try:
        cols, _kvs, cache = tz.mixed_table_kvs(3000)
        dag = DagRequest(executors=[
            TableScan(TABLE_ID, cols),
            Selection([call("lt", col(1), const_int(4000))]),
            Aggregation([col(3)], [AggDescriptor("sum", col(4))])])
        ev = JaxDagEvaluator(dag, block_rows=1024)
        ev.run(None, cache=cache)
        assert ev._zone_evaluator().served == 1
    finally:
        jax_zone.TILE_ROWS = old


def _run_jax_join_family():
    from tikv_tpu.copr import jax_join

    keys = np.arange(16, dtype=np.int64)
    jax_join._kernel("rank")(keys, keys[:8].copy())


def _run_mesh_family():
    from tikv_tpu.parallel.mesh import ShardedDagEvaluator, make_mesh

    cols, _kvs, (a, b, c) = numeric_table_kvs(1024)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, cols),
        Selection([call("lt", col(1), const_int(500))]),
        Aggregation([], [AggDescriptor("count", None)])])
    mesh = make_mesh(groups=2)
    ev = ShardedDagEvaluator(dag, mesh, 1024 // mesh.shape["regions"],
                             capacity=16)
    nulls = np.zeros(1024, dtype=bool)
    ev.run_arrays({1: (a.astype(np.int64), nulls), 2: (b.astype(np.int64), nulls),
                   3: (c.astype(np.int64), nulls)}, 1024,
                  np.zeros(1024, dtype=np.int32))


@pytest.mark.parametrize("family,run,expect", [
    ("copr/jax_eval.py", _run_jax_eval_family, "jax_eval.agg_step"),
    ("copr/jax_zone.py", _run_jax_zone_family, "jax_zone.full"),
    ("copr/jax_join.py", _run_jax_join_family, "jax_join.rank"),
    ("parallel/mesh.py", _run_mesh_family, "mesh.agg_step"),
])
def test_program_module_is_named_after_its_site(family, run, expect, dispatched):
    """One program of each family, lowered at the shapes it ran with: the
    XLA module is ``jit_<site, its dot an underscore>``, never ``jit_fn``."""
    sites = _timed_jit_sites()
    assert sites[family], f"no timed_jit site found in {family}"
    run()
    assert expect in dispatched, sorted(dispatched)
    prefixes = tuple(sites[family])
    for site, (fn, specs) in dispatched.items():
        assert site.startswith(prefixes), (site, prefixes)
        first = fn.lower(*specs).as_text().splitlines()[0]
        assert first.startswith(f"module @jit_{site.replace('.', '_')} "), \
            (site, first)
