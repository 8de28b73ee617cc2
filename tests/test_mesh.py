"""Mesh-sharded evaluation must match single-device aggregation exactly."""

import numpy as np
import pytest

import jax

from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan, TopN
from tikv_tpu.copr.jax_eval import _NO_ROW
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.parallel.mesh import (
    ShardedDagEvaluator,
    ShardedGroupedEvaluator,
    ShardedTopNEvaluator,
    make_mesh,
)

from copr_fixtures import TABLE_ID, numeric_table_kvs

COLS, _, (A, B, C) = numeric_table_kvs(4096)


def q6ish():
    return DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Selection([call("lt", col(1), const_int(500))]),
            Aggregation(
                [],
                [
                    AggDescriptor("count", None),
                    AggDescriptor("sum", col(3)),
                    AggDescriptor("min", col(1)),
                    AggDescriptor("max", col(2)),
                ],
            ),
        ]
    )


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_sharded_simple_agg_matches_numpy(groups):
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    mesh = make_mesh(groups=groups)
    rows_per_shard = 4096 // mesh.shape["regions"]
    ev = ShardedDagEvaluator(q6ish(), mesh, rows_per_shard, capacity=16)
    n = 4096
    columns = {
        1: (A.astype(np.int64), np.zeros(n, dtype=bool)),
        2: (B.astype(np.int64), np.zeros(n, dtype=bool)),
        3: (C.astype(np.int64), np.zeros(n, dtype=bool)),
    }
    gids = np.zeros(n, dtype=np.int32)
    first, carries = jax.tree.map(np.asarray, ev.run_arrays(columns, n, gids))
    mask = A < 500
    assert carries[0][0][0] == mask.sum()  # count
    assert carries[1][1][0] == C[mask].sum()  # sum
    assert carries[2][1][0] == A[mask].min()  # min
    assert carries[3][1][0] == B[mask].max()  # max
    assert first[0] == int(np.flatnonzero(mask)[0])


@pytest.mark.parametrize("groups", [2, 4])
def test_sharded_group_agg_matches_numpy(groups):
    mesh = make_mesh(groups=groups)
    rows_per_shard = 4096 // mesh.shape["regions"]
    dag = DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Aggregation([col(2)], [AggDescriptor("count", None), AggDescriptor("sum", col(3))]),
        ]
    )
    ev = ShardedDagEvaluator(dag, mesh, rows_per_shard, capacity=16)
    n = 4096
    gkey = (B % 16).astype(np.int32)
    columns = {
        2: (B.astype(np.int64), np.zeros(n, dtype=bool)),
        3: (C.astype(np.int64), np.zeros(n, dtype=bool)),
    }
    first, carries = jax.tree.map(np.asarray, ev.run_arrays(columns, n, gkey))
    for g in range(16):
        m = gkey == g
        assert carries[0][0][g] == m.sum()
        assert carries[1][1][g] == C[m].sum()
        if m.any():
            assert first[g] != _NO_ROW


def _columns(n, cols_map):
    return {i: (v.astype(np.int64), np.zeros(n, dtype=bool)) for i, v in cols_map.items()}


def test_multi_block_carry_simple_agg():
    """Aggregate state stays on device across super-blocks (long-scan carry)."""
    mesh = make_mesh(groups=2)
    rows = 4096 // mesh.shape["regions"] // 4  # 4 super-blocks
    ev = ShardedDagEvaluator(q6ish(), mesh, rows, capacity=16)
    total = ev.total_rows
    blocks = []
    for b in range(4):
        sl = slice(b * total, (b + 1) * total)
        blocks.append(
            (_columns(total, {1: A[sl], 2: B[sl], 3: C[sl]}), total, np.zeros(total, np.int32))
        )
    first, carries = jax.tree.map(np.asarray, ev.run_blocks(blocks))
    mask = A < 500
    assert carries[0][0][0] == mask.sum()
    assert carries[1][1][0] == C[mask].sum()
    assert carries[2][1][0] == A[mask].min()
    assert carries[3][1][0] == B[mask].max()
    assert first[0] == int(np.flatnonzero(mask)[0])


def grouped_dag():
    return DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Selection([call("lt", col(1), const_int(800))]),
            Aggregation(
                [col(2)],
                [
                    AggDescriptor("count", None),
                    AggDescriptor("sum", col(3)),
                    AggDescriptor("min", col(1)),
                ],
            ),
        ]
    )


def _grouped_oracle(mask, gkey):
    """numpy oracle: per-group count/sum/min in first-occurrence order."""
    order, seen = [], set()
    for i in np.flatnonzero(mask):
        g = int(gkey[i])
        if g not in seen:
            seen.add(g)
            order.append(g)
    return order


@pytest.mark.parametrize("groups", [1, 2])
def test_device_group_dict_matches_oracle(groups):
    """The group DICTIONARY is built on device across shards; results come
    back in first-occurrence order, matching the host dict-coded path."""
    mesh = make_mesh(groups=groups)
    rows_per_shard = 4096 // mesh.shape["regions"]
    ev = ShardedGroupedEvaluator(grouped_dag(), mesh, rows_per_shard, capacity=64)
    n = 4096
    gkey = (B % 13).astype(np.int64)
    columns = _columns(n, {1: A, 2: gkey, 3: C})
    out = ev.finalize(ev.run_blocks([(columns, n)]))
    assert not out["overflow"]
    mask = A < 800
    order = _grouped_oracle(mask, gkey)
    assert list(out["keys"]) == order
    for pos, g in enumerate(order):
        m = mask & (gkey == g)
        assert out["aggs"][0][0][pos] == m.sum()
        assert out["aggs"][1][1][pos] == C[m].sum()
        assert out["aggs"][2][1][pos] == A[m].min()


def test_device_group_dict_multi_block_carry():
    """New groups appearing in LATER blocks reshuffle the sorted dictionary;
    carried per-slot states must be remapped, and first-occurrence order must
    use the global stream index."""
    mesh = make_mesh(groups=2)
    rows = 4096 // mesh.shape["regions"] // 4
    ev = ShardedGroupedEvaluator(grouped_dag(), mesh, rows, capacity=64)
    total = ev.total_rows
    # force new (smaller-sorting) keys to appear only in later blocks
    gkey = (B % 7).astype(np.int64) + 20
    gkey[2 * total :] = (B[2 * total :] % 5).astype(np.int64)  # keys 0..4 late
    blocks = []
    for b in range(4):
        sl = slice(b * total, (b + 1) * total)
        blocks.append((_columns(total, {1: A[sl], 2: gkey[sl], 3: C[sl]}), total))
    out = ev.finalize(ev.run_blocks(blocks))
    assert not out["overflow"]
    mask = A < 800
    order = _grouped_oracle(mask, gkey)
    assert list(out["keys"]) == order
    for pos, g in enumerate(order):
        m = mask & (gkey == g)
        assert out["aggs"][0][0][pos] == m.sum()
        assert out["aggs"][1][1][pos] == C[m].sum()
        assert out["aggs"][2][1][pos] == A[m].min()


def test_group_dict_overflow_is_detected():
    mesh = make_mesh(groups=1)
    rows_per_shard = 4096 // mesh.shape["regions"]
    ev = ShardedGroupedEvaluator(grouped_dag(), mesh, rows_per_shard, capacity=8)
    n = 4096
    gkey = (np.arange(n) % 50).astype(np.int64)  # 50 groups > capacity 8
    columns = _columns(n, {1: A, 2: gkey, 3: C})
    out = ev.finalize(ev.run_blocks([(columns, n)]))
    assert out["overflow"], "50 groups into capacity 8 must flag overflow"


def topn_dag(k=10):
    return DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Selection([call("lt", col(1), const_int(700))]),
            TopN([(col(2), True), (col(3), False)], k),
        ]
    )


def _topn_oracle(mask, k):
    """numpy oracle: rows sorted by (B desc, C asc, stream order), top k."""
    idx = np.flatnonzero(mask)
    order = np.lexsort((idx, C[idx], -B[idx]))
    return idx[order][:k]


@pytest.mark.parametrize("n_blocks", [1, 4])
def test_sharded_topn_matches_oracle(n_blocks):
    """Per-shard running top-K + collective merge == single-stream top-K,
    including cross-shard tie-breaks by global stream order."""
    mesh = make_mesh(groups=2)
    rows = 4096 // mesh.shape["regions"] // n_blocks
    ev = ShardedTopNEvaluator(topn_dag(10), mesh, rows)
    total = ev.total_rows
    blocks = []
    for b in range(n_blocks):
        sl = slice(b * total, (b + 1) * total)
        h = np.arange(b * total, (b + 1) * total)
        blocks.append((_columns(total, {0: h, 1: A[sl], 2: B[sl], 3: C[sl]}), total))
    out = ev.finalize(ev.run_blocks(blocks))
    expect = _topn_oracle(A < 700, 10)
    assert out["rows"] == len(expect)
    assert list(out["gidx"]) == list(expect)
    # payload columns carry the right rows (0=handle, 1=A, 2=B, 3=C)
    np.testing.assert_array_equal(out["payload"][0][0], expect)
    np.testing.assert_array_equal(out["payload"][2][0], B[expect])
    np.testing.assert_array_equal(out["payload"][3][0], C[expect])


def test_sharded_topn_ties_resolve_in_stream_order():
    """Rows with IDENTICAL keys across different shards must come back in
    global stream order (the CPU executor's seq tie-break)."""
    mesh = make_mesh(groups=1)
    rows = 512 // mesh.shape["regions"]
    dag = DagRequest(executors=[TableScan(TABLE_ID, COLS), TopN([(col(2), False)], 6)])
    ev = ShardedTopNEvaluator(dag, mesh, rows)
    n = ev.total_rows
    const_b = np.full(n, 42, dtype=np.int64)  # every key ties
    columns = _columns(n, {0: np.arange(n), 1: A[:n], 2: const_b, 3: C[:n]})
    out = ev.finalize(ev.run_blocks([(columns, n)]))
    assert list(out["gidx"]) == [0, 1, 2, 3, 4, 5]


def test_sharded_topn_fewer_rows_than_k():
    mesh = make_mesh(groups=1)
    rows = 512 // mesh.shape["regions"]
    dag = DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Selection([call("lt", col(1), const_int(3))]),
            TopN([(col(1), False)], 50),
        ]
    )
    ev = ShardedTopNEvaluator(dag, mesh, rows)
    n = ev.total_rows
    columns = _columns(n, {0: np.arange(n), 1: A[:n], 2: B[:n], 3: C[:n]})
    out = ev.finalize(ev.run_blocks([(columns, n)]))
    assert out["rows"] == int((A[:n] < 3).sum())


def test_group_key_out_of_range_flags_overflow():
    """Values that cannot pack losslessly into the key lane (negative, or
    >= the NULL lane) must flag overflow — truncation would silently merge
    distinct groups."""
    mesh = make_mesh(groups=1)
    rows_per_shard = 512 // mesh.shape["regions"]
    ev = ShardedGroupedEvaluator(grouped_dag(), mesh, rows_per_shard, capacity=8)
    n = ev.total_rows
    gkey = np.zeros(n, dtype=np.int64)
    gkey[: n // 2] = -1                # negative: cannot pack
    gkey[n // 2 :] = (1 << 31) - 1     # collides with the NULL lane
    columns = _columns(n, {1: np.zeros(n, np.int64), 2: gkey, 3: C[:n]})
    out = ev.finalize(ev.run_blocks([(columns, n)]))
    assert out["overflow"], "out-of-range group keys must flag overflow"


def test_too_many_group_keys_rejected_at_init():
    with pytest.raises(ValueError):
        dag = DagRequest(
            executors=[
                TableScan(TABLE_ID, COLS),
                Aggregation([col(1), col(2), col(3)], [AggDescriptor("count", None)]),
            ]
        )
        ShardedGroupedEvaluator(dag, make_mesh(groups=1), 64, capacity=8)


# --- serving-path mesh integration -----------------------------------------


def _mvcc_engine(n=3000):
    """Committed MVCC rows of the numeric table inside a BTreeEngine."""
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.engine import WriteBatch
    from tikv_tpu.storage.txn_types import Key, Write, WriteType

    cols, kvs, _ = numeric_table_kvs(n, seed=7)
    eng = BTreeEngine()
    wb = WriteBatch()
    for rk, val in kvs:
        wb.put_cf("write", Key.from_raw(rk).append_ts(11).encoded,
                  Write(WriteType.PUT, 10, short_value=val).to_bytes())
    eng.write(wb)
    return cols, eng


@pytest.mark.parametrize("groups", [1, 2])
def test_endpoint_mesh_serving_byte_identical(groups):
    """Endpoint.handle_request over an MVCC-decoded region must return
    byte-identical responses on 1 device and on the full 8-device mesh."""
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.kv import LocalEngine

    cols, eng = _mvcc_engine()
    mesh = make_mesh(groups=groups)
    ep_mesh = Endpoint(LocalEngine(eng), enable_device=True, mesh=mesh)
    ep_one = Endpoint(LocalEngine(eng), enable_device=True)
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    plans = [
        # scalar aggregation with selection (Q6 shape)
        [TableScan(TABLE_ID, cols),
         Selection([call("lt", col(2), const_int(40))]),
         Aggregation([], [AggDescriptor("count", None),
                          AggDescriptor("sum", col(3)),
                          AggDescriptor("min", col(2)),
                          AggDescriptor("max", col(3))])],
        # grouped aggregation (Q1 shape)
        [TableScan(TABLE_ID, cols),
         Aggregation([col(2)], [AggDescriptor("count", None),
                                AggDescriptor("sum", col(3)),
                                AggDescriptor("avg", col(3))])],
    ]
    for execs in plans:
        req = lambda: CoprRequest(
            103, DagRequest(executors=execs), [record_range(TABLE_ID)], 100, context={})
        r_mesh = ep_mesh.handle_request(req())
        r_one = ep_one.handle_request(req())
        r_cpu = ep_cpu.handle_request(req())
        assert r_mesh.from_device, f"mesh path fell back: {ep_mesh.last_device_error}"
        assert r_mesh.data == r_one.data == r_cpu.data
    assert ep_mesh.device_fallbacks == 0, ep_mesh.last_device_error
    # the mesh runners were actually used for these aggregation DAGs
    assert len(ep_mesh._mesh_runners) == len(plans)


def test_endpoint_mesh_group_growth():
    """More groups than the initial sharded capacity: state migrates to a
    larger capacity mid-scan and the answer stays byte-identical."""
    from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.kv import LocalEngine

    cols, eng = _mvcc_engine(2500)
    ep_mesh = Endpoint(LocalEngine(eng), enable_device=True, mesh=make_mesh(groups=2))
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    # group by id % large modulus → dozens of groups (> capacity 16)
    execs = [TableScan(TABLE_ID, cols),
             Aggregation([call("mod", col(1), const_int(97))],
                         [AggDescriptor("count", None), AggDescriptor("sum", col(2))])]
    req = lambda: CoprRequest(
        103, DagRequest(executors=execs), [record_range(TABLE_ID)], 100, context={})
    r_mesh = ep_mesh.handle_request(req())
    r_cpu = ep_cpu.handle_request(req())
    assert r_mesh.from_device, ep_mesh.last_device_error
    assert r_mesh.data == r_cpu.data


def test_mesh_serving_runner_non_pow2_groups():
    """A groups axis of 3 must yield a divisible capacity, not an infinite
    capacity-search loop."""
    from tikv_tpu.parallel.mesh import MeshServingRunner

    mesh = make_mesh(jax.devices()[:6], groups=3)
    runner = MeshServingRunner(q6ish(), mesh, rows_per_shard=64)
    assert runner.sharded.capacity % 3 == 0


def test_mesh_rejects_non_agg_dag_cheaply():
    """Scan/TopN DAGs route to the single-device evaluator, and the negative
    outcome is cached so repeat requests skip re-probing."""
    from tikv_tpu.copr.endpoint import Endpoint
    from tikv_tpu.storage.btree_engine import BTreeEngine
    from tikv_tpu.storage.kv import LocalEngine

    ep = Endpoint(LocalEngine(BTreeEngine()), mesh=make_mesh(groups=2))
    dag = DagRequest(executors=[TableScan(TABLE_ID, COLS), TopN([(col(1), False)], 5)])
    assert ep._mesh_evaluator_for(dag) is None
    key = next(iter(ep._mesh_runners))
    assert ep._mesh_runners[key] is None  # cached negative
    assert ep._mesh_evaluator_for(dag) is None


def test_mesh_bit_aggs_and_first_decline():
    """bit_and/or/xor merge across region shards; 'first' (paired argmin
    carry) declines mesh construction so the endpoint memoizes the
    single-device route instead of re-probing."""
    import numpy as np
    import pytest

    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation, DagRequest, TableScan
    from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
    from tikv_tpu.copr.rpn import col
    from tikv_tpu.parallel.mesh import ShardedDagEvaluator, make_mesh

    cols_info = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
    ]
    dag = DagRequest(executors=[
        TableScan(1, cols_info),
        Aggregation(group_by=[], agg_funcs=[
            AggDescriptor("bit_and", col(1)),
            AggDescriptor("bit_or", col(1)),
            AggDescriptor("bit_xor", col(1)),
            AggDescriptor("count", None),
        ]),
    ])
    mesh = make_mesh(jax.devices()[:8], groups=2)
    ev = ShardedDagEvaluator(dag, mesh, rows_per_shard=64, capacity=4)
    n = ev.total_rows
    rng = np.random.default_rng(2)
    vals = rng.integers(0, 1 << 20, n).astype(np.int64)
    columns = {i: (vals, np.zeros(n, dtype=bool)) for i in ev.ev.device_cols}
    gids = rng.integers(0, 4, n).astype(np.int32)
    state = jax.tree.map(np.asarray, ev.run_arrays(columns, n, gids))
    for slot in range(4):
        m = gids == slot
        assert int(state[1][0][1][slot]) == int(np.bitwise_and.reduce(vals[m])) if m.any() else True
        assert int(state[1][1][1][slot]) == int(np.bitwise_or.reduce(vals[m], initial=0))
        assert int(state[1][2][1][slot]) == int(np.bitwise_xor.reduce(vals[m], initial=0))

    first_dag = DagRequest(executors=[
        TableScan(1, cols_info),
        Aggregation(group_by=[], agg_funcs=[AggDescriptor("first", col(1))]),
    ])
    with pytest.raises(ValueError, match="mesh merge"):
        ShardedDagEvaluator(first_dag, mesh, rows_per_shard=64, capacity=4)
