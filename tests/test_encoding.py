"""Unit tests for copr/encoding.py — the compressed-resident column layer.

Round-trips (encode→decode byte-stable), late-materialize gathers, in-place
payload patches vs demotions, dictionary encoding with sorted codes, the
device-plan eligibility matrix with per-cause decline counters, and the
dict-code-space predicate rewrite rules."""

import numpy as np

from tikv_tpu.copr import encoding as E
from tikv_tpu.copr.cache import ColumnBlockCache
from tikv_tpu.copr.dag import DagRequest, Selection, TableScan
from tikv_tpu.copr.datatypes import Column, ColumnInfo, EvalType, FieldType
from tikv_tpu.copr.rpn import call, col, const_bytes
from tikv_tpu.util.metrics import REGISTRY

from copr_fixtures import TABLE_ID


def _col(values):
    return Column.from_values(EvalType.INT, values)


def _cache_with(cols_per_block, n_valids):
    cache = ColumnBlockCache()
    for cols, nv in zip(cols_per_block, n_valids):
        cache.add(cols, nv)
    cache.filled = True
    return cache


def _counter_val(name, **labels):
    c = REGISTRY._metrics.get(name)
    if c is None:
        return 0
    return c.get(**labels)


# -- encode / decode round trips --------------------------------------------

def test_bitpack_round_trip_and_nulls():
    vals = [100, 105, None, 227] * 200
    c = _col(vals)
    e = E._encode_one(c, len(vals))
    assert e is not None and e.kind == "bp"
    assert e.packed.dtype == np.int8 and e.ref == 100
    assert np.array_equal(e.data, c.data)  # null slots normalize to 0
    assert np.array_equal(e.nulls, c.nulls)
    assert e.encoded_nbytes() < (c.data.nbytes + c.nulls.nbytes) // 4


def test_rle_round_trip_with_null_runs():
    vals = [7] * 500 + [None] * 300 + [-2] * 200
    c = _col(vals)
    e = E._encode_one(c, len(vals))
    assert e is not None and e.kind == "rle"
    assert len(e.run_values) == 3
    assert np.array_equal(e.data, c.data)
    assert np.array_equal(e.nulls, c.nulls)


def test_take_late_materializes_only_selected_rows():
    c = _col(list(range(50, 150)) * 10)
    e = E._encode_one(c, 1000)
    assert e is not None and e.kind == "bp"
    idx = np.array([0, 7, 999])
    t = e.take(idx)
    assert list(t.data) == [int(c.data[i]) for i in idx]
    r = E._encode_one(_col([3] * 900 + [4] * 100), 1000)
    assert r.kind == "rle"
    t2 = r.take(np.array([0, 899, 900, 999]))
    assert list(t2.data) == [3, 3, 4, 4]


def test_wide_range_column_stays_plain():
    rng = np.random.default_rng(0)
    c = _col([int(x) for x in rng.integers(-(1 << 40), 1 << 40, 500)])
    assert E._encode_one(c, 500) is None


def test_real_columns_stay_plain():
    c = Column.from_values(EvalType.REAL, [1.5, 1.5, 1.5] * 100)
    assert E._encode_one(c, 300) is None


# -- in-place patch vs demote ------------------------------------------------

def test_demote_column_counts_and_drops_pins():
    cache = _cache_with([[_col([1, 1, 1, 1])]], [4])
    E.encode_blocks(cache, None)
    assert isinstance(cache.blocks[0].cols[0], E.EncodedColumn)
    before = _counter_val("tikv_tpu_never", x="y")  # counter access shape
    v0 = cache.enc_version
    E.demote_column(cache, 0, "inplace_update")
    assert not isinstance(cache.blocks[0].cols[0], E.EncodedColumn)
    assert cache.enc_version > v0
    assert _counter_val("tikv_coprocessor_encoding_demote_total",
                        kind="rle", cause="inplace_update") >= 1
    assert before == 0


# -- fill-time stats pass ----------------------------------------------------

def test_encode_blocks_uniform_choice_and_dictionary():
    n = 60
    name = np.empty(n, dtype=object)
    name[:] = [[b"b", b"a", b"c"][i % 3] for i in range(n)]
    blocks = [
        [_col(list(range(1, n + 1))),                   # increasing → bp
         Column(EvalType.BYTES, name, np.zeros(n, bool)),
         _col([9] * n)],                                 # runs → rle
    ]
    cache = _cache_with(blocks, [n])
    changed = E.encode_blocks(cache, None)
    assert changed[0] == "bp" and changed[2] == "rle"
    assert changed[1] == "dict"
    dcol = cache.blocks[0].cols[1]
    assert dcol.is_dict_encoded
    # dictionary is SORTED → order-preserving codes (range rewrites)
    assert [bytes(v) for v in dcol.dictionary] == [b"a", b"b", b"c"]
    assert np.array_equal(dcol.data[:6], [1, 0, 2, 1, 0, 2])
    assert dcol.data.dtype == np.int8


def _encoded_cache(seed=0, n=256):
    rng = np.random.default_rng(seed)
    cache = _cache_with(
        [[_col([int(x) for x in rng.integers(0, 50, n)]), _col([3] * n)]], [n])
    E.encode_blocks(cache, None)
    return cache


def test_device_plan_descriptors_and_memo():
    cache = _encoded_cache()
    plan = E.device_plan(cache, [0, 1], [])
    assert plan is not None
    assert plan.sig[0][0] == "bp" and plan.sig[1][0] == "rle"
    assert E.device_plan(cache, [0, 1], []) is plan  # memoized
    E.demote_column(cache, 1, "inplace_update")
    plan2 = E.device_plan(cache, [0, 1], [])
    assert plan2 is not plan and plan2.sig[1] == ("plain",)


def test_batch_plan_mismatch_and_rle_declines_counted():
    a, b = _encoded_cache(1), _encoded_cache(2)
    # identical shapes/signatures → encoded
    assert E.batch_plan([a, b], [0, 1], [], "xregion") is not None
    # rle excluded on the sharded path → decode-ship, counted per-cause
    before = _counter_val("tikv_coprocessor_encoded_decline_total",
                          path="mesh_sharded", cause="rle_sharded")
    assert E.batch_plan([a, b], [0, 1], [], "mesh_sharded",
                        allow_rle=False) is None
    assert _counter_val("tikv_coprocessor_encoded_decline_total",
                        path="mesh_sharded", cause="rle_sharded") == before + 1
    # signature mismatch (one cache demoted) → decode-ship, counted
    E.demote_column(b, 0, "inplace_update")
    before = _counter_val("tikv_coprocessor_encoded_decline_total",
                          path="xregion", cause="enc_mismatch")
    assert E.batch_plan([a, b], [0, 1], [], "xregion") is None
    assert _counter_val("tikv_coprocessor_encoded_decline_total",
                        path="xregion", cause="enc_mismatch") == before + 1


def test_byte_accounting_encoded_vs_decoded():
    cache = _encoded_cache()
    assert cache.nbytes() < cache.nbytes_decoded() // 2


# -- dict-code-space rewrite -------------------------------------------------

def _dict_blocks(values, sorted_dict=True):
    values = list(values) * 30  # clear the cardinality gate
    data = np.empty(len(values), dtype=object)
    data[:] = values
    cache = _cache_with(
        [[_col(list(range(len(values)))),
          Column(EvalType.BYTES, data, np.zeros(len(values), bool))]],
        [len(values)])
    E.encode_blocks(cache, None)
    if not sorted_dict:
        # simulate a delta-grown (append-ordered) dictionary
        c = cache.blocks[0].cols[1]
        d = np.empty(len(c.dictionary) + 1, dtype=object)
        d[:-1] = c.dictionary
        d[-1] = b"a_late"
        c.dictionary = d
    return cache.blocks


def _sel_dag(cond):
    cols_info = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
                 ColumnInfo(2, FieldType.varchar())]
    return DagRequest(executors=[TableScan(TABLE_ID, cols_info),
                                 Selection([cond])])


def test_rewrite_probe_and_eq_rewrite():
    dag = _sel_dag(call("eq", col(1), const_bytes(b"bb")))
    assert E.dict_rewrite_probe(dag)
    blocks = _dict_blocks([b"aa", b"bb", b"cc", b"bb"])
    new_dag, rewritten = E.rewrite_dag_for_dict(dag, blocks)
    assert new_dag is not None and rewritten == {1}
    cond = new_dag.executors[1].conditions[0]
    assert cond.op == "eq" and cond.children[1].value == 1  # code of b"bb"
    assert cond.children[1].eval_type == EvalType.INT
    # absent constant maps to the impossible code -1
    dag2 = _sel_dag(call("eq", col(1), const_bytes(b"zz")))
    nd2, _ = E.rewrite_dag_for_dict(dag2, blocks)
    assert nd2.executors[1].conditions[0].children[1].value == -1


def test_rewrite_range_requires_sorted_dictionary():
    dag = _sel_dag(call("lt", col(1), const_bytes(b"bb")))
    nd, _ = E.rewrite_dag_for_dict(dag, _dict_blocks([b"aa", b"bb", b"cc"]))
    assert nd is not None
    nd2, cause = E.rewrite_dag_for_dict(
        dag, _dict_blocks([b"aa", b"bb", b"cc"], sorted_dict=False))
    assert nd2 is None and cause == "dict_unsorted"
    # equality stays rewritable on the unsorted dictionary
    nd3, _ = E.rewrite_dag_for_dict(
        _sel_dag(call("eq", col(1), const_bytes(b"a_late"))),
        _dict_blocks([b"aa", b"bb", b"cc"], sorted_dict=False))
    assert nd3 is not None


def test_rewrite_declines_outside_references():
    """A rewritten column's schema entry becomes INT, so a reference
    anywhere else (aggregate arg, group-by, another condition) would serve
    raw dictionary codes — the rewrite must decline those plans."""
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation

    blocks = _dict_blocks([b"aa", b"bb", b"cc"])
    cols_info = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
                 ColumnInfo(2, FieldType.varchar())]
    for extra in (Aggregation([], [AggDescriptor("max", col(1))]),
                  Aggregation([col(1)], [AggDescriptor("count", None)])):
        dag = DagRequest(executors=[
            TableScan(TABLE_ID, cols_info),
            Selection([call("ge", col(1), const_bytes(b"bb"))]),
            extra,
        ])
        nd, cause = E.rewrite_dag_for_dict(dag, blocks)
        assert nd is None and cause == "outside_reference", (cause, extra)
    # an unrewritable condition referencing the column blocks it too
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, cols_info),
        Selection([call("eq", col(1), const_bytes(b"bb")),
                   call("eq", col(1), col(1))]),
    ])
    nd, cause = E.rewrite_dag_for_dict(dag, blocks)
    assert nd is None and cause == "outside_reference"


def test_rewrite_probe_rejects_non_candidates():
    cols_info = [ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
                 ColumnInfo(2, FieldType.int64())]
    dag = DagRequest(executors=[TableScan(TABLE_ID, cols_info),
                                Selection([call("eq", col(1), col(1))])])
    assert not E.dict_rewrite_probe(dag)
