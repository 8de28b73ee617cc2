"""Differential tests for the zone-tiled clustered warm path (jax_zone.py).

Every case runs a DAG through the device warm-cache path with small tiles (so
full / empty / partial tiles all occur) and asserts the encoded response is
byte-identical to the CPU pipeline — the same oracle contract as
test_jax_eval.py, plus assertions that the zone path (not the generic scan)
actually served the query where expected.
"""

import numpy as np
import pytest

from tikv_tpu.copr import jax_zone
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.cache import ColumnBlockCache
from tikv_tpu.copr.dag import (
    Aggregation,
    BatchExecutorsRunner,
    DagRequest,
    Limit,
    Selection,
    TableScan,
    TopN,
)
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.executors import FixtureScanSource
from tikv_tpu.copr.jax_eval import JaxDagEvaluator
from tikv_tpu.copr.rpn import call, col, const_bytes, const_decimal, const_int
from tikv_tpu.copr.table import encode_row, record_key

from copr_fixtures import TABLE_ID


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    """Small tiles so a few thousand rows produce many tiles with mixed
    full/empty/partial classifications."""
    monkeypatch.setattr(jax_zone, "TILE_ROWS", 64)


def mixed_table_kvs(n, seed=0, with_nulls=False):
    """id, v int (sortable range col), d decimal(2), tag varchar (dict-coded
    group key), w int.  Optional NULLs in v and tag.

    Returns (cols, kvs, cache): kvs feed the CPU oracle; the pre-filled
    ColumnBlockCache is the decoded image with dict-coded varchars sharing
    ONE dictionary object across blocks (the stable-dictionary contract the
    zone path keys on — built directly, the same way
    lineitem_fixture.build_cache does, because the row decoder only
    dictionary-encodes fixed-layout rows)."""
    rng = np.random.default_rng(seed)
    cols = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.decimal_type(2)),
        ColumnInfo(4, FieldType.varchar()),
        ColumnInfo(5, FieldType.int64()),
    ]
    v = rng.integers(0, 10_000, n)
    d = rng.integers(0, 5_000, n)
    tags = [b"alpha", b"beta", b"gamma"]
    t = rng.integers(0, 3, n)
    w = rng.integers(-50, 50, n)
    null_v = rng.random(n) < 0.05 if with_nulls else np.zeros(n, dtype=bool)
    null_t = rng.random(n) < 0.05 if with_nulls else np.zeros(n, dtype=bool)
    non_handle = cols[1:]
    kvs = []
    for i in range(n):
        row = [
            None if null_v[i] else int(v[i]),
            int(d[i]),
            None if null_t[i] else tags[t[i]],
            int(w[i]),
        ]
        kvs.append((record_key(TABLE_ID, i), encode_row(non_handle, row)))

    from tikv_tpu.copr.datatypes import Column, EvalType

    dictionary = np.empty(3, dtype=object)
    dictionary[:] = sorted(tags)
    code_of = {tag: j for j, tag in enumerate(sorted(tags))}
    codes = np.array([code_of[tags[ti]] for ti in t], dtype=np.int64)
    handles = np.arange(n, dtype=np.int64)
    cache = ColumnBlockCache()
    block = 2048  # long group runs so boundary/pad tiles stay a small fraction
    for s in range(0, n, block):
        e = min(s + block, n)
        m = e - s
        z = np.zeros(m, dtype=bool)
        cache.add(
            [
                Column(EvalType.INT, handles[s:e], z.copy()),
                Column(EvalType.INT, np.where(null_v[s:e], 0, v[s:e]), null_v[s:e].copy()),
                Column(EvalType.DECIMAL, d[s:e].copy(), z.copy(), 2),
                Column(EvalType.BYTES, codes[s:e].copy(), null_t[s:e].copy(), 0, dictionary),
                Column(EvalType.INT, w[s:e].copy(), z.copy()),
            ],
            m,
        )
    cache.filled = True
    return cols, kvs, cache


def run_warm(executors, fixture, output_offsets=None):
    cols, kvs, cache = fixture
    dag = DagRequest(executors=executors, output_offsets=output_offsets)
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=2048)
    warm = ev.run(None, cache=cache)
    return cpu, warm, ev


def zone_served(ev) -> bool:
    zone = getattr(ev, "_zone", None)
    return bool(zone) and zone.served > 0


FIX = mixed_table_kvs(6000)
NFIX = mixed_table_kvs(6000, seed=1, with_nulls=True)
COLS, KVS, CACHE = FIX
NCOLS, NKVS, NCACHE = NFIX


def test_zone_grouped_range_predicate():
    """Grouped agg with a recognized range conjunct: the bench Q1 shape."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("le", col(1), const_int(7000))]),
            Aggregation(
                group_by=[col(3)],
                agg_funcs=[
                    AggDescriptor("sum", col(1)),
                    AggDescriptor("avg", col(2)),
                    AggDescriptor("count", None),
                ],
            ),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_ungrouped_multi_conjunct():
    """Q6 shape: several conjuncts, expression aggregate, no grouping."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection(
                [
                    call("ge", col(1), const_int(2000)),
                    call("lt", col(1), const_int(3000)),
                    call("ge", col(2), const_decimal(500, 2)),
                ]
            ),
            Aggregation(group_by=[], agg_funcs=[AggDescriptor("sum", call("multiply", col(2), col(4)))]),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_min_max_and_negative_values():
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("gt", col(1), const_int(1000))]),
            Aggregation(
                group_by=[col(3)],
                agg_funcs=[
                    AggDescriptor("min", col(4)),
                    AggDescriptor("max", col(4)),
                    AggDescriptor("sum", col(4)),
                ],
            ),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_nulls_in_group_key_and_values():
    """NULLs force tiles partial; NULL group keys form their own group."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, NCOLS),
            Selection([call("le", col(1), const_int(8000))]),
            Aggregation(
                group_by=[col(3)],
                agg_funcs=[
                    AggDescriptor("sum", col(1)),
                    AggDescriptor("count", col(1)),
                    AggDescriptor("avg", col(1)),
                    AggDescriptor("count", None),
                ],
            ),
        ],
        NFIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_unrecognized_conjunct_still_exact():
    """A non col-vs-const conjunct classifies everything partial; with the
    partial fraction at 100% the zone path declines and the generic warm
    path serves — response must still match."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("lt", col(1), call("plus", col(4), const_int(5000)))]),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("count", None)]),
        ],
        FIX,
    )
    assert warm.encode() == cpu.encode()


def test_zone_all_tiles_empty():
    """A predicate no row satisfies: zero groups, empty response."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("gt", col(1), const_int(10_000_000))]),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1))]),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_eq_and_flipped_conjuncts():
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            # const-on-the-left flavors exercise the flipped recognition
            Selection([call("ge", const_int(9000), col(1)), call("ne", col(2), const_decimal(600000, 2))]),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(4))]),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_post_agg_topn_limit():
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("le", col(1), const_int(9500))]),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1))]),
            TopN([(col(0), True)], 2),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_no_selection():
    """No conjuncts at all: every tile is full (minus pad tiles)."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1)), AggDescriptor("count", None)]),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_var_pop_served():
    """var_pop rides the zone path: int sums + f64 sum-of-squares per tile
    (the same carry layout as the CPU AggState) — covering bare int and
    NEGATIVE-valued columns, a DECIMAL column, and an EXPRESSION argument."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, COLS),
            Selection([call("le", col(1), const_int(7000))]),
            Aggregation(group_by=[col(3)], agg_funcs=[
                AggDescriptor("var_pop", col(1)),
                AggDescriptor("var_pop", col(4)),
                AggDescriptor("var_pop", col(2)),  # decimal(2)
                AggDescriptor("var_pop", call("multiply", col(1), col(4))),
                AggDescriptor("count", None),
            ]),
        ],
        FIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_var_pop_with_nulls():
    """NULL-bearing argument column: null tiles are forced partial and the
    partial path's live-mask gates the sum-of-squares."""
    cpu, warm, ev = run_warm(
        [
            TableScan(TABLE_ID, NCOLS),
            Selection([call("le", col(1), const_int(8000))]),
            Aggregation(group_by=[col(3)], agg_funcs=[
                AggDescriptor("var_pop", col(1)),
                AggDescriptor("count", col(1)),
            ]),
        ],
        NFIX,
    )
    assert zone_served(ev)
    assert warm.encode() == cpu.encode()


def test_zone_repeat_and_second_evaluator_share_layout():
    dag = DagRequest(
        executors=[
            TableScan(TABLE_ID, COLS),
            Selection([call("le", col(1), const_int(7000))]),
            Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1))]),
        ]
    )
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(KVS)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=2048)
    w1 = ev.run(None, cache=CACHE)
    w2 = ev.run(None, cache=CACHE)
    assert w1.encode() == w2.encode() == cpu.encode()
    ev2 = JaxDagEvaluator(dag, block_rows=512)
    assert ev2.run(None, cache=CACHE).encode() == cpu.encode()


@pytest.mark.parametrize("seed", [11, 22, 33, 44, 55, 66])
def test_zone_differential_fuzz(seed):
    """Randomized plans over randomized tables: every response must match
    the CPU pipeline byte-for-byte whichever path (zone / generic / fused)
    serves it.  Seeded — failures reproduce exactly."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(3000, 9000))
    from tikv_tpu.copr.datatypes import Column, EvalType

    cols_info = [
        ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
        ColumnInfo(2, FieldType.int64()),
        ColumnInfo(3, FieldType.decimal_type(2)),
        ColumnInfo(4, FieldType.varchar()),
        ColumnInfo(5, FieldType.varchar()),
        ColumnInfo(6, FieldType.int64()),
    ]
    v = rng.integers(-5000, 5000, n)
    d = rng.integers(0, 100000, n)
    tags_a = [b"aa", b"bb", b"cc"]
    tags_b = [b"xx", b"yy"]
    ta = rng.integers(0, 3, n)
    tb = rng.integers(0, 2, n)
    w = rng.integers(0, 1 << 30, n)
    null_v = rng.random(n) < float(rng.choice([0.0, 0.05, 0.3]))
    kvs = [
        (record_key(TABLE_ID, i), encode_row(cols_info[1:], [
            None if null_v[i] else int(v[i]), int(d[i]),
            tags_a[ta[i]], tags_b[tb[i]], int(w[i]),
        ]))
        for i in range(n)
    ]
    da = np.empty(3, dtype=object); da[:] = tags_a
    db = np.empty(2, dtype=object); db[:] = tags_b
    cache = ColumnBlockCache()
    B = int(rng.choice([1024, 2048, 4096]))
    handles = np.arange(n, dtype=np.int64)
    for s in range(0, n, B):
        e = min(s + B, n); m = e - s
        z = lambda: np.zeros(m, dtype=bool)
        cache.add([
            Column(EvalType.INT, handles[s:e], z()),
            Column(EvalType.INT, np.where(null_v[s:e], 0, v[s:e]), null_v[s:e].copy()),
            Column(EvalType.DECIMAL, d[s:e].copy(), z(), 2),
            Column(EvalType.BYTES, ta[s:e].astype(np.int64), z(), 0, da),
            Column(EvalType.BYTES, tb[s:e].astype(np.int64), z(), 0, db),
            Column(EvalType.INT, w[s:e].copy(), z()),
        ], m)
    cache.filled = True

    conj_pool = [
        lambda: call("le", col(1), const_int(int(rng.integers(-4000, 6000)))),
        lambda: call("gt", col(1), const_int(int(rng.integers(-6000, 4000)))),
        lambda: call("ge", col(2), const_decimal(int(rng.integers(0, 90000)), 2)),
        lambda: call("ne", col(1), const_int(int(rng.integers(-5000, 5000)))),
        lambda: call("lt", col(1), call("plus", col(5), const_int(100))),  # unrecognized
    ]
    agg_pool = [
        lambda: AggDescriptor("sum", col(1)),
        lambda: AggDescriptor("count", None),
        lambda: AggDescriptor("avg", col(2)),
        lambda: AggDescriptor("min", col(1)),
        lambda: AggDescriptor("max", col(2)),
        lambda: AggDescriptor("count", col(1)),
        lambda: AggDescriptor("sum", call("multiply", col(2), col(1))),
        lambda: AggDescriptor("var_pop", col(1)),
        # outside the zone op set: exercises the generic warm paths' byte
        # parity under the same randomized tables
        lambda: AggDescriptor("first", col(1)),
        lambda: AggDescriptor("bit_xor", col(5)),
        lambda: AggDescriptor("bit_and", col(5)),
        lambda: AggDescriptor("bit_or", col(5)),
    ]
    for _case in range(6):
        n_conj = int(rng.integers(0, 3))
        conds = [conj_pool[int(rng.integers(0, len(conj_pool)))]() for _ in range(n_conj)]
        group = [[], [col(3)], [col(3), col(4)]][int(rng.integers(0, 3))]
        aggs = [agg_pool[int(rng.integers(0, len(agg_pool)))]()
                for _ in range(int(rng.integers(1, 4)))]
        execs = [TableScan(TABLE_ID, cols_info)]
        if conds:
            execs.append(Selection(conds))
        execs.append(Aggregation(group_by=group, agg_funcs=aggs))
        dag = DagRequest(executors=execs)
        cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
        ev = JaxDagEvaluator(dag, block_rows=B)
        warm = ev.run(None, cache=cache)
        assert warm.encode() == cpu.encode(), (
            f"seed={seed} case={_case} conds={n_conj} group={len(group)} "
            f"aggs={[a.op for a in aggs]}"
        )

    # raw TopN with a varchar payload over the same cache (device top-K merge)
    for _t in range(2):
        desc = bool(rng.integers(0, 2))
        execs = [
            TableScan(TABLE_ID, cols_info),
            Selection([call("gt", col(1), const_int(int(rng.integers(-4000, 2000))))]),
            TopN([(col(1), desc), (col(0), not desc)], int(rng.integers(1, 60))),
        ]
        dag = DagRequest(executors=execs)
        cpu = BatchExecutorsRunner(dag, FixtureScanSource(kvs)).handle_request()
        dev = JaxDagEvaluator(dag, block_rows=B).run(None, cache=cache)
        assert dev.encode() == cpu.encode(), f"seed={seed} topn case={_t}"


def test_zone_failure_falls_through_to_generic(monkeypatch):
    """A zone-path exception (backend/compiler failure on a new accelerator)
    must fall through to the generic warm path and be remembered — never
    surface to the caller."""
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("le", col(1), const_int(7000))]),
        Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1))]),
    ])
    cpu = BatchExecutorsRunner(dag, FixtureScanSource(KVS)).handle_request()
    ev = JaxDagEvaluator(dag, block_rows=2048)
    zone = ev._zone_evaluator()
    calls = {"n": 0}

    def boom(cache, params):
        calls["n"] += 1
        raise RuntimeError("simulated backend failure")

    monkeypatch.setattr(zone, "_try_run_inner", boom)
    assert ev.run(None, cache=CACHE).encode() == cpu.encode()
    assert CACHE in zone._declined  # remembered: no retry storm
    assert zone.failed >= 1 and "simulated" in zone.last_error
    assert ev.run(None, cache=CACHE).encode() == cpu.encode()
    assert calls["n"] >= 1


def test_full_tile_program_shared_across_selection_constants():
    """Distinct selection CONSTANTS must reuse one compiled full-tile
    program: the full-tile fn never evaluates selection row-wise (the
    classification arrives as w_full), so keying its cache on the full plan
    signature churned the per-layout cache and recompiled identical XLA
    (advisor round 5)."""
    fix = mixed_table_kvs(6000, seed=7)
    _cols, _kvs, cache = fix
    consts = [3000, 4000, 5000, 6000]
    for c in consts:
        cpu, warm, ev = run_warm(
            [
                TableScan(TABLE_ID, fix[0]),
                Selection([call("le", col(1), const_int(c))]),
                Aggregation(group_by=[col(3)], agg_funcs=[AggDescriptor("sum", col(1))]),
            ],
            fix,
        )
        assert zone_served(ev)
        assert warm.encode() == cpu.encode()
    layout_fns = cache.blocks[0].device
    for sig, entry in layout_fns.items():
        if sig[0] == "zone_layout":
            fns = entry.__dict__.get("_zone_fns", {})
            full_keys = [k for k in fns if k[0] == "full"]
            assert len(full_keys) == 1, full_keys  # shared across constants
            partial_keys = [k for k in fns if k[0] == "partial"]
            assert len(partial_keys) >= 2  # partial programs DO depend on constants
            break
    else:
        raise AssertionError("no zone layout pinned")
