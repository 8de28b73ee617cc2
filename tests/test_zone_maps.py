"""Zone-map pruned execution: soundness + byte-identity coverage.

The contract under test is the ISSUE 16 acceptance list: per-block
min/max/null zone maps prune provably-empty blocks at trace time on every
device path, Limit/TopN ride zone-order early exits, and EVERY pruned serve
stays byte-identical to the unpruned device path and the CPU oracle —
across dict/RLE/bitpack/plain encodings, scan/selection/agg/topN/limit
plans, and mid-stream write-delta folds (stale-but-sound widening)."""

import numpy as np
import pytest

from copr_fixtures import TABLE_ID
from fixtures import delete_committed, put_committed

from tikv_tpu.copr import encoding as E
from tikv_tpu.copr import zone_maps as Z
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.cache import _Block
from tikv_tpu.copr.dag import (
    Aggregation, DagRequest, Limit, Selection, TableScan, TopN,
)
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.util.metrics import REGISTRY

# id (pk) | category (dict) | band (monotonic) | small (bitpack) | wide (plain)
COLUMNS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),
    ColumnInfo(3, FieldType.int64()),
    ColumnInfo(4, FieldType.int64()),
    ColumnInfo(5, FieldType.int64()),
]
NON_HANDLE = COLUMNS[1:]
CATS = [b"alpha", b"beta", b"gamma", b"delta"]


@pytest.fixture(autouse=True)
def _restore_prune_switch():
    yield
    Z.set_enabled(None)


def _row(i, rng):
    return [CATS[i % len(CATS)], i // 100, int(rng.integers(0, 120)),
            int(rng.integers(-(1 << 40), 1 << 40))]


def _engine(n=600, v2=False, seed=0):
    rng = np.random.default_rng(seed)
    eng = BTreeEngine()
    enc = encode_row_v2 if v2 else encode_row
    for i in range(n):
        put_committed(eng, record_key(TABLE_ID, i),
                      enc(NON_HANDLE, _row(i, rng)), 90, 100)
    return eng


def _req(dag, ts, ai, region_id=7):
    return CoprRequest(103, dag, [record_range(TABLE_ID)], ts,
                       context={"region_id": region_id,
                                "region_epoch": (1, 1), "apply_index": ai})


def _pair(eng, **kw):
    kw.setdefault("block_rows", 64)  # many blocks → real pruning decisions
    warm = Endpoint(LocalEngine(eng), enable_device=True, **kw)
    cold = Endpoint(LocalEngine(eng), enable_device=False,
                    enable_region_cache=False)
    return warm, cold


def _image(warm):
    [img] = warm.region_cache._images.values()
    return img


def _prune_count(path, outcome):
    return REGISTRY.counter("tikv_coprocessor_zone_prune_total", "").get(
        path=path, outcome=outcome)


# ---------------------------------------------------------------------------
# Direct units: prune soundness vs brute force, fold widening, TopN cutoff
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [11, 23, 37])
def test_prune_blocks_matches_brute_force(seed):
    """A pruned block must hold NO row satisfying every recognized conjunct
    — checked against a numpy brute-force evaluation of the same predicate
    over the decoded block payloads."""
    rng = np.random.default_rng(seed)
    eng = _engine(n=500, seed=seed)
    warm, _ = _pair(eng)
    ops = {"lt": np.less, "le": np.less_equal, "gt": np.greater,
           "ge": np.greater_equal, "eq": np.equal, "ne": np.not_equal}
    for _ in range(12):
        op = list(ops)[int(rng.integers(0, len(ops)))]
        ci, const = [(0, int(rng.integers(0, 500))),
                     (2, int(rng.integers(0, 6))),
                     (3, int(rng.integers(0, 120)))][int(rng.integers(0, 3))]
        dag = DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Selection([call(op, col(ci), const_int(const))])])
        warm.handle_request(_req(dag, 200, 3))
        cache = _image(warm).block_cache
        ev, params = warm._bind(dag)
        keep = Z.prune_blocks(cache, ev.bound_sel_rpns(params))
        if keep is None:
            continue
        for bi, blk in enumerate(cache.blocks):
            if keep[bi]:
                continue
            data = np.asarray(E.decoded_data(blk.cols[ci]))[:blk.n_valid]
            nulls = np.asarray(E.decoded_nulls(blk.cols[ci]))[:blk.n_valid]
            hits = ops[op](data, const) & ~nulls
            assert not hits.any(), (op, ci, const, bi)


def _zblock(lo, hi, n, nulls=0):
    b = _Block(cols=[], n_valid=n)
    b.zones = {3: Z.ColumnZone(lo, hi, nulls, nulls, n)}
    return b


def test_topn_cutoff_order_ascending_and_descending():
    blocks = [_zblock(0, 9, 10), _zblock(10, 19, 10), _zblock(20, 29, 10)]
    keep = np.ones(3, dtype=bool)
    # ascending, k=5: block 0 alone guarantees 5 rows <= 9, so every block
    # with lo > 9 provably misses the top-k
    out = Z.topn_cutoff_order(blocks, keep, 3, False, 5)
    assert list(out) == [True, False, False]
    # descending, k=5: block 2 guarantees 5 rows >= 20 → blocks below exit
    out = Z.topn_cutoff_order(blocks, keep, 3, True, 5)
    assert list(out) == [False, False, True]
    # k beyond the bounded rows: no exit is provable
    assert Z.topn_cutoff_order(blocks, keep, 3, False, 31) is None
    # a block with possible nulls can never exit ascending (nulls sort first)
    nully = [_zblock(0, 9, 10), _zblock(20, 29, 10, nulls=3)]
    out = Z.topn_cutoff_order(nully, np.ones(2, dtype=bool), 3, False, 5)
    assert out is None or bool(out[1])
    # untracked order column → no sound bound at all
    blocks[1].zones = {}
    assert Z.topn_cutoff_order(blocks, keep, 3, False, 5) is None


def test_kill_switch_disables_pruning():
    eng = _engine(n=200)
    warm, _ = _pair(eng)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        Selection([call("ge", col(0), const_int(199))])])
    warm.handle_request(_req(dag, 200, 3))
    cache = _image(warm).block_cache
    ev, params = warm._bind(dag)
    assert Z.prune_blocks(cache, ev.bound_sel_rpns(params)) is not None
    Z.set_enabled(False)
    assert Z.prune_blocks(cache, ev.bound_sel_rpns(params)) is None


# ---------------------------------------------------------------------------
# Zone soundness under seeded write-delta chaos
# ---------------------------------------------------------------------------


def _assert_zones_sound(cache):
    for blk in cache.blocks:
        if not blk.zones:
            continue
        for ci, z in blk.zones.items():
            data = np.asarray(E.decoded_data(blk.cols[ci]))[:blk.n_valid]
            if data.dtype == object:
                continue
            nulls = np.asarray(E.decoded_nulls(blk.cols[ci]))[:blk.n_valid]
            live = data[~nulls]
            nn = int(nulls.sum())
            assert z.null_lo <= nn <= z.null_hi, (ci, z, nn)
            if len(live):
                assert z.lo is not None and z.lo <= live.min(), (ci, z)
                assert z.hi >= live.max(), (ci, z)


@pytest.mark.parametrize("seed", [5, 17])
def test_zones_stay_sound_under_write_delta_chaos(seed):
    """Rounds of random in-place updates, inserts, and deletes fold into a
    warm image; after every fold each block's zones must still bound the
    actual resident values (stale-but-sound), and pruned serving must still
    answer the oracle's bytes."""
    rng = np.random.default_rng(seed)
    n = 400
    eng = _engine(n=n, seed=seed)
    warm, cold = _pair(eng)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        Selection([call("ge", col(3), const_int(60))])])
    warm.handle_request(_req(dag, 200, 3))
    ts, ai = 200, 3
    for _round in range(4):
        ts, ai = ts + 100, ai + 1
        for _ in range(int(rng.integers(1, 6))):
            h = int(rng.integers(0, n))
            put_committed(eng, record_key(TABLE_ID, h),
                          encode_row(NON_HANDLE, _row(h, rng)),
                          ts - 50, ts - 40)
        if rng.integers(0, 2):
            put_committed(eng, record_key(TABLE_ID, n + _round),
                          encode_row(NON_HANDLE, _row(n + _round, rng)),
                          ts - 50, ts - 40)
        if rng.integers(0, 2):
            delete_committed(eng, record_key(TABLE_ID, int(rng.integers(0, n))),
                             ts - 50, ts - 40)
        r = warm.handle_request(_req(dag, ts, ai))
        assert r.data == cold.handle_request(_req(dag, ts, ai)).data
        cache = _image(warm).block_cache
        Z.ensure_zones(cache)
        _assert_zones_sound(cache)


# ---------------------------------------------------------------------------
# End-to-end byte identity: pruned vs unpruned vs CPU oracle
# ---------------------------------------------------------------------------


def _plans(rng, n):
    sel = lambda: [call("ge", col(0), const_int(n - n // 10)),
                   call("gt", col(3), const_int(int(rng.integers(0, 120))))]
    return [
        DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                              Selection(sel()), Limit(1 << 20)]),
        DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                              Selection(sel()),
                              Limit(int(rng.integers(1, 30)))]),
        DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Selection([call("eq", col(2), const_int(int(rng.integers(0, 8))))]),
            Aggregation([col(1)], [AggDescriptor("sum", col(3)),
                                   AggDescriptor("count", None)])]),
        DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Selection(sel()),
            TopN([(col(3), bool(rng.integers(0, 2))), (col(0), False)],
                 int(rng.integers(1, 25)))]),
        DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            TopN([(col(0), bool(rng.integers(0, 2)))],
                 int(rng.integers(1, 40)))]),
    ]


@pytest.mark.parametrize("v2", [False, True], ids=["rowv1", "rowv2"])
@pytest.mark.parametrize("seed", [101, 202])
def test_pruned_serving_byte_identical_fuzz(seed, v2):
    """Selective scan / Limit / agg / TopN plans over a warm image answer
    the SAME bytes with pruning on, with pruning force-disabled, and on the
    CPU oracle — before and after a mid-stream delta fold."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(300, 600))
    eng = _engine(n=n, v2=v2, seed=seed)
    warm, cold = _pair(eng)

    def check(ts, ai):
        for dag in _plans(rng, n):
            oracle = cold.handle_request(_req(dag, ts, ai)).data
            Z.set_enabled(True)
            pruned = warm.handle_request(_req(dag, ts, ai))
            Z.set_enabled(False)
            unpruned = warm.handle_request(_req(dag, ts, ai))
            Z.set_enabled(None)
            assert pruned.data == oracle, (
                seed, v2, ts, [type(e).__name__ for e in dag.executors])
            assert unpruned.data == oracle, (
                seed, v2, ts, [type(e).__name__ for e in dag.executors])

    before = _prune_count("unary", "pruned")
    check(200, 3)
    assert _prune_count("unary", "pruned") > before, \
        "selective plans over a warm image pruned nothing"
    enc = encode_row_v2 if v2 else encode_row
    for _ in range(int(rng.integers(2, 6))):
        h = int(rng.integers(0, n))
        put_committed(eng, record_key(TABLE_ID, h),
                      enc(NON_HANDLE, [
                          CATS[int(rng.integers(0, len(CATS)))],
                          int(rng.integers(0, 1 << int(rng.choice([3, 50])))),
                          int(rng.integers(0, 200)),
                          int(rng.integers(-(1 << 40), 1 << 40))]),
                      210, 220)
    put_committed(eng, record_key(TABLE_ID, n + 50),
                  enc(NON_HANDLE, _row(n + 50, rng)), 210, 220)
    delete_committed(eng, record_key(TABLE_ID, 1), 210, 220)
    check(300, 4)
    check(300, 4)  # pure hits over the folded images


def test_limit_scan_prunes_on_device():
    """A selective Limit-bearing scan serves warm ON DEVICE with blocks
    pruned (counted), byte-identical to the oracle."""
    eng = _engine(n=600)
    warm, cold = _pair(eng)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        Selection([call("ge", col(0), const_int(540))]),
        Limit(25)])
    oracle = cold.handle_request(_req(dag, 200, 3)).data
    warm.handle_request(_req(dag, 200, 3))
    before = _prune_count("unary", "pruned")
    r = warm.handle_request(_req(dag, 200, 3))
    assert r.from_device and r.data == oracle
    assert _prune_count("unary", "pruned") > before


def test_topn_zone_order_early_exit():
    """A bare-key TopN over a warm image exits blocks that provably cannot
    reach the top-k (counted as early_exit), byte-identical both ways."""
    eng = _engine(n=600)
    warm, cold = _pair(eng)
    for desc in (False, True):
        dag = DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            TopN([(col(0), desc)], 10)])
        oracle = cold.handle_request(_req(dag, 200, 3)).data
        warm.handle_request(_req(dag, 200, 3))
        before = _prune_count("unary", "early_exit")
        r = warm.handle_request(_req(dag, 200, 3))
        assert r.from_device and r.data == oracle, desc
        assert _prune_count("unary", "early_exit") > before, desc


def test_device_plan_decline_named_for_limit_topn():
    """A Limit/TopN-bearing plan the device declines is counted under
    tikv_coprocessor_encoded_decline_total{path=device_plan} with the
    eligibility gate's named cause — never a silent CPU fallback."""
    from tikv_tpu.copr import jax_eval

    eng = _engine(n=100)
    warm, cold = _pair(eng)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        TopN([(col(3), False)], 5000)])  # beyond the device TopN bound
    assert jax_eval.decline_cause(dag) == "topn_limit_too_large"
    before = REGISTRY.counter(
        "tikv_coprocessor_encoded_decline_total", "").get(
        path="device_plan", cause="topn_limit_too_large")
    r = warm.handle_request(_req(dag, 200, 3))
    assert not r.from_device
    assert r.data == cold.handle_request(_req(dag, 200, 3)).data
    assert REGISTRY.counter(
        "tikv_coprocessor_encoded_decline_total", "").get(
        path="device_plan", cause="topn_limit_too_large") == before + 1
    # an eligible plan names no cause
    ok = DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                               TopN([(col(3), False)], 10)])
    assert jax_eval.decline_cause(ok) is None


def test_observatory_profiles_pruned_blocks():
    """Warm pruned serves report blocks examined/pruned into the per-sig
    profile, and the floor carries the pruned fraction for obs_diff."""
    from tikv_tpu.copr.observatory import OBSERVATORY, floor_diff

    OBSERVATORY.reset()
    eng = _engine(n=600)
    warm, _ = _pair(eng)
    dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        Selection([call("ge", col(0), const_int(540))])])
    for _ in range(4):
        warm.handle_request(_req(dag, 200, 3))
    snap = OBSERVATORY.snapshot()
    views = [v for entry in snap["sigs"].values()
             for pk, v in entry["paths"].items()
             if v.get("blocks_pruned", 0) > 0]
    assert views, "no profile recorded pruned blocks"
    assert all(v["blocks_examined"] >= v["blocks_pruned"] for v in views)
    floor = OBSERVATORY.floor(min_count=3)
    frs = [p.get("pruned_fraction") for sig in floor["sigs"].values()
           for p in sig.values() if p.get("pruned_fraction")]
    assert frs and all(0 < f <= 1 for f in frs)
    # pruning regression: same throughput, collapsed pruned fraction → flag
    import copy

    cur = copy.deepcopy(floor)
    for sig in cur["sigs"].values():
        for p in sig.values():
            p.pop("pruned_fraction", None)
    verdict = floor_diff(floor, cur)
    assert any(r.get("kind") == "pruning" for r in verdict["regressions"])
