"""Compressed device-resident columns: end-to-end differential coverage.

The contract under test is the ISSUE 10 acceptance list: encoded-resident
region images must serve BYTE-IDENTICALLY to the CPU oracle on every path
(unary warm, fused same-region batch, cross-region vmapped), through
mid-stream delta folds and encoding-breaking updates, across dict/RLE/
bitpacked columns × rowv1/rowv2 × scan/selection/agg/topN — and an equal
byte budget must keep ≥2× more regions warm encoded than decoded, with the
integrity plane detecting encoded-payload corruption."""

import random

import numpy as np
import pytest

from copr_fixtures import TABLE_ID
from fixtures import delete_committed, put_committed

from tikv_tpu.copr import encoding as E
from tikv_tpu.copr import jax_eval
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import (
    Aggregation, DagRequest, Limit, Selection, TableScan, TopN,
)
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.region_cache import RegionColumnCache
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.rpn import call, col, const_bytes, const_int
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.util import chaos
from tikv_tpu.util.metrics import REGISTRY

# id (pk) | category (dict) | runlen (rle) | small (bitpack) | wide (plain)
COLUMNS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),
    ColumnInfo(3, FieldType.int64()),
    ColumnInfo(4, FieldType.int64()),
    ColumnInfo(5, FieldType.int64()),
]
NON_HANDLE = COLUMNS[1:]
CATS = [b"alpha", b"beta", b"gamma", b"delta"]


def _row(i, rng):
    return [CATS[i % len(CATS)], i // 100, int(rng.integers(0, 120)),
            int(rng.integers(-(1 << 40), 1 << 40))]


def _engine(n=600, v2=False, seed=0, table_id=TABLE_ID):
    rng = np.random.default_rng(seed)
    eng = BTreeEngine()
    enc = encode_row_v2 if v2 else encode_row
    for i in range(n):
        put_committed(eng, record_key(table_id, i),
                      enc(NON_HANDLE, _row(i, rng)), 90, 100)
    return eng


def _req(dag, ts, ai, region_id=7, ranges=None):
    return CoprRequest(103, dag, ranges or [record_range(TABLE_ID)], ts,
                       context={"region_id": region_id,
                                "region_epoch": (1, 1), "apply_index": ai})


def _pair(eng, **kw):
    warm = Endpoint(LocalEngine(eng), enable_device=True, **kw)
    cold = Endpoint(LocalEngine(eng), enable_device=False,
                    enable_region_cache=False)
    return warm, cold


def _dags():
    return {
        "scan": DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                                      Limit(1 << 20)]),
        "selection": DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Selection([call("gt", col(3), const_int(40)),
                       call("le", col(2), const_int(4))]),
        ]),
        "agg": DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Aggregation([col(1)], [AggDescriptor("sum", col(3)),
                                   AggDescriptor("min", col(4)),
                                   AggDescriptor("count", None)]),
        ]),
        "topn": DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Selection([call("ge", col(3), const_int(10))]),
            TopN([(col(3), True), (col(0), False)], 25),
        ]),
    }


def _image(warm):
    [img] = warm.region_cache._images.values()
    return img


@pytest.mark.parametrize("v2", [False, True], ids=["rowv1", "rowv2"])
@pytest.mark.parametrize("name", ["scan", "selection", "agg", "topn"])
def test_encoded_serve_byte_identical_through_deltas(v2, name):
    """Every plan shape over an ENCODED-resident image answers the CPU
    oracle's bytes — warm, then again after a delta fold that includes an
    in-place bitpack patch, an encoding-BREAKING update (RLE column and an
    out-of-frame bitpack value), a new dictionary value, an insert and a
    delete (structural repack + re-encode)."""
    dag = _dags()[name]
    eng = _engine(v2=v2)
    warm, cold = _pair(eng)
    r0 = warm.handle_request(_req(dag, 200, 3))
    assert r0.metrics["region_cache"] == "miss"
    img = _image(warm)
    assert img.encodings, "stats pass encoded nothing"
    kinds = set(img.encodings.values())
    assert {"dict", "rle", "bp"} <= kinds
    assert r0.data == cold.handle_request(_req(dag, 200, 3)).data
    r1 = warm.handle_request(_req(dag, 200, 3))
    assert r1.metrics["region_cache"] == "hit" and r1.data == r0.data

    enc = encode_row_v2 if v2 else encode_row
    # in-place within-frame update (bitpack patch), RLE-breaking update,
    # out-of-frame value, new dictionary value
    put_committed(eng, record_key(TABLE_ID, 5),
                  enc(NON_HANDLE, [b"beta", 0, 119, 1]), 210, 220)
    put_committed(eng, record_key(TABLE_ID, 6),
                  enc(NON_HANDLE, [b"omega", 999999, 1 << 50, 2]), 210, 220)
    r2 = warm.handle_request(_req(dag, 300, 4))
    assert r2.metrics["region_cache"] in ("delta", "wt_delta")
    assert r2.data == cold.handle_request(_req(dag, 300, 4)).data

    # structural: insert + delete → repack → re-encode from fresh stats
    put_committed(eng, record_key(TABLE_ID, 900),
                  enc(NON_HANDLE, [b"alpha", 9, 50, 3]), 310, 320)
    delete_committed(eng, record_key(TABLE_ID, 0), 310, 320)
    r3 = warm.handle_request(_req(dag, 400, 5))
    assert r3.metrics["region_cache"] in ("delta", "wt_delta")
    assert r3.data == cold.handle_request(_req(dag, 400, 5)).data
    img = _image(warm)
    assert img.encodings, "repack lost the encodings"
    r4 = warm.handle_request(_req(dag, 400, 5))
    assert r4.metrics["region_cache"] == "hit" and r4.data == r3.data


def test_budget_accounts_encoded_bytes_and_doubles_capacity():
    """THE density claim: at one fixed byte budget, encoded residency keeps
    ≥2× the regions warm that decoded residency does."""
    eng = _engine(n=900)
    budget = None
    for encode in (False, True):
        rc = RegionColumnCache(byte_budget=1 << 62, max_regions=64,
                               encode_columns=encode)
        warm = Endpoint(LocalEngine(eng), enable_device=True, region_cache=rc)
        warm.handle_request(_req(_dags()["scan"], 200, 3, region_id=1))
        img = _image(warm)
        if not encode:
            budget = img.nbytes  # decoded size of ONE region
            decoded_bytes = img.nbytes
        else:
            encoded_bytes = img.nbytes
    assert encoded_bytes * 2 <= decoded_bytes, (encoded_bytes, decoded_bytes)

    resident = {}
    for encode in (False, True):
        rc = RegionColumnCache(byte_budget=budget * 3, max_regions=64,
                               encode_columns=encode)
        warm = Endpoint(LocalEngine(eng), enable_device=True, region_cache=rc)
        for rid in range(1, 13):
            warm.handle_request(_req(_dags()["scan"], 200, 3, region_id=rid))
        resident[encode] = len(rc)
    assert resident[True] >= 2 * resident[False], resident


def test_gauges_report_encoded_bytes_and_ratio():
    eng = _engine()
    pinned = {}
    for encode in (True, False):
        rc = RegionColumnCache(block_rows=1024, encode_columns=encode)
        warm = Endpoint(LocalEngine(eng), enable_device=True,
                        region_cache=rc, block_rows=1024)
        # selection (no zone layout — THAT pins its own clustered geometry)
        # so encoded and decoded runs pin the same per-block signature shape
        warm.handle_request(_req(_dags()["selection"], 200, 3))
        warm.handle_request(_req(_dags()["selection"], 200, 3))  # pins arrays
        img = _image(warm)
        assert REGISTRY._metrics[
            "tikv_coprocessor_region_cache_bytes"].get() == img.nbytes
        if encode:
            ratio = REGISTRY._metrics[
                "tikv_coprocessor_region_cache_compression_ratio"].get()
            assert ratio >= 2.0
        rc._gauge_bytes()
        pinned[encode] = REGISTRY._metrics[
            "tikv_coprocessor_region_cache_device_pinned_bytes"].get()
        assert pinned[encode] > 0
    # TRUE HBM bytes: the encoded pins (narrow lanes + runs) cost under
    # half the decoded pins for the SAME plan and block geometry
    assert pinned[True] * 2 <= pinned[False], pinned


def test_fused_and_xregion_paths_serve_encoded_images():
    """The same-region fused batch and the cross-region vmapped program both
    consume the encoded pins (descriptors ride the jit keys) and stay
    byte-identical to per-request serving."""
    eng = _engine()
    warm, cold = _pair(eng)
    agg = _dags()["agg"]
    lo, hi = record_range(TABLE_ID)
    mid = record_key(TABLE_ID, 300)
    ra, rb = [(lo, mid)], [(mid, hi)]
    warm.handle_request(_req(agg, 200, 3, region_id=1, ranges=ra))
    warm.handle_request(_req(agg, 200, 3, region_id=2, ranges=rb))
    caches = [img.block_cache
              for img in warm.region_cache._images.values()]
    assert len(caches) == 2
    ev, params = warm._bind(agg)
    before = REGISTRY.counter(
        "tikv_coprocessor_encoded_path_total", "").get(
        path="xregion", decision="encoded")
    outs = jax_eval.run_xregion_cached(ev, caches, params)
    assert REGISTRY.counter(
        "tikv_coprocessor_encoded_path_total", "").get(
        path="xregion", decision="encoded") == before + 1
    assert outs[0].encode() == cold.handle_request(
        _req(agg, 200, 3, ranges=ra)).data
    assert outs[1].encode() == cold.handle_request(
        _req(agg, 200, 3, ranges=rb)).data

    # fused same-region batch over the encoded image
    agg2 = DagRequest(executors=[
        TableScan(TABLE_ID, COLUMNS),
        Aggregation([], [AggDescriptor("count", None),
                         AggDescriptor("max", col(3))]),
    ])
    ev2, params2 = warm._bind(agg2)
    # rebuild a full-range image for the fused pair
    warm.handle_request(_req(agg, 200, 3, region_id=9))
    cache9 = next(img.block_cache
                  for k, img in warm.region_cache._images.items()
                  if k[0] == 9)
    fused = jax_eval.run_batch_cached([ev, ev2], cache9, [params, params2])
    assert fused[0].encode() == cold.handle_request(_req(agg, 200, 3)).data
    assert fused[1].encode() == cold.handle_request(_req(agg2, 200, 3)).data


def test_xregion_enc_mismatch_decode_ships_byte_identically():
    """Regions whose encodings diverged (one demoted) decode-ship the batch
    — counted, never silent — and bytes stay identical."""
    eng = _engine()
    warm, cold = _pair(eng)
    agg = _dags()["agg"]
    lo, hi = record_range(TABLE_ID)
    mid = record_key(TABLE_ID, 300)
    ra, rb = [(lo, mid)], [(mid, hi)]
    warm.handle_request(_req(agg, 200, 3, region_id=1, ranges=ra))
    warm.handle_request(_req(agg, 200, 3, region_id=2, ranges=rb))
    caches = [img.block_cache for img in warm.region_cache._images.values()]
    E.demote_column(caches[0], 3, "inplace_update")  # break a SHIPPED lane
    before = REGISTRY.counter(
        "tikv_coprocessor_encoded_decline_total", "").get(
        path="xregion", cause="enc_mismatch")
    ev, params = warm._bind(agg)
    outs = jax_eval.run_xregion_cached(ev, caches, params)
    assert REGISTRY.counter(
        "tikv_coprocessor_encoded_decline_total", "").get(
        path="xregion", cause="enc_mismatch") == before + 1
    assert outs[0].encode() == cold.handle_request(
        _req(agg, 200, 3, ranges=ra)).data
    assert outs[1].encode() == cold.handle_request(
        _req(agg, 200, 3, ranges=rb)).data


def test_dict_rewrite_serves_bytes_predicates_on_device():
    """equality / IN / range bytes predicates rewrite into the sorted
    dictionary's code space and serve warm on the device, byte-identical;
    a dictionary grown unsorted by a delta declines range ops (counted)."""
    eng = _engine()
    warm, cold = _pair(eng)
    conds = [
        call("eq", col(1), const_bytes(b"beta")),
        call("in", col(1), const_bytes(b"alpha"), const_bytes(b"nope")),
        call("lt", col(1), const_bytes(b"c")),
        call("ge", col(1), const_bytes(b"delta")),
    ]
    for cond in conds:
        dag = DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                                    Selection([cond])])
        warm.handle_request(_req(dag, 200, 3))
        r = warm.handle_request(_req(dag, 200, 3))
        assert r.from_device, cond.op
        assert r.data == cold.handle_request(_req(dag, 200, 3)).data

    # a delta introduces a NEW dictionary value (appended → unsorted):
    # range ops must now decline to the CPU path, still byte-identical
    put_committed(eng, record_key(TABLE_ID, 3),
                  enc_row := encode_row(NON_HANDLE, [b"aardvark", 0, 1, 1]),
                  210, 220)
    dag = DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                                Selection([call("lt", col(1),
                                                const_bytes(b"c"))])])
    warm.handle_request(_req(dag, 300, 4))  # folds the delta
    before = REGISTRY.counter(
        "tikv_coprocessor_encoded_rewrite_total", "").get(outcome="declined")
    r = warm.handle_request(_req(dag, 300, 4))
    assert not r.from_device
    assert r.data == cold.handle_request(_req(dag, 300, 4)).data
    assert REGISTRY.counter(
        "tikv_coprocessor_encoded_rewrite_total", "").get(
        outcome="declined") >= before + 1


def test_encoded_corruption_detected_by_shadow_and_scrub():
    """corrupt_image(mode="encoded") flips ENCODED payload bytes; a
    shadow-sampled serve detects it, serves the oracle bytes, and
    quarantines; the deep scrub detects the same flip independently."""
    eng = _engine()
    warm, cold = _pair(eng, shadow_sample=1)
    dag = _dags()["scan"]
    oracle = cold.handle_request(_req(dag, 200, 3)).data
    warm.handle_request(_req(dag, 200, 3))
    r1 = warm.handle_request(_req(dag, 200, 3))
    assert r1.from_device and r1.data == oracle

    info = chaos.corrupt_image(warm.region_cache, random.Random(5),
                               mode="encoded")
    assert info is not None and info["mode"] == "encoded"
    r2 = warm.handle_request(_req(dag, 200, 3))
    assert r2.data == oracle and not r2.from_device
    ledger = warm.region_cache.quarantine_ledger
    assert ledger and ledger[-1]["stage"] == "shadow_read"

    # independent detection: deep scrub on a freshly corrupted image
    warm2, _ = _pair(eng)
    warm2.handle_request(_req(dag, 200, 3))
    info = chaos.corrupt_image(warm2.region_cache, random.Random(6),
                               mode="encoded")
    assert info is not None
    res = warm2.scrubber.scrub_once()
    assert any(r.get("outcome") == "mismatch" for r in res), res
    assert warm2.region_cache.quarantine_ledger
    # quarantine → rebuild → byte-identical again
    r3 = warm2.handle_request(_req(dag, 200, 3))
    assert r3.data == oracle


def test_delta_folds_leave_no_decode_caches():
    """In-place delta folds must not leave full decode caches on encoded
    columns — the budget counts ENCODED bytes, so a cached decode would be
    unaccounted host memory on every written-to image."""
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_dags()["scan"], 200, 3))
    put_committed(eng, record_key(TABLE_ID, 7),
                  encode_row(NON_HANDLE, [b"beta", 0, 60, 2]), 210, 220)
    r = warm.handle_request(_req(_dags()["scan"], 300, 4))
    assert r.metrics["region_cache"] in ("delta", "wt_delta")
    assert r.data == cold.handle_request(_req(_dags()["scan"], 300, 4)).data
    img = _image(warm)
    cached = [
        (ci, c.kind) for b in img.block_cache.blocks
        for ci, c in enumerate(b.cols)
        if isinstance(c, E.EncodedColumn) and c._data is not None
    ]
    assert not cached, cached


def test_encode_columns_kill_switch_stays_decoded():
    eng = _engine()
    rc = RegionColumnCache(encode_columns=False)
    warm = Endpoint(LocalEngine(eng), enable_device=True, region_cache=rc)
    _, cold = _pair(eng)
    dag = _dags()["scan"]
    r = warm.handle_request(_req(dag, 200, 3))
    assert r.data == cold.handle_request(_req(dag, 200, 3)).data
    img = _image(warm)
    assert not img.encodings and not img.encode_enabled
    assert not any(isinstance(c, E.EncodedColumn)
                   for b in img.block_cache.blocks for c in b.cols)


@pytest.mark.parametrize("seed", [101, 202, 303])
def test_compressed_differential_fuzz(seed):
    """Randomized plans over randomized encodable tables, rowv1 and rowv2:
    warm encoded serving == warm decoded serving == CPU oracle bytes,
    including a mid-stream delta fold between the two serve rounds."""
    rng = np.random.default_rng(seed)
    v2 = bool(rng.integers(0, 2))
    n = int(rng.integers(300, 800))
    eng = _engine(n=n, v2=v2, seed=seed)
    warm_enc = Endpoint(LocalEngine(eng), enable_device=True)
    warm_dec = Endpoint(LocalEngine(eng), enable_device=True,
                        encode_columns=False)
    cold = Endpoint(LocalEngine(eng), enable_device=False,
                    enable_region_cache=False)

    conj_pool = [
        lambda: call("gt", col(3), const_int(int(rng.integers(0, 120)))),
        lambda: call("le", col(2), const_int(int(rng.integers(0, n // 100 + 1)))),
        lambda: call("ne", col(0), const_int(int(rng.integers(0, n)))),
        lambda: call("eq", col(1), const_bytes(
            CATS[int(rng.integers(0, len(CATS)))])),
    ]
    agg_pool = [
        lambda: AggDescriptor("sum", col(3)),
        lambda: AggDescriptor("count", None),
        lambda: AggDescriptor("min", col(4)),
        lambda: AggDescriptor("max", col(2)),
        lambda: AggDescriptor("avg", col(3)),
    ]

    def plans():
        out = [DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                                     Limit(1 << 20)])]
        conds = [conj_pool[int(rng.integers(0, len(conj_pool)))]()
                 for _ in range(int(rng.integers(1, 3)))]
        out.append(DagRequest(executors=[TableScan(TABLE_ID, COLUMNS),
                                         Selection(conds)]))
        group = [[], [col(1)], [col(2)]][int(rng.integers(0, 3))]
        aggs = [agg_pool[int(rng.integers(0, len(agg_pool)))]()
                for _ in range(int(rng.integers(1, 3)))]
        out.append(DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            Aggregation(group_by=group, agg_funcs=aggs)]))
        out.append(DagRequest(executors=[
            TableScan(TABLE_ID, COLUMNS),
            TopN([(col(3), bool(rng.integers(0, 2))), (col(0), False)],
                 int(rng.integers(1, 40)))]))
        return out

    def check(ts, ai):
        for dag in plans():
            oracle = cold.handle_request(_req(dag, ts, ai)).data
            for ep in (warm_enc, warm_dec):
                got = ep.handle_request(_req(dag, ts, ai))
                assert got.data == oracle, (
                    f"seed={seed} v2={v2} ts={ts} "
                    f"execs={[type(e).__name__ for e in dag.executors]}")

    check(200, 3)
    # mid-stream delta: updates (some encoding-breaking), insert, delete
    enc = encode_row_v2 if v2 else encode_row
    for _ in range(int(rng.integers(1, 6))):
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
