"""Integrity plane: fingerprints, scrubber, shadow reads, quarantine/repair.

The contract under test is the ISSUE 9 acceptance list (docs/integrity.md):

* the image fingerprint fold over wt_delta / scan_delta applies equals a
  full recompute AND the engine oracle on every tested schedule;
* Checksum (tp=105) served off a warm image fingerprint is byte-identical
  to the CPU-oracle scan;
* with ``corrupt_image`` faults injected mid-traffic, every mismatch is
  detected by the scrubber or a shadow read, ZERO wrong bytes reach any
  client (the shadow path serves the CPU result), the image quarantines
  and rebuilds, and post-heal warm serves are byte-identical;
* split/merge/conf-change invalidation holds under a seeded Nemesis
  schedule — no stale-epoch image is ever served;
* the raft consistency check counts per result, rides the derived-plane
  scrub, and surfaces through the debug RPCs.
"""

import random

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID, rowv2_rows_decoded
from fixtures import put_committed

from tikv_tpu.copr import integrity
from tikv_tpu.copr.analyze import checksum_range, crc64
from tikv_tpu.copr.dag import DagRequest, Limit, TableScan
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.raft.cluster import Cluster, FIRST_REGION_ID
from tikv_tpu.storage.engine import CF_WRITE, WriteBatch
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util import chaos
from tikv_tpu.util.metrics import REGISTRY
from tikv_tpu.util.chaos import Nemesis

NON_HANDLE = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]


def _engine(n=64, v2=False):
    from tikv_tpu.storage.btree_engine import BTreeEngine

    eng = BTreeEngine()
    enc = encode_row_v2 if v2 else encode_row
    for i in range(n):
        name = [b"apple", b"banana", b"cherry"][i % 3]
        put_committed(eng, record_key(TABLE_ID, i),
                      enc(NON_HANDLE, [name, i * 7 % 23, 100 + i]), 90, 100)
    return eng


def _scan_dag():
    return DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), Limit(1 << 20)])


def _req(dag, ts, apply_index, region_id=7, epoch=(1, 1), tp=103):
    return CoprRequest(
        tp, dag, [record_range(TABLE_ID)], ts,
        context={"region_id": region_id, "region_epoch": epoch,
                 "apply_index": apply_index},
    )


def _checksum_req(ts, apply_index, region_id=7):
    return _req(None, ts, apply_index, region_id=region_id, tp=105)


def _pair(eng, **kw):
    warm = Endpoint(LocalEngine(eng), enable_device=True, **kw)
    cold = Endpoint(LocalEngine(eng), enable_device=False,
                    enable_region_cache=False)
    return warm, cold


def _the_image(ep):
    cache = ep.region_cache
    (key,) = list(cache._images)
    return key, cache._images[key]


# ---------------------------------------------------------------------------
# fingerprint primitives
# ---------------------------------------------------------------------------

def test_crc64_batch_matches_scalar():
    rng = random.Random(0)
    rows = [bytes(rng.randrange(256) for _ in range(rng.randrange(0, 70)))
            for _ in range(257)]
    got = integrity.crc64_batch(rows)
    want = np.array([crc64(r) for r in rows], dtype=np.uint64)
    assert (got == want).all()
    assert integrity.crc64_batch([]).size == 0


def test_crc64_batch_bounded_on_skewed_lengths(monkeypatch):
    """A jumbo blob among small rows must take the scalar path (never a step
    a byte of the blob for the whole batch), and the small rows the batch's
    — both paths stay bit-identical to the scalar crc64."""
    scalar = []
    monkeypatch.setattr("tikv_tpu.copr.analyze.crc64",
                        lambda data, _crc64=crc64: scalar.append(len(data)) or _crc64(data))
    rng = random.Random(1)
    rows = [bytes(rng.randrange(256) for _ in range(rng.randrange(1, 40)))
            for _ in range(64)]
    rows[7] = bytes(rng.randrange(256)
                    for _ in range(integrity._JUMBO_ROW + 500))
    rows[40] = b""
    got = integrity.crc64_batch(rows)
    assert scalar == [integrity._JUMBO_ROW + 500]
    want = np.array([crc64(r) for r in rows], dtype=np.uint64)
    assert (got == want).all()


def test_shadow_sampler_deterministic_cadence(monkeypatch):
    s = integrity.ShadowSampler(4)
    picks = [s.pick("unary") for _ in range(9)]
    assert picks == [False, False, False, True] * 2 + [False]
    assert integrity.ShadowSampler(0).pick("unary") is False
    monkeypatch.setenv("TIKV_TPU_SHADOW_SAMPLE", "2")
    s2 = integrity.ShadowSampler()
    assert [s2.pick("x") for _ in range(4)] == [False, True, False, True]


# ---------------------------------------------------------------------------
# fold == recompute == oracle across delta schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("v2", [False, True], ids=["rowv1", "rowv2"])
def test_fingerprint_fold_matches_recompute_and_oracle(v2):
    """Build → hit → in-place update delta → structural insert+delete delta:
    after every step the incremental fold equals the vectorized recompute
    of the row arrays AND the engine-oracle verification passes."""
    from fixtures import delete_committed

    eng = _engine(v2=v2)
    warm, cold = _pair(eng)
    enc = encode_row_v2 if v2 else encode_row

    def check(label):
        key, img = _the_image(warm)
        assert img.fp_valid, label
        assert img.fp_value == integrity.fold(img.row_fp), label
        assert img.fp_integrity == integrity.fold(
            integrity.mix_fp(img.row_fp, img.row_commit_ts)), label
        res = integrity.verify_image(
            warm.region_cache, key, warm.engine.snapshot(None))
        assert res["outcome"] == "ok", (label, res)

    warm.handle_request(_req(_scan_dag(), 200, 3))
    check("build")
    # in-place update path
    put_committed(eng, record_key(TABLE_ID, 7),
                  enc(NON_HANDLE, [b"apple", 1, 2]), 210, 220)
    r = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "delta"
    check("in-place delta")
    # structural path: new dictionary value + insert + delete
    put_committed(eng, record_key(TABLE_ID, 5),
                  enc(NON_HANDLE, [b"durian", 999, 5]), 310, 320)
    put_committed(eng, record_key(TABLE_ID, 500),
                  enc(NON_HANDLE, [b"elder", 7, 1]), 310, 320)
    delete_committed(eng, record_key(TABLE_ID, 0), 310, 320)
    r = warm.handle_request(_req(_scan_dag(), 400, 5))
    assert r.metrics["region_cache"] == "delta"
    check("structural delta")
    # and the served bytes stayed byte-identical throughout
    assert r.data == cold.handle_request(_req(_scan_dag(), 400, 5)).data


def _seed_rows(kv, region_id, n=32):
    wb = WriteBatch()
    for i in range(n):
        k = Key.from_raw(record_key(TABLE_ID, i))
        w = Write(WriteType.PUT, 90,
                  short_value=encode_row(NON_HANDLE, [b"apple", i % 23, 100 + i]))
        wb.put_cf(CF_WRITE, k.append_ts(100).encoded, w.to_bytes())
    kv.write({"region_id": region_id}, wb)


def _commit_rows(kv, region_id, rows, ts0):
    from tikv_tpu.storage.txn.commands import Commit, Prewrite
    from tikv_tpu.storage.txn.scheduler import Scheduler
    from tikv_tpu.storage.txn_types import Mutation

    sched = Scheduler(kv, pool_size=1, group_commit_max=16)
    ctx = {"region_id": region_id}
    try:
        for i, (handle, row) in enumerate(rows):
            rk = record_key(TABLE_ID, handle)
            t = sched.submit(Prewrite(
                [Mutation.put(Key.from_raw(rk), row)], rk, start_ts=ts0 + i), ctx)
            assert t.done.wait(30) and t.exc is None, t.exc
            t = sched.submit(Commit(
                [Key.from_raw(rk)], ts0 + i, ts0 + 500 + i), ctx)
            assert t.done.wait(30) and t.exc is None, t.exc
    finally:
        sched.stop()
    return ts0 + 500 + len(rows)


def _rreq(dag, ts, region_id, tp=103):
    return CoprRequest(tp, dag, [record_range(TABLE_ID)], ts,
                       context={"region_id": region_id})


def test_wt_delta_fold_equals_full_recompute():
    """The write-through fold (zero CF_WRITE scans) lands the exact
    fingerprint a from-scratch build computes, and the oracle agrees —
    through a real raft write path."""
    c = Cluster(1)
    c.run()
    kv = c.raftkv(1)
    rid = FIRST_REGION_ID
    _seed_rows(kv, rid)
    warm = Endpoint(kv, enable_device=True)
    warm.handle_request(_rreq(_scan_dag(), 200, rid))
    hi = _commit_rows(kv, rid, [
        (3, encode_row(NON_HANDLE, [b"banana", 3, 3])),
        (40, encode_row(NON_HANDLE, [b"cherry", 4, 4])),
    ], ts0=300)
    r = warm.handle_request(_rreq(_scan_dag(), hi + 10, rid))
    assert r.metrics["region_cache"] == "wt_delta"
    key, img = _the_image(warm)
    assert img.fp_valid
    assert img.fp_value == integrity.fold(img.row_fp)
    # full recompute: an independent endpoint builds the same view cold
    fresh = Endpoint(kv, enable_device=True)
    fresh.handle_request(_rreq(_scan_dag(), hi + 10, rid))
    _, img2 = _the_image(fresh)
    assert (img.fp_value, img.fp_integrity) == (img2.fp_value, img2.fp_integrity)
    # and the scrubber oracle (local protocol-free snapshot) agrees
    res = integrity.verify_image(warm.region_cache, key, kv.local_snapshot(rid))
    assert res["outcome"] == "ok", res


# ---------------------------------------------------------------------------
# Checksum (tp=105) off the warm fingerprint
# ---------------------------------------------------------------------------

def test_checksum_warm_serves_off_fingerprint_byte_identical():
    eng = _engine()
    warm, cold = _pair(eng)
    before = REGISTRY.counter("tikv_coprocessor_checksum_total").get(path="warm")
    cold_resp = cold.handle_request(_checksum_req(200, 3))
    # no image yet: the warm endpoint's first checksum scans cold too
    r0 = warm.handle_request(_checksum_req(200, 3))
    assert r0.data == cold_resp.data and not r0.from_cache
    warm.handle_request(_req(_scan_dag(), 200, 3))  # build the image
    r1 = warm.handle_request(_checksum_req(200, 3))
    assert r1.from_cache, "fresh image must answer the checksum warm"
    assert r1.data == cold_resp.data
    assert REGISTRY.counter(
        "tikv_coprocessor_checksum_total").get(path="warm") == before + 1
    # the checksum definition really is checksum_range's (crc64-xor)
    from tikv_tpu.storage.mvcc import ForwardScanner

    start, end = record_range(TABLE_ID)
    kvs = list(ForwardScanner(eng.snapshot(), 200,
                              Key.from_raw(start), Key.from_raw(end)))
    oracle = checksum_range(kvs)
    _, img = _the_image(warm)
    assert img.checksum_parts() == (
        oracle["checksum"], oracle["total_kvs"], oracle["total_bytes"])


def test_checksum_below_image_snapshot_ts_serves_cold():
    """A Checksum at a start_ts BELOW the image's snapshot must refuse the
    warm path (the image may hold rows committed above the reader's ts) —
    the same stale guard as the serving hit path."""
    eng = _engine()  # rows committed at cts=100
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))  # image at snapshot_ts=200
    r = warm.handle_request(_checksum_req(50, 3))
    assert not r.from_cache, "a ts=50 reader must never see the ts=200 image"
    assert r.data == cold.handle_request(_checksum_req(50, 3)).data


def test_checksum_stays_byte_identical_through_deltas():
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    put_committed(eng, record_key(TABLE_ID, 9),
                  encode_row(NON_HANDLE, [b"kiwi", 5, 5]), 210, 220)
    r = warm.handle_request(_req(_scan_dag(), 300, 4))  # fold the delta
    assert r.metrics["region_cache"] == "delta"
    rw = warm.handle_request(_checksum_req(300, 4))
    rc = cold.handle_request(_checksum_req(300, 4))
    assert rw.from_cache and rw.data == rc.data


# ---------------------------------------------------------------------------
# shadow reads: detect → serve oracle → quarantine → rebuild
# ---------------------------------------------------------------------------

def test_shadow_read_detects_corruption_and_serves_oracle():
    eng = _engine()
    warm, cold = _pair(eng, shadow_sample=1)
    oracle = cold.handle_request(_req(_scan_dag(), 200, 3)).data
    warm.handle_request(_req(_scan_dag(), 200, 3))
    r1 = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r1.from_device and r1.data == oracle
    assert warm.shadow.results.get(("unary", "ok"), 0) >= 1

    info = chaos.corrupt_image(warm.region_cache, random.Random(1), mode="block")
    assert info is not None and info["mode"] == "block"
    r2 = warm.handle_request(_req(_scan_dag(), 200, 3))
    # the CPU result served: zero wrong bytes despite the corrupted image
    assert r2.data == oracle and not r2.from_device
    assert warm.shadow.results.get(("unary", "mismatch")) == 1
    ledger = warm.region_cache.quarantine_ledger
    assert len(ledger) == 1 and ledger[0]["stage"] == "shadow_read"
    # quarantine dropped the image; the next serve rebuilds byte-identically
    r3 = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r3.metrics["region_cache"] == "miss" and r3.data == oracle
    r4 = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r4.metrics["region_cache"] == "hit" and r4.from_device
    assert r4.data == oracle


def test_shadow_read_mismatch_fatal_env_raises(monkeypatch):
    eng = _engine()
    warm, _cold = _pair(eng, shadow_sample=1)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    chaos.corrupt_image(warm.region_cache, random.Random(3), mode="block")
    monkeypatch.setenv("TIKV_TPU_INTEGRITY_FATAL", "1")
    with pytest.raises(integrity.IntegrityMismatch):
        warm.handle_request(_req(_scan_dag(), 200, 3))


def test_shadow_read_samples_batch_path():
    """The scheduler's cross-region batch path samples too, and a corrupt
    image batch slot serves the CPU oracle bytes."""
    from tikv_tpu.copr.aggr import AggDescriptor
    from tikv_tpu.copr.dag import Aggregation
    from tikv_tpu.copr.rpn import col

    def agg_dag():
        return DagRequest(executors=[
            TableScan(TABLE_ID, PRODUCT_COLUMNS),
            Aggregation([], [AggDescriptor("sum", col(2)),
                             AggDescriptor("count", None)]),
        ])

    eng = _engine()
    warm, cold = _pair(eng, shadow_sample=1)

    def reqs():
        return [_req(agg_dag(), 200, 3, region_id=r) for r in (7, 8)]

    oracles = [cold.handle_request(r).data for r in reqs()]
    warm.handle_batch(reqs())  # cold fills
    r1 = warm.handle_batch(reqs())  # warm xregion batch, sampled
    assert [r.data for r in r1] == oracles
    assert warm.shadow.results.get(("batch", "ok"), 0) >= 1
    # corrupt until the strike lands on a column this plan aggregates (a
    # flip in an unread column legitimately leaves the response identical)
    rng = random.Random(5)
    while chaos.corrupt_image(warm.region_cache, rng, region_id=7,
                              mode="block")["column"] != 2:
        pass
    r2 = warm.handle_batch(reqs())
    assert [r.data for r in r2] == oracles, "corrupt slot must serve oracle bytes"
    assert warm.shadow.results.get(("batch", "mismatch"), 0) >= 1
    assert any(e["region_id"] == 7 for e in warm.region_cache.quarantine_ledger)


# ---------------------------------------------------------------------------
# scrubber
# ---------------------------------------------------------------------------

def test_scrubber_detects_corrupt_pending_fold():
    """A corrupted write-through pending delta folds into the image; the
    fingerprint tracks the corrupted CONTENT while the engine oracle holds
    the truth — the hash scrub catches it and the eager rebuild repairs."""
    from tikv_tpu.copr.region_cache import notify_region_write
    from tikv_tpu.storage.txn_types import append_ts

    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))

    # one committed batch: engine write + matching write-through notify
    row = encode_row(NON_HANDLE, [b"banana", 9, 9])
    put_committed(eng, record_key(TABLE_ID, 4), row, 210, 220)
    enc_user = Key.from_raw(record_key(TABLE_ID, 4)).encoded
    w = Write(WriteType.PUT, 210, short_value=row)
    notify_region_write(
        7, [("put", CF_WRITE, append_ts(enc_user, 220), w.to_bytes())], 4)
    _key, img = _the_image(warm)
    assert img.wt_pending is not None

    info = chaos.corrupt_image(warm.region_cache, random.Random(11),
                               mode="pending")
    assert info == {"mode": "pending", "region_id": 7, "handle": 4}
    r = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "wt_delta", "corrupt value folded in"

    results = warm.scrubber.scrub_once()
    assert [x["outcome"] for x in results] == ["mismatch"]
    assert "content" in results[0]["failed"]
    assert warm.region_cache.quarantine_ledger[-1]["stage"] == "scrub"
    # eager rebuild: the image is back, verified, serving oracle bytes warm
    assert [x["outcome"] for x in warm.scrubber.scrub_once()] == ["ok"]
    r2 = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r2.metrics["region_cache"] == "hit"
    assert r2.data == cold.handle_request(_req(_scan_dag(), 300, 4)).data


def test_scrubber_deep_detects_block_corruption_without_traffic():
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    before = REGISTRY.counter(
        "tikv_coprocessor_integrity_scrub_total").get(outcome="mismatch")
    chaos.corrupt_image(warm.region_cache, random.Random(2), mode="block")
    results = warm.scrubber.scrub_once()
    assert [x["outcome"] for x in results] == ["mismatch"]
    assert any(f.startswith(("column:", "nulls:", "handles", "commit_ts"))
               for f in results[0]["failed"])
    assert REGISTRY.counter(
        "tikv_coprocessor_integrity_scrub_total").get(outcome="mismatch") == before + 1
    # repaired eagerly — serving resumes byte-identical with zero cold cost
    r = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 200, 3)).data


def _mixed_engine(n=96):
    """v2 rows of several layouts: names of three lengths, NULL prices, ints
    of one and two bytes — the array decoder's block, not the reshape's."""
    from tikv_tpu.storage.btree_engine import BTreeEngine

    eng = BTreeEngine()
    for i in range(n):
        name = [b"fig", b"banana", b"clementine"][i % 3]
        price = None if i % 11 == 0 else 100 + i * 37
        put_committed(eng, record_key(TABLE_ID, i),
                      encode_row_v2(NON_HANDLE, [name, i * 7 % 23, price]), 90, 100)
    return eng


def test_mixed_layout_image_fingerprints_are_what_they_were():
    """The image of mixed-layout rows is built by the array decoder, and its
    fingerprints are the literal values the per-row build gave (computed on
    the parent of the PR that brought the arrays, cd28a35)."""
    eng = _mixed_engine()
    warm, cold = _pair(eng)
    _r, moved = rowv2_rows_decoded(lambda: warm.handle_request(_req(_scan_dag(), 200, 3)))
    assert moved == {"uniform": 0, "vector": 96, "walk": 0}
    _key, img = _the_image(warm)
    assert img.fp_valid
    assert (img.fp_value, img.fp_integrity) == (FP_VALUE_PARENT, FP_INTEGRITY_PARENT)
    assert img.fp_value == checksum_range(
        (record_key(TABLE_ID, i), v) for i, v in _mixed_values(eng))["checksum"]
    r = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 200, 3)).data


FP_VALUE_PARENT = 759004103826497781
FP_INTEGRITY_PARENT = 363352670706918358


def _mixed_values(eng):
    snap = eng.snapshot()
    for i in range(96):
        k = Key.from_raw(record_key(TABLE_ID, i)).append_ts(100).encoded
        yield i, Write.from_bytes(snap.get_cf(CF_WRITE, k)).short_value


@pytest.mark.parametrize("seed", [2, 5, 9])
def test_mixed_layout_block_flip_ends_in_its_column(seed):
    eng = _mixed_engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    info = chaos.corrupt_image(warm.region_cache, random.Random(seed), mode="block")
    results = warm.scrubber.scrub_once()
    assert [x["outcome"] for x in results] == ["mismatch"]
    assert results[0]["failed"] == [f"column:{info['column']}"]
    # repaired eagerly, through the same decoder
    assert [x["outcome"] for x in warm.scrubber.scrub_once()] == ["ok"]
    r = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 200, 3)).data


def test_mixed_layout_engine_value_flip_ends_in_content():
    """A bit flipped in a row's bytes in the engine, under an image that was
    built before: the oracle's hashes no longer fold to the image's."""
    eng = _mixed_engine()
    warm, _cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    _i, v = list(_mixed_values(eng))[40]
    ba = bytearray(v)
    ba[-1] ^= 0x04  # the last cell's last byte: the row still decodes
    put_committed(eng, record_key(TABLE_ID, 40), bytes(ba), 90, 100)
    results = warm.scrubber.scrub_once()
    assert [x["outcome"] for x in results] == ["mismatch"]
    assert "content" in results[0]["failed"]


def test_scrubber_worker_cadence_and_snapshot():
    eng = _engine()
    warm, _ = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    s = warm.scrubber
    s.start(0.02)
    try:
        import time

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and s.snapshot()["rounds"] == 0:
            time.sleep(0.01)
        snap = s.snapshot()
        assert snap["running"] and snap["rounds"] >= 1 and snap["ok"] >= 1
    finally:
        s.stop()
    assert not s.snapshot()["running"]


# ---------------------------------------------------------------------------
# raft consistency check: metrics + derived-plane ride-along
# ---------------------------------------------------------------------------

def _run_consistency_round(c, rid):
    import threading

    leader = c.wait_leader(rid)
    done = threading.Event()
    leader.schedule_consistency_check(lambda r: done.set())
    for _ in range(300):
        c.process()
        c.tick()
        if done.is_set() and all(
            rid in s.consistency_hashes for s in c.stores.values()
        ):
            break
    return leader


def test_consistency_check_counts_results_and_scrubs_images():
    c = Cluster(3)
    c.run()
    rid = FIRST_REGION_ID
    kv = c.raftkv(1)
    _seed_rows(kv, rid)
    warm = Endpoint(kv, enable_device=True)
    cold = Endpoint(kv, enable_device=False)
    oracle = cold.handle_request(_rreq(_scan_dag(), 200, rid)).data
    warm.handle_request(_rreq(_scan_dag(), 200, rid))

    compute0 = REGISTRY.counter("tikv_raft_consistency_check_total").get(result="compute")
    match0 = REGISTRY.counter("tikv_raft_consistency_check_total").get(result="match")
    _run_consistency_round(c, rid)
    cnt = REGISTRY.counter("tikv_raft_consistency_check_total")
    assert cnt.get(result="compute") >= compute0 + 3, "every replica computes"
    assert cnt.get(result="match") >= match0 + 3, "every replica verifies"
    assert cnt.get(result="mismatch") == 0
    # the clean warm image rode the round unquarantined
    assert warm.region_cache.quarantine_ledger == []

    # corrupt the raw fingerprint chain of the leader store's warm image:
    # the NEXT round's ride-along (hash-level — the apply thread never pays
    # a full decode) must quarantine it with zero read traffic
    _key, img = _the_image(warm)
    img.row_fp[0] ^= np.uint64(1)
    _run_consistency_round(c, rid)
    ledger = warm.region_cache.quarantine_ledger
    assert ledger and ledger[-1]["stage"] == "consistency_check"
    # serving recovers byte-identically (rebuild on next serve)
    r = warm.handle_request(_rreq(_scan_dag(), 200, rid))
    assert r.data == oracle


def test_verify_hash_cmd_codec_carries_image_fingerprints():
    """The verify_hash raft entry must round-trip the leader's image
    fingerprint payload through encode_cmd/decode_cmd — otherwise the
    replica cross-check is dead code on the real raft path — and still
    decode pre-integrity-plane entries that carry no payload."""
    from tikv_tpu.raft.store import decode_cmd, encode_cmd

    fps = {"a1b2c3d4e5f60718": {"apply_index": 42, "snapshot_ts": 200,
                                "max_commit_ts": 100,
                                "fingerprint": (1 << 64) - 3},
           "00ff00ff00ff00ff": {"apply_index": 7, "snapshot_ts": 90,
                                "max_commit_ts": 0, "fingerprint": 12345}}
    cmd = {"epoch": (1, 2), "ops": [], "admin": ("verify_hash", 9, 777, fps)}
    rt = decode_cmd(encode_cmd(cmd))
    assert rt["admin"] == ("verify_hash", 9, 777, fps)
    # empty payload round-trips too
    cmd2 = {"epoch": (1, 2), "ops": [], "admin": ("verify_hash", 9, 777, {})}
    assert decode_cmd(encode_cmd(cmd2))["admin"] == ("verify_hash", 9, 777, {})
    # a pre-integrity-plane entry (no count byte) still decodes
    from tikv_tpu.util import codec as ucodec

    legacy = bytearray()
    legacy += ucodec.encode_var_u64(1) + ucodec.encode_var_u64(2)
    legacy.append(6)
    legacy += ucodec.encode_var_u64(9) + ucodec.encode_var_u64(777)
    assert decode_cmd(bytes(legacy))["admin"] == ("verify_hash", 9, 777, {})


def test_scrubber_fatal_mode_recorded_not_swallowed(monkeypatch):
    """Fatal mode on the cadenced path: scrub_once finishes the round's
    bookkeeping then raises, and the worker wrapper records the error
    (the Worker itself swallows exceptions) and halts further rounds."""
    eng = _engine()
    warm, _ = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    chaos.corrupt_image(warm.region_cache, random.Random(2), mode="block")
    monkeypatch.setenv("TIKV_TPU_INTEGRITY_FATAL", "1")
    with pytest.raises(integrity.IntegrityMismatch):
        warm.scrubber.scrub_once()
    # the raise did NOT skip the round's bookkeeping
    snap = warm.scrubber.snapshot()
    assert snap["rounds"] == 1 and snap["mismatch"] == 1
    assert warm.region_cache.quarantine_ledger, "quarantine still recorded"
    # cadenced path: the wrapper records and halts instead of vanishing
    warm.handle_request(_req(_scan_dag(), 200, 3))  # rebuild an image
    chaos.corrupt_image(warm.region_cache, random.Random(3), mode="block")
    s = warm.scrubber
    s.start(0.01)
    try:
        import time

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and s.fatal_error is None:
            time.sleep(0.01)
        assert s.fatal_error is not None
        assert s.snapshot()["fatal_error"] == s.fatal_error
    finally:
        s.stop()


def test_replica_cross_check_quarantines_divergent_image():
    """verify_hash carries the leader's image fingerprints; a local image
    at the SAME apply index with a different fingerprint is quarantined."""
    eng = _engine()
    warm, _ = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3, region_id=731))
    key, img = _the_image(warm)
    kid = integrity.image_key_id(key)

    def rec(**over):
        base = {"apply_index": img.apply_index, "snapshot_ts": img.snapshot_ts,
                "max_commit_ts": img.max_commit_ts,
                "fingerprint": img.fp_integrity}
        base.update(over)
        return {kid: base}

    # leader agrees: nothing happens
    ok = integrity.cross_check_image_fps(731, None, rec())
    assert ok == [] and warm.region_cache.quarantine_ledger == []
    # different apply index: incomparable, skipped
    assert integrity.cross_check_image_fps(
        731, None, rec(apply_index=img.apply_index + 5,
                       fingerprint=img.fp_integrity ^ 1)) == []
    # same apply index but a version separates the two read points (the
    # leader's image saw a commit above OUR snapshot): healthy images built
    # at different stale-read timestamps must NOT false-quarantine
    assert integrity.cross_check_image_fps(
        731, None, rec(max_commit_ts=img.snapshot_ts + 50,
                       snapshot_ts=img.snapshot_ts + 100,
                       fingerprint=img.fp_integrity ^ 1)) == []
    assert warm.region_cache.quarantine_ledger == []
    # provably-identical row sets, different fingerprint: quarantined
    bad = integrity.cross_check_image_fps(
        731, None, rec(fingerprint=img.fp_integrity ^ 1))
    assert len(bad) == 1 and bad[0]["stage"] == "replica_divergence"
    assert key not in warm.region_cache._images


# ---------------------------------------------------------------------------
# debug surfaces
# ---------------------------------------------------------------------------

def test_debug_integrity_and_consistency_check_rpcs():
    from tikv_tpu.server.service import KvService
    from tikv_tpu.storage.storage import Storage

    c = Cluster(1)
    c.run()
    rid = FIRST_REGION_ID
    kv = c.raftkv(1)
    _seed_rows(kv, rid)
    warm = Endpoint(kv, enable_device=True)
    warm.handle_request(_rreq(_scan_dag(), 200, rid))
    svc = KvService(Storage(engine=kv), warm, raft_router=c.stores[1])

    out = svc.debug_integrity({})
    assert out["enabled"] and len(out["fingerprints"]) == 1
    fp = out["fingerprints"][0]
    assert fp["region_id"] == rid and fp["fp_valid"]
    assert out["quarantine"] == []
    assert out["shadow"]["every"] >= 0 and out["scrubber"]["running"] is False

    trig = svc.debug_consistency_check({})
    assert trig["scheduled"] == [rid]
    for _ in range(200):
        c.process()
        c.tick()
        if rid in c.stores[1].consistency_hashes:
            break
    res = svc.debug_consistency({})
    assert rid in res["hashes"] and res["inconsistent"] == {}

    # quarantine shows up in the ledger view
    chaos.corrupt_image(warm.region_cache, random.Random(1), mode="block")
    warm.scrubber.scrub_once()
    out = svc.debug_integrity({})
    assert len(out["quarantine"]) == 1
    assert out["scrubber"]["mismatch"] == 1


# ---------------------------------------------------------------------------
# THE seeded corruption chaos scenario (tier-1 closure)
# ---------------------------------------------------------------------------

def test_seeded_corruption_chaos_detect_quarantine_repair():
    """corrupt_image faults injected mid-traffic under transport chaos:
    every corruption is detected by a shadow read or the scrubber, ZERO
    wrong bytes reach any client, quarantined images rebuild, and post-heal
    warm serving is byte-identical to the CPU oracle."""
    c = Cluster(3)
    c.run()
    rid = FIRST_REGION_ID
    kv = c.raftkv(1)
    _seed_rows(kv, rid)
    warm = Endpoint(kv, enable_device=True, shadow_sample=1)
    cold = Endpoint(kv, enable_device=False)
    nem = Nemesis(c, seed=909)
    injected = detected_before = 0
    try:
        nem.delay(1, 2, rate=0.3)
        nem.duplicate(rate=0.2)
        ts = 300
        for round_i in range(4):
            # writes land through raft under transport chaos
            ts = _commit_rows(kv, rid, [
                (3 + round_i, encode_row(NON_HANDLE, [b"banana", round_i, 1])),
                (40 + round_i, encode_row(NON_HANDLE, [b"cherry", round_i, 2])),
            ], ts0=ts + 100)
            r = warm.handle_request(_rreq(_scan_dag(), ts + 10, rid))
            assert r.data == cold.handle_request(_rreq(_scan_dag(), ts + 10, rid)).data
            # strike: corrupt the warm image (block and pending modes both
            # land across the seeded schedule), then read immediately — the
            # shadow path must serve the oracle bytes
            info = nem.corrupt_image(warm.region_cache, region_id=rid)
            if info is not None:
                injected += 1
                r = warm.handle_request(_rreq(_scan_dag(), ts + 20, rid))
                assert r.data == cold.handle_request(
                    _rreq(_scan_dag(), ts + 20, rid)).data, \
                    f"round {round_i}: wrong bytes reached a client"
            # scrub sweeps whatever traffic did not touch
            warm.scrubber.scrub_once()
        nem.heal()
        detected = (warm.shadow.results.get(("unary", "mismatch"), 0)
                    + warm.scrubber.snapshot()["mismatch"])
        assert injected >= 2, "the seeded schedule must actually strike"
        assert detected >= injected - detected_before, (
            f"every corruption must be detected: injected={injected} "
            f"detected={detected}")
        assert len(warm.region_cache.quarantine_ledger) >= injected
        # post-heal: warm serving resumes, verified and byte-identical
        ts = _commit_rows(kv, rid, [
            (90, encode_row(NON_HANDLE, [b"elder", 6, 6])),
        ], ts0=ts + 100)
        r = warm.handle_request(_rreq(_scan_dag(), ts + 10, rid))
        assert r.data == cold.handle_request(_rreq(_scan_dag(), ts + 10, rid)).data
        key, img = _the_image(warm)
        assert img.fp_valid and img.fp_value == integrity.fold(img.row_fp)
        res = integrity.verify_image(warm.region_cache, key, kv.local_snapshot(rid))
        assert res["outcome"] == "ok", res
    finally:
        nem.heal()
        nem.close()


# ---------------------------------------------------------------------------
# split/merge/conf-change invalidation under chaos (PR-1 hooks under faults)
# ---------------------------------------------------------------------------

def test_split_merge_conf_change_invalidation_under_chaos():
    """A seeded Nemesis schedule splits, conf-changes, and merges the
    region mid-traffic: no stale-epoch image is ever served — every warm
    response stays byte-identical to the CPU oracle, and the first serve
    after each epoch change rebuilds instead of hitting the dead image."""
    c = Cluster(3)
    c.run()
    rid = FIRST_REGION_ID
    kv = c.raftkv(1)
    _seed_rows(kv, rid)
    warm = Endpoint(kv, enable_device=True, shadow_sample=1)
    cold = Endpoint(kv, enable_device=False)
    nem = Nemesis(c, seed=1234)

    def serve_identical(region_id, ts):
        rw = warm.handle_request(_rreq(_scan_dag(), ts, region_id))
        rc = cold.handle_request(_rreq(_scan_dag(), ts, region_id))
        assert rw.data == rc.data, f"region {region_id} diverged at ts {ts}"
        return rw

    def no_stale_epoch_images():
        with warm.region_cache._mu:
            for key, img in warm.region_cache._images.items():
                peer = c.stores[1].peers.get(key[0])
                assert peer is not None, f"image of dead region {key[0]}"
                cur = (peer.region.epoch.conf_ver, peer.region.epoch.version)
                assert img.epoch == cur, (
                    f"stale-epoch image: region {key[0]} image epoch "
                    f"{img.epoch} != current {cur}")

    try:
        nem.delay(1, 2, rate=0.3)
        nem.reorder(window=3)
        inval0 = warm.region_cache.stats.invalidations
        assert serve_identical(rid, 200).metrics["region_cache"] == "miss"
        serve_identical(rid, 200)
        no_stale_epoch_images()

        # split mid-traffic: both children must serve their clamped halves
        right_id = c.split_region(rid, record_key(TABLE_ID, 16))
        # the new region's leader lands wherever the election fell — pull
        # it onto store 1, whose raftkv both endpoints serve through
        c.elect_leader(right_id, 1)
        r = serve_identical(rid, 300)
        assert r.metrics["region_cache"] == "miss", \
            "post-split serve must rebuild, never hit the pre-split image"
        serve_identical(right_id, 300)
        no_stale_epoch_images()

        # conf change mid-traffic (remove a follower, re-add it)
        leader = c.wait_leader(rid)
        victim_store = next(s for s in (2, 3)
                            if s != leader.region.peer_by_id(leader.peer_id).store_id)
        victim = leader.region.peer_on_store(victim_store)
        c.remove_peer(rid, victim.peer_id)
        serve_identical(rid, 400)
        c.add_peer(rid, victim_store)
        serve_identical(rid, 500)
        no_stale_epoch_images()

        # merge the halves back mid-traffic
        c.merge_regions(rid, right_id)
        r = serve_identical(rid, 600)
        assert r.metrics["region_cache"] == "miss", \
            "post-merge serve must rebuild over the widened range"
        serve_identical(rid, 600)
        no_stale_epoch_images()
        assert warm.region_cache.stats.invalidations > inval0, \
            "the epoch-change hooks must actually fire under this schedule"
        # the whole run was shadow-verified with zero mismatches
        assert warm.shadow.results.get(("unary", "mismatch"), 0) == 0
    finally:
        nem.heal()
        nem.close()
