"""Region column cache: delta apply, invalidation, budget, fallbacks.

The contract under test is the ISSUE 1 acceptance list: byte-identical
DAGResponses across insert/update/delete deltas (vs a cold endpoint with the
cache off), invalidation on real region epoch changes (a raft split), LRU
eviction under a small byte budget, and the stale-``start_ts`` fallback.
"""

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID
from fixtures import delete_committed, lock_key, put_committed

from tikv_tpu.copr.dag import Aggregation, DagRequest, Limit, Selection, TableScan
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.region_cache import RegionColumnCache, notify_region_epoch_change
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_LOCK, Snapshot
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key
from tikv_tpu.util import trace
from tikv_tpu.util.metrics import REGISTRY

try:
    from tikv_tpu.native.engine import NativeEngine, native_available

    _NATIVE = native_available()
except ImportError:
    _NATIVE = False

NON_HANDLE = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
N_ROWS = 64


def _engine(n=N_ROWS, v2=False, table_id=TABLE_ID, mk=BTreeEngine):
    eng = mk()
    enc = encode_row_v2 if v2 else encode_row
    for i in range(n):
        name = [b"apple", b"banana", b"cherry"][i % 3]
        val = enc(NON_HANDLE, [name, i * 7 % 23, 100 + i])
        put_committed(eng, record_key(table_id, i), val, 90, 100)
    return eng


def _scan_dag(table_id=TABLE_ID):
    return DagRequest(executors=[TableScan(table_id, PRODUCT_COLUMNS), Limit(1 << 20)])


def _sel_dag(table_id=TABLE_ID):
    return DagRequest(executors=[
        TableScan(table_id, PRODUCT_COLUMNS),
        Selection([call("gt", col(2), const_int(5))]),
    ])


def _agg_dag(table_id=TABLE_ID):
    aggs = [AggDescriptor("sum", col(2)), AggDescriptor("count", None)]
    return DagRequest(executors=[
        TableScan(table_id, PRODUCT_COLUMNS), Aggregation([col(1)], aggs),
    ])


def _req(dag, ts, apply_index, region_id=7, epoch=(1, 1), table_id=TABLE_ID):
    return CoprRequest(
        103, dag, [record_range(table_id)], ts,
        context={"region_id": region_id, "region_epoch": epoch,
                 "apply_index": apply_index},
    )


def _pair(eng, **kw):
    warm = Endpoint(LocalEngine(eng), enable_device=True, **kw)
    cold = Endpoint(LocalEngine(eng), enable_device=True, enable_region_cache=False)
    return warm, cold


@pytest.mark.parametrize("v2", [False, True], ids=["rowv1", "rowv2"])
@pytest.mark.parametrize("mk_dag", [_scan_dag, _sel_dag, _agg_dag],
                         ids=["scan", "selection", "aggregation"])
def test_delta_apply_byte_identical(v2, mk_dag):
    """Insert + update + delete between two apply_indexes must serve the
    exact cold-decode bytes through the incremental delta path."""
    eng = _engine(v2=v2)
    warm, cold = _pair(eng)

    r0 = warm.handle_request(_req(mk_dag(), 200, 3))
    assert r0.metrics["region_cache"] == "miss"
    assert r0.data == cold.handle_request(_req(mk_dag(), 200, 3)).data
    r1 = warm.handle_request(_req(mk_dag(), 200, 3))
    assert r1.metrics["region_cache"] == "hit"
    assert r1.data == r0.data

    enc = encode_row_v2 if v2 else encode_row
    # update 2 rows (one with a NEW dictionary value), insert 1, delete 1
    put_committed(eng, record_key(TABLE_ID, 5),
                  enc(NON_HANDLE, [b"durian", 999, 5]), 210, 220)
    put_committed(eng, record_key(TABLE_ID, 11),
                  enc(NON_HANDLE, [b"apple", 1000, 6]), 210, 220)
    put_committed(eng, record_key(TABLE_ID, 500),
                  enc(NON_HANDLE, [b"elderberry", 7, 1]), 210, 220)
    delete_committed(eng, record_key(TABLE_ID, 0), 210, 220)

    r2 = warm.handle_request(_req(mk_dag(), 300, 4))
    assert r2.metrics["region_cache"] == "delta"
    assert r2.metrics["region_cache_delta_rows"] == 4
    assert r2.data == cold.handle_request(_req(mk_dag(), 300, 4)).data
    # and the post-delta image keeps serving hits byte-identically
    r3 = warm.handle_request(_req(mk_dag(), 300, 4))
    assert r3.metrics["region_cache"] == "hit"
    assert r3.data == r2.data


def test_update_only_delta_scatters_into_pinned_arrays():
    """An update-only delta takes the in-place scatter path (device pins are
    patched, not dropped) and later requests stay byte-identical."""
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_agg_dag(), 200, 3))  # build + pin
    warm.handle_request(_req(_agg_dag(), 200, 3))  # warm agg pins stacked arrays
    for i in (2, 9, 30):
        put_committed(eng, record_key(TABLE_ID, i),
                      encode_row(NON_HANDLE, [b"banana", 4, 4]), 210, 220)
    r = warm.handle_request(_req(_agg_dag(), 300, 4))
    assert r.metrics["region_cache"] == "delta"
    assert r.data == cold.handle_request(_req(_agg_dag(), 300, 4)).data
    # host blocks and device pins agree on the next pure hit
    r2 = warm.handle_request(_req(_sel_dag(), 300, 4))
    assert r2.metrics["region_cache"] == "hit"
    assert r2.data == cold.handle_request(_req(_sel_dag(), 300, 4)).data


def test_stale_start_ts_falls_back():
    """A read below the image's snapshot ts must not serve from the image
    (it would see too-new data) — it reports 'stale' and answers through
    the per-request path, byte-identical to the cache-off endpoint."""
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    put_committed(eng, record_key(TABLE_ID, 1),
                  encode_row(NON_HANDLE, [b"apple", 1, 1]), 110, 120)
    r = warm.handle_request(_req(_scan_dag(), 150, 4))
    assert r.metrics["region_cache"] == "stale"
    assert r.data == cold.handle_request(_req(_scan_dag(), 150, 4)).data
    assert warm.region_cache.stats.stale == 1


def test_epoch_change_in_context_invalidates():
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3, epoch=(1, 1)))
    assert len(warm.region_cache) == 1
    # a split bumped the version: same region id, new epoch
    r = warm.handle_request(_req(_scan_dag(), 300, 4, epoch=(1, 2)))
    assert r.metrics["region_cache"] == "miss"
    assert warm.region_cache.stats.invalidations == 1
    assert r.data == cold.handle_request(_req(_scan_dag(), 300, 4, epoch=(1, 2))).data


def test_raft_split_invalidates_cache():
    """A real region split through the raft apply path must invalidate the
    cached images of both sides via the store.py epoch-change hook."""
    from tikv_tpu.raft.cluster import FIRST_REGION_ID, Cluster

    eng = _engine()
    warm, _cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3, region_id=FIRST_REGION_ID))
    assert len(warm.region_cache) == 1

    c = Cluster(3)
    c.run()
    c.must_put(b"a", b"1")
    c.must_put(b"z", b"2")
    c.split_region(FIRST_REGION_ID, b"m")
    assert len(warm.region_cache) == 0
    assert warm.region_cache.stats.invalidations >= 1


def test_notify_hook_is_region_scoped():
    eng = _engine()
    warm, _cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3, region_id=7))
    notify_region_epoch_change(8)  # some other region
    assert len(warm.region_cache) == 1
    notify_region_epoch_change(7, reason="merge")
    assert len(warm.region_cache) == 0


def test_lru_eviction_under_byte_budget():
    """Three regions under a budget that fits ~one image: LRU evicts, the
    endpoint keeps answering correctly, and nothing OOMs."""
    eng = _engine(n=128)
    # decoded residency: this test pins the LRU/budget mechanics — with
    # column encoding on (the default) all three images FIT the budget,
    # which is the capacity win tests/test_compressed_columns.py asserts
    small = RegionColumnCache(byte_budget=1 << 14, max_regions=8,
                              encode_columns=False)
    warm = Endpoint(LocalEngine(eng), enable_device=True, region_cache=small)
    cold = Endpoint(LocalEngine(eng), enable_device=True, enable_region_cache=False)
    for rid in (1, 2, 3):
        r = warm.handle_request(_req(_scan_dag(), 200, 3, region_id=rid))
        assert r.data == cold.handle_request(_req(_scan_dag(), 200, 3)).data
    assert small.stats.evictions >= 2
    assert small.total_bytes() <= (1 << 14) or len(small) == 1
    # the survivor still serves hits
    r = warm.handle_request(_req(_scan_dag(), 200, 3, region_id=3))
    assert r.metrics["region_cache"] == "hit"


def test_region_too_big_for_budget_degrades():
    eng = _engine(n=128)
    tiny = RegionColumnCache(byte_budget=64, max_regions=8)
    warm = Endpoint(LocalEngine(eng), enable_device=True, region_cache=tiny)
    cold = Endpoint(LocalEngine(eng), enable_device=True, enable_region_cache=False)
    r = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r.metrics["region_cache"] == "too_big"
    assert len(tiny) == 0  # never pinned
    assert r.data == cold.handle_request(_req(_scan_dag(), 200, 3)).data


def test_locked_range_still_blocks_cached_reads():
    """A pending lock below the read ts must surface through the cached path
    exactly like the scanners (the CPU fallback re-raises it)."""
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    lock_key(eng, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 250)
    with pytest.raises(Exception, match="locked"):
        warm.handle_request(_req(_scan_dag(), 300, 4))
    with pytest.raises(Exception, match="locked"):
        cold.handle_request(_req(_scan_dag(), 300, 4))


def test_counters_and_tracker_exposure():
    from tikv_tpu.util.metrics import REGISTRY

    eng = _engine()
    warm, _cold = _pair(eng)
    before = REGISTRY.counter(
        "tikv_coprocessor_region_cache_total", "").get(outcome="hit")
    r0 = warm.handle_request(_req(_scan_dag(), 200, 3))
    r1 = warm.handle_request(_req(_scan_dag(), 200, 3))
    assert r0.metrics["region_cache"] == "miss"
    assert r1.metrics["region_cache"] == "hit"
    assert REGISTRY.counter(
        "tikv_coprocessor_region_cache_total", "").get(outcome="hit") == before + 1
    st = warm.region_cache.stats.to_dict()
    assert st["hits"] >= 1 and st["misses"] >= 1 and st["bytes_pinned"] > 0


def test_missing_context_is_off():
    eng = _engine()
    warm, cold = _pair(eng)
    req = CoprRequest(103, _scan_dag(), [record_range(TABLE_ID)], 200,
                      context={"region_id": 7})  # no epoch / apply_index
    r = warm.handle_request(req)
    assert "region_cache" not in r.metrics
    assert r.data == cold.handle_request(req).data
    assert len(warm.region_cache) == 0


def test_delta_update_with_large_value_resolves_exactly():
    """A changed key whose new value lives in CF_DEFAULT (no inline short
    value) must re-resolve through the exact path — regression for the
    encoded-key double-encoding that misclassified such updates as deletes."""
    from fixtures import put_committed_large

    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    # a real encoded row forced into CF_DEFAULT (no inline short value)
    row = encode_row(NON_HANDLE, [b"fig", 77, 88])
    put_committed_large(eng, record_key(TABLE_ID, 9), row, 210, 220)
    r = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "delta"
    assert r.metrics["region_cache_delta_rows"] == 1
    assert r.data == cold.handle_request(_req(_scan_dag(), 300, 4)).data


def test_delta_rollback_pick_resolves_older_version():
    """A rollback record newer than the cached fingerprint must re-resolve
    to the surviving older version, not delete the row."""
    from fixtures import rollback

    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    rollback(eng, record_key(TABLE_ID, 9), 150)
    r = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "delta"
    assert r.data == cold.handle_request(_req(_scan_dag(), 300, 4)).data
    # row 9 must still be present (update fingerprint, keep old value)
    r2 = warm.handle_request(_req(_sel_dag(), 300, 4))
    assert r2.data == cold.handle_request(_req(_sel_dag(), 300, 4)).data


# ---------------------------------------------------------------------------
# the lock-free memo (docs/region_column_cache.md "Locks"): a warm hit skips
# the CF_LOCK scan only where its snapshot cannot differ, in CF_LOCK, from a
# snapshot whose scan of the image's ranges met no lock.  What the scan would
# have said is asked of the scan itself, never of a switch.
# ---------------------------------------------------------------------------

both_engines = pytest.mark.parametrize("mk_kv", [
    BTreeEngine,
    pytest.param(lambda: NativeEngine(), id="NativeEngine", marks=pytest.mark.skipif(
        not _NATIVE, reason="no native engine")),
])


class _HeldEngine(LocalEngine):
    """Hands out ``held`` (a snapshot frozen earlier) while it is set."""

    held = None

    def snapshot(self, ctx=None):
        return self.held if self.held is not None else super().snapshot(ctx)


def _checks() -> dict:
    c = REGISTRY.counter("tikv_coprocessor_region_cache_lock_check_total", "")
    return {how: c.get(how=how) for how in ("memo", "scan")}


def _traced(warm, req) -> tuple:
    """Serve ``req`` under a kept trace, whatever the store's rate; the
    response and the trace's spans."""
    rate = trace.sample_rate()
    trace.set_sample_rate(1.0)
    try:
        with trace.start_trace("root") as root:
            r = warm.handle_request(req)
    finally:
        trace.set_sample_rate(rate)
    return r, trace.TRACER.get(root.rec.trace_id)["spans"]


def _how(warm, ts, apply_index=3):
    """Serve one warm hit at ``ts``; say how its lock check was answered, by
    the stage's tag and by the counter, which must agree."""
    before = _checks()
    r, spans = _traced(warm, _req(_scan_dag(), ts, apply_index))
    assert r.metrics["region_cache"] == "hit"
    after = _checks()
    moved = {how: after[how] - before[how] for how in after}
    stages = [s for s in spans if s["name"] == "cache.lock_check"]
    assert len(stages) == 1 and sum(moved.values()) == 1
    how = stages[0]["tags"]["how"]
    assert moved[how] == 1
    return how


def _warm_with_memo(kv):
    """An endpoint whose image of the table holds a lock-free memo: a miss,
    then a hit at a higher ts that scans CF_LOCK and finds it empty."""
    eng = _HeldEngine(kv)
    warm = Endpoint(eng, enable_device=True)
    assert warm.handle_request(
        _req(_scan_dag(), 200, 3)).metrics["region_cache"] == "miss"
    assert _how(warm, 300) == "scan"
    (img,) = warm.region_cache._images.values()
    assert img.lock_free_seq == kv.seq()
    return eng, warm, img


def _scan_would_say(warm, img, snap, ts) -> int:
    """The oracle: the scan itself, on an image with no memo to consult."""
    from tikv_tpu.copr.region_cache import RegionImage
    from tikv_tpu.storage.mvcc.reader import Statistics

    bare = RegionImage(img.key, img.epoch, img.schema, img.block_rows)
    return warm.region_cache._check_locks(
        bare, snap, list(img.key[1]), ts, Statistics())


def test_memo_second_rising_hit_skips_the_scan():
    """(a) nothing written between two hits at rising start_ts: the second
    is answered by the memo, byte-identical to the cold endpoint."""
    kv = _engine()
    _eng, warm, img = _warm_with_memo(kv)
    cold = Endpoint(LocalEngine(kv), enable_device=True, enable_region_cache=False)
    assert _how(warm, 400) == "memo"
    assert _how(warm, 500) == "memo"
    assert img.snapshot_ts == 500  # still raised to the reader's on a hit
    assert _scan_would_say(warm, img, kv.snapshot(), 500) == 0
    r = warm.handle_request(_req(_scan_dag(), 600, 3))
    assert r.data == cold.handle_request(_req(_scan_dag(), 600, 3)).data
    # writes to other CFs do not move CF_LOCK's stamp
    put_committed(kv, record_key(TABLE_ID + 1, 1), b"x", 610, 620)
    assert _how(warm, 700) == "memo"


@both_engines
def test_memo_lock_written_after_it_is_met(mk_kv):
    """(b) a lock written after the memo blocks the next reader exactly as
    the scanners would have it."""
    kv = _engine(mk=mk_kv)
    _eng, warm, img = _warm_with_memo(kv)
    assert _how(warm, 400) == "memo"
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 450)
    before = _checks()
    with pytest.raises(Exception, match="locked"):
        warm.handle_request(_req(_scan_dag(), 500, 3))
    assert _checks()["scan"] == before["scan"] + 1
    assert _checks()["memo"] == before["memo"]
    with pytest.raises(Exception, match="locked"):
        _scan_would_say(warm, img, kv.snapshot(), 500)


@both_engines
def test_memo_records_the_snapshot_not_a_late_stamp(mk_kv):
    """(c) the race the witness exists for: a snapshot frozen BEFORE a lock
    is written scans empty AFTER it; what it records is its own sequence,
    so a reader on a new snapshot still scans, and meets the lock."""
    kv = _engine(mk=mk_kv)
    eng, warm, img = _warm_with_memo(kv)
    frozen = kv.snapshot()
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 350)
    assert frozen.cf_touched_seq(CF_LOCK) == kv.seq() > frozen.sequence()
    eng.held = frozen
    assert _how(warm, 400) == "scan"  # the stamp moved; the frozen scan is empty
    assert img.lock_free_seq == frozen.sequence() < kv.seq()
    eng.held = None
    with pytest.raises(Exception, match="locked"):
        warm.handle_request(_req(_scan_dag(), 500, 3))
    with pytest.raises(Exception, match="locked"):
        _scan_would_say(warm, img, kv.snapshot(), 500)


@both_engines
def test_memo_reader_older_than_it_scans(mk_kv):
    """(d) a lock written and removed between two lock-free scans: a reader
    whose snapshot is OLDER than the memo's, and holds the lock, must not be
    vouched for by the newer scan."""
    kv = _engine(mk=mk_kv)
    eng, warm, img = _warm_with_memo(kv)
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 350)
    holds_lock = kv.snapshot()
    kv.delete_cf(CF_LOCK, Key.from_raw(record_key(TABLE_ID, 4)).encoded)
    assert _how(warm, 400) == "scan"  # CF_LOCK moved: one scan, empty again
    assert img.lock_free_seq == kv.seq() > holds_lock.sequence()
    assert _how(warm, 450) == "memo"
    eng.held = holds_lock
    before = _checks()
    with pytest.raises(Exception, match="locked"):
        warm.handle_request(_req(_scan_dag(), 500, 3))
    assert _checks() == {"memo": before["memo"], "scan": before["scan"] + 1}
    eng.held = None
    assert _how(warm, 600) == "memo"


def test_memo_lock_outside_the_ranges_costs_one_scan():
    """(e) the stamp is per CF and per store: a lock in another table moves
    it, costs this image one scan, and the memo is set again."""
    kv = _engine()
    _eng, warm, img = _warm_with_memo(kv)
    assert _how(warm, 400) == "memo"
    lock_key(kv, record_key(TABLE_ID + 1, 9), record_key(TABLE_ID + 1, 9), 410)
    assert _how(warm, 500) == "scan"
    assert img.lock_free_seq == kv.seq()
    assert _how(warm, 600) == "memo"


@both_engines
def test_memo_cf_lock_write_that_bypasses_raft_apply(mk_kv):
    """(f) unsafe_destroy_range deletes a range of CF_LOCK straight on the
    engine: no apply, no notify, no apply_index.  The stamp still moves."""
    from tikv_tpu.server.gc_worker import GcWorker

    kv = _engine(mk=mk_kv)
    eng, warm, img = _warm_with_memo(kv)
    assert _how(warm, 400) == "memo"
    memo = img.lock_free_seq
    GcWorker(eng).unsafe_destroy_range(
        record_key(TABLE_ID + 1, 0), record_key(TABLE_ID + 1, 100))
    assert kv.cf_touched_seq(CF_LOCK) == kv.seq() > memo
    assert _how(warm, 500) == "scan"
    assert img.lock_free_seq == kv.seq()
    assert _how(warm, 600) == "memo"


class _NoStampSnapshot(Snapshot):
    """A snapshot type that keeps no sequence numbers (the trait's default)."""

    def __init__(self, inner):
        self._inner = inner

    def get_cf(self, cf, key):
        return self._inner.get_cf(cf, key)

    def cursor_cf(self, cf, lower=None, upper=None):
        return self._inner.cursor_cf(cf, lower, upper)


def test_memo_snapshot_without_a_stamp_scans_every_time():
    """(g) the behaviour follows what the snapshot can prove."""
    kv = _engine()

    class _NoStampEngine(LocalEngine):
        def snapshot(self, ctx=None):
            return _NoStampSnapshot(self.kv.snapshot())

    warm = Endpoint(_NoStampEngine(kv), enable_device=True)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    assert [_how(warm, ts) for ts in (300, 400, 500)] == ["scan"] * 3
    (img,) = warm.region_cache._images.values()
    assert img.lock_free_seq is None
    # and a memo set through a snapshot that can say is no use to one that cannot
    img.lock_free_seq = kv.seq()
    assert _how(warm, 600) == "scan"


def test_memo_not_recorded_over_a_non_blocking_lock():
    """(h) a lock that does not block ts 300 may block ts 1100: a scan that
    met any lock records nothing."""
    kv = _engine()
    eng = _HeldEngine(kv)
    warm = Endpoint(eng, enable_device=True)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 1000)
    assert _how(warm, 300) == "scan"
    (img,) = warm.region_cache._images.values()
    assert img.lock_free_seq is None
    assert _how(warm, 400) == "scan"
    with pytest.raises(Exception, match="locked"):
        warm.handle_request(_req(_scan_dag(), 1100, 3))


def test_memo_does_not_override_a_dirty_image():
    """``locks_dirty`` (a write-through batch locked a key in range) still
    forces a scan, whatever the stamps say."""
    kv = _engine()
    _eng, warm, img = _warm_with_memo(kv)
    img.locks_dirty = True
    img.locks_dirty_at = 3
    assert _how(warm, 400) == "scan"
    assert img.locks_dirty is False
    assert _how(warm, 500) == "memo"


def test_memo_serves_the_warm_checksum_path():
    """checksum_serve shares _hit_fresh_locked, so the memo and a lock
    written after it behave there as on a served hit."""
    kv = _engine()
    _eng, warm, img = _warm_with_memo(kv)
    ctx = {"region_id": 7, "region_epoch": (1, 1), "apply_index": 3}
    ranges = list(img.key[1])
    before = _checks()
    if warm.region_cache.checksum_serve(kv.snapshot(), ctx, ranges, 400) is not None:
        assert _checks() == {"memo": before["memo"] + 1, "scan": before["scan"]}
        lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 450)
        with pytest.raises(Exception, match="locked"):
            warm.region_cache.checksum_serve(kv.snapshot(), ctx, ranges, 500)
    else:
        pytest.skip("image has no fingerprint")


@both_engines
def test_memo_agrees_with_the_scan_under_concurrent_lock_writes(mk_kv):
    """Readers on fresh snapshots race a writer that locks and unlocks a key
    in range (straight on the engine: no notify, no apply_index).  Whatever
    the interleaving, a reader is refused exactly when ITS snapshot holds
    the lock: the memo never vouches for a snapshot it did not cover."""
    import sys
    import threading
    import time

    kv = _engine(mk=mk_kv)
    cache = RegionColumnCache()
    ctx = {"region_id": 7, "region_epoch": (1, 1), "apply_index": 3}
    ranges = [record_range(TABLE_ID)]
    locked_key = Key.from_raw(record_key(TABLE_ID, 4)).encoded
    assert cache.serve(kv.snapshot(), ctx, PRODUCT_COLUMNS, ranges, 200)[1] == "miss"
    stop = time.monotonic() + 0.5
    ts_mu = threading.Lock()
    next_ts = [300]
    wrong: list = []
    seen = {"hit": 0, "locked": 0}

    def writer():
        # the sleeps hand the interpreter to the readers with the lock held,
        # and with it gone, whatever the scheduler does under load
        try:
            while time.monotonic() < stop:
                lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 50)
                time.sleep(0.001)
                kv.delete_cf(CF_LOCK, locked_key)
                time.sleep(0.001)
        except Exception as e:  # noqa: BLE001 — a dead writer must fail the test
            wrong.append(("writer", repr(e)))

    def reader():
        while time.monotonic() < stop:
            with ts_mu:
                next_ts[0] += 1
                ts = next_ts[0]
            snap = kv.snapshot()
            holds = snap.get_cf(CF_LOCK, locked_key) is not None
            try:
                outcome = cache.serve(snap, ctx, PRODUCT_COLUMNS, ranges, ts)[1]
            except Exception as e:  # noqa: BLE001 — KeyIsLocked, by its text
                outcome = "locked" if "locked" in str(e).lower() else repr(e)
            # a slower reader's ts falls below the image's snapshot_ts; no
            # data is committed here, so it is served all the same: "stale"
            # is a failure like any other outcome
            if outcome != ("locked" if holds else "hit"):
                wrong.append((ts, holds, outcome))
            elif outcome in seen:
                seen[outcome] += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not wrong, wrong[:5]
    assert seen["hit"] and seen["locked"], seen


# ---------------------------------------------------------------------------
# readers below the snapshot (docs/region_column_cache.md "Readers below the
# snapshot"): another session's hit raised snapshot_ts past this reader's
# start_ts.  The image serves it exactly when it holds nothing the reader may
# not see: same apply_index and start_ts >= max_commit_ts.  The plain
# reference is the cache-off endpoint at the reader's own start_ts.
# ---------------------------------------------------------------------------


def _below_counts() -> dict:
    c = REGISTRY.counter("tikv_coprocessor_region_cache_below_snapshot_total", "")
    return {o: c.get(outcome=o) for o in ("served", "refused")}


def _lookup_tags(warm, req) -> tuple:
    """The response to ``req`` and the tags of its ``cache.lookup`` stage
    (the last segment carries them all)."""
    r, spans = _traced(warm, req)
    stages = [s for s in spans if s["name"] == "cache.lookup"]
    assert stages
    return r, stages[-1]["tags"]


def _overtaken(eng, mk_dag=_scan_dag, **kw):
    """An image another session has read at 400: built at 200, hit at 400."""
    warm, cold = _pair(eng, **kw)
    assert warm.handle_request(
        _req(mk_dag(), 200, 3)).metrics["region_cache"] == "miss"
    assert warm.handle_request(
        _req(mk_dag(), 400, 3)).metrics["region_cache"] == "hit"
    (img,) = warm.region_cache._images.values()
    assert (img.snapshot_ts, img.max_commit_ts) == (400, 100)
    return warm, cold, img


@pytest.mark.parametrize("mk_dag", [_scan_dag, _sel_dag, _agg_dag],
                         ids=["scan", "selection", "aggregation"])
def test_below_snapshot_reader_at_or_above_max_commit_ts_is_a_hit(mk_dag):
    """(a) same apply_index, start_ts >= max_commit_ts: a hit, byte-identical
    to the cold endpoint at the reader's ts; snapshot_ts and the memo stay
    where they were; counted and tagged."""
    eng = _engine()
    warm, cold, img = _overtaken(eng, mk_dag)
    memo = img.lock_free_seq
    before = _below_counts()
    hits = warm.region_cache.stats.hits
    r, tags = _lookup_tags(warm, _req(mk_dag(), 300, 3))
    assert r.metrics["region_cache"] == "hit"
    assert tags["outcome"] == "hit" and tags["below_snapshot"] == 1
    assert r.data == cold.handle_request(_req(mk_dag(), 300, 3)).data
    assert img.snapshot_ts == 400 and img.max_commit_ts == 100
    assert img.lock_free_seq == memo is not None and not img.locks_dirty
    st = warm.region_cache.stats
    assert (st.below_snapshot, st.stale, st.hits) == (1, 0, hits + 1)
    assert _below_counts() == {"served": before["served"] + 1,
                               "refused": before["refused"]}
    # the bound itself: a reader AT max_commit_ts sees that commit
    r = warm.handle_request(_req(mk_dag(), 100, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(mk_dag(), 100, 3)).data
    # a reader at or above the snapshot is no such reader
    _r, tags = _lookup_tags(warm, _req(mk_dag(), 400, 3))
    assert "below_snapshot" not in tags
    assert st.below_snapshot == 2


def _commit_between(eng, kind: str) -> None:
    """One commit at 350 (start 340), between the readers at 300 and 400."""
    if kind == "update":
        put_committed(eng, record_key(TABLE_ID, 5),
                      encode_row(NON_HANDLE, [b"durian", 999, 5]), 340, 350)
    elif kind == "insert":
        put_committed(eng, record_key(TABLE_ID, 500),
                      encode_row(NON_HANDLE, [b"elderberry", 7, 1]), 340, 350)
    else:
        delete_committed(eng, record_key(TABLE_ID, 0), 340, 350)


@pytest.mark.parametrize("kind", ["update", "insert", "delete"])
def test_below_snapshot_reader_under_max_commit_ts_is_stale(kind):
    """(b) a commit between the two timestamps is IN the image: the lower
    reader is stale and byte-identical to the cold endpoint.  With the bound
    loosened (the image made to forget that commit's timestamp, which is
    what comparing against anything lower amounts to) the same reader is
    served the image and its answer is wrong: the case guards the bound."""
    eng = _engine()
    warm, cold = _pair(eng)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    _commit_between(eng, kind)
    r = warm.handle_request(_req(_scan_dag(), 400, 4))
    assert r.metrics["region_cache"] == "delta"
    assert r.data == cold.handle_request(_req(_scan_dag(), 400, 4)).data
    (img,) = warm.region_cache._images.values()
    assert (img.snapshot_ts, img.max_commit_ts) == (400, 350)
    before = _below_counts()
    want = cold.handle_request(_req(_scan_dag(), 300, 4)).data
    assert want != r.data
    r, tags = _lookup_tags(warm, _req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "stale" and tags["below_snapshot"] == 1
    assert r.data == want
    assert warm.region_cache.stats.stale == 1
    assert warm.region_cache.stats.below_snapshot == 0
    assert _below_counts() == {"served": before["served"],
                               "refused": before["refused"] + 1}
    # a reader between the commit and the snapshot is served
    r = warm.handle_request(_req(_scan_dag(), 360, 4))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 360, 4)).data
    # the bound loosened
    img.max_commit_ts = 100
    r = warm.handle_request(_req(_scan_dag(), 300, 4))
    assert r.metrics["region_cache"] == "hit" and r.data != want


def test_below_snapshot_reader_at_another_apply_index_is_stale():
    """The image speaks for one engine state only: a reader below the
    snapshot whose snapshot is at another apply_index is stale, whatever its
    timestamp."""
    eng = _engine()
    warm, cold, img = _overtaken(eng)
    for apply_index in (2, 4):
        r = warm.handle_request(_req(_scan_dag(), 300, apply_index))
        assert r.metrics["region_cache"] == "stale"
        assert r.data == cold.handle_request(_req(_scan_dag(), 300, apply_index)).data
    assert warm.region_cache.stats.below_snapshot == 0
    assert (img.snapshot_ts, img.apply_index) == (400, 3)


@both_engines
def test_below_snapshot_reader_meets_a_lock_at_its_own_ts(mk_kv):
    """(c) the lock check runs at the READER's start_ts: a lock that blocks
    the lower reader raises for it as the oracle scan does, and one above
    its timestamp does not block it."""
    kv = _engine(mk=mk_kv)
    warm, cold, img = _overtaken(kv)
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 250)
    before = warm.region_cache.stats.below_snapshot
    for ep in (warm, cold):
        with pytest.raises(Exception, match="locked"):
            ep.handle_request(_req(_scan_dag(), 300, 3))
    assert warm.region_cache.stats.below_snapshot == before  # not served
    r = warm.handle_request(_req(_scan_dag(), 240, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 240, 3)).data
    assert warm.region_cache.stats.below_snapshot == before + 1
    assert img.snapshot_ts == 400 and img.lock_free_seq is not None


def test_below_snapshot_warm_checksum_follows_the_rule():
    """(d) the warm Checksum path shares the one definition."""
    from tikv_tpu.copr.analyze import checksum_range
    from tikv_tpu.storage.mvcc import ForwardScanner

    def oracle(ts):
        start, end = record_range(TABLE_ID)
        r = checksum_range(list(ForwardScanner(
            kv.snapshot(), ts, Key.from_raw(start), Key.from_raw(end))))
        return r["checksum"], r["total_kvs"], r["total_bytes"]

    kv = _engine()
    warm, _cold, img = _overtaken(kv)
    if not img.fp_valid:
        pytest.skip("image has no fingerprint")
    cache = warm.region_cache
    ranges = list(img.key[1])
    ctx = {"region_id": 7, "region_epoch": (1, 1), "apply_index": 3}
    before = _below_counts()
    assert cache.checksum_serve(kv.snapshot(), ctx, ranges, 300) == oracle(300)
    assert cache.stats.below_snapshot == 1 and img.snapshot_ts == 400
    # a commit between the two timestamps, folded into the image
    _commit_between(kv, "update")
    assert warm.handle_request(
        _req(_scan_dag(), 500, 4)).metrics["region_cache"] == "delta"
    ctx["apply_index"] = 4
    assert cache.checksum_serve(kv.snapshot(), ctx, ranges, 300) is None
    assert cache.checksum_serve(kv.snapshot(), ctx, ranges, 360) == oracle(360)
    assert oracle(360) != oracle(300)
    assert _below_counts() == {"served": before["served"] + 2,
                               "refused": before["refused"] + 1}


def test_below_snapshot_warm_checksum_counts_a_reader_once():
    """One Checksum reader is held to every image of the region in turn: it
    counts once, `served` if any image answered it, else `refused`."""
    kv = _engine()
    warm, _cold, img = _overtaken(kv)
    narrow = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS[:2]),
                                   Limit(1 << 20)])
    warm.handle_request(_req(narrow, 400, 3))
    _commit_between(kv, "update")
    # the first image alone is repaired to apply_index 4 (and touched last,
    # so the loop meets the narrow image, still at 3, first)
    assert warm.handle_request(
        _req(_scan_dag(), 500, 4)).metrics["region_cache"] == "delta"
    cache = warm.region_cache
    imgs = list(cache._images.values())
    assert len(imgs) == 2 and imgs[-1] is img
    if not all(i.fp_valid for i in imgs):
        pytest.skip("an image has no fingerprint")
    ctx = {"region_id": 7, "region_epoch": (1, 1), "apply_index": 4}
    ranges = list(img.key[1])
    before = _below_counts()
    assert cache.checksum_serve(kv.snapshot(), ctx, ranges, 360) is not None
    assert _below_counts() == {"served": before["served"] + 1,
                               "refused": before["refused"]}
    assert cache.checksum_serve(kv.snapshot(), ctx, ranges, 300) is None
    assert _below_counts() == {"served": before["served"] + 1,
                               "refused": before["refused"] + 1}


def test_below_snapshot_reader_leaves_a_pending_chain_alone():
    """A reader at the image's own apply_index, overtaken by a session AND by
    a write-through batch it predates, is served the image as it stands; the
    pending chain waits for a reader whose snapshot holds it."""
    from tikv_tpu.copr.region_cache import notify_region_write
    from tikv_tpu.storage.engine import CF_WRITE, WriteBatch
    from tikv_tpu.storage.txn_types import Write, WriteType

    kv = _engine()
    eng = _HeldEngine(kv)
    warm = Endpoint(eng, enable_device=True)
    cold = Endpoint(eng, enable_device=True, enable_region_cache=False)
    warm.handle_request(_req(_scan_dag(), 200, 3))
    warm.handle_request(_req(_scan_dag(), 400, 3))
    predates = kv.snapshot()
    k = Key.from_raw(record_key(TABLE_ID, 5))
    val = encode_row(NON_HANDLE, [b"durian", 999, 5])
    ops = [("put", CF_WRITE, k.append_ts(460).encoded,
            Write(WriteType.PUT, 450, short_value=val).to_bytes())]
    wb = WriteBatch()
    wb.put_cf(*ops[0][1:])
    kv.write(wb)
    notify_region_write(7, ops, 4)
    (img,) = warm.region_cache._images.values()
    assert img.wt_pending is not None
    eng.held = predates
    r = warm.handle_request(_req(_scan_dag(), 300, 3))
    assert r.metrics["region_cache"] == "hit"
    assert r.data == cold.handle_request(_req(_scan_dag(), 300, 3)).data
    assert img.wt_pending is not None and img.apply_index == 3
    eng.held = None
    r = warm.handle_request(_req(_scan_dag(), 500, 4))
    assert r.metrics["region_cache"] == "wt_delta"
    assert r.data == cold.handle_request(_req(_scan_dag(), 500, 4)).data
    # now the image is ahead of that reader's snapshot: stale, as before
    eng.held = predates
    r = warm.handle_request(_req(_scan_dag(), 470, 3))
    assert r.metrics["region_cache"] == "stale"
    assert r.data == cold.handle_request(_req(_scan_dag(), 470, 3)).data


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
@pytest.mark.parametrize("mk_dag", [_scan_dag, _agg_dag],
                         ids=["scan", "aggregation"])
def test_below_snapshot_seeded_interleaving_equals_cold(seed, mk_dag):
    """(e) sessions draw timestamps from one oracle, commits and deletes land
    between some of them, and the tasks reach the region in another order:
    every answer equals the cold endpoint's at its own start_ts."""
    rng = np.random.default_rng(seed)
    eng = _engine()
    warm, cold = _pair(eng)
    tso = [200]
    apply_index = [3]

    def ts():
        tso[0] += int(rng.integers(1, 9))
        return tso[0]

    def write():
        handle = int(rng.integers(0, N_ROWS + 8))
        start = ts()
        if rng.random() < 0.3:
            delete_committed(eng, record_key(TABLE_ID, handle), start, ts())
        else:
            name = [b"apple", b"banana", b"fig"][int(rng.integers(3))]
            put_committed(
                eng, record_key(TABLE_ID, handle),
                encode_row(NON_HANDLE, [name, int(rng.integers(50)), handle]),
                start, ts())
        apply_index[0] += 1

    outcomes: dict = {}
    warm.handle_request(_req(mk_dag(), ts(), apply_index[0]))
    for _round in range(24):
        # the round's sessions draw their timestamps, a writer commits
        # between some of them; all of it is in the engine before the first
        # of the round's tasks is served
        readers = []
        for _ in range(int(rng.integers(2, 6))):
            if rng.random() < 0.15:
                write()
            readers.append(ts())
        for i in rng.permutation(len(readers)):
            req = _req(mk_dag(), readers[int(i)], apply_index[0])
            r = warm.handle_request(req)
            o = r.metrics["region_cache"]
            outcomes[o] = outcomes.get(o, 0) + 1
            assert r.data == cold.handle_request(req).data, (seed, readers, o)
    st = warm.region_cache.stats
    assert st.below_snapshot > 0 and st.stale > 0, (st.to_dict(), outcomes)
    assert outcomes.get("stale", 0) == st.stale

