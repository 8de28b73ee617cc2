"""Engine conformance suite — any KvEngine implementation must pass.

Plays the role of the reference's components/engine_traits_tests crate: the
same assertions run against every registered engine (BTreeEngine now, the
native C++ engine once wired in).
"""

import pytest

from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE, WriteBatch

ENGINES = {"btree": BTreeEngine}

try:
    from tikv_tpu.native.engine import NativeEngine, native_available

    if native_available():
        ENGINES["native"] = NativeEngine
except ImportError:
    pass


@pytest.fixture(params=sorted(ENGINES))
def engine(request):
    return ENGINES[request.param]()


def test_point_ops(engine):
    assert engine.get(b"k") is None
    engine.put_cf(CF_DEFAULT, b"k", b"v")
    assert engine.get(b"k") == b"v"
    engine.put_cf(CF_DEFAULT, b"k", b"v2")
    assert engine.get(b"k") == b"v2"
    engine.delete_cf(CF_DEFAULT, b"k")
    assert engine.get(b"k") is None


def test_cf_isolation(engine):
    engine.put_cf(CF_DEFAULT, b"k", b"d")
    engine.put_cf(CF_LOCK, b"k", b"l")
    engine.put_cf(CF_WRITE, b"k", b"w")
    assert engine.get_cf(CF_DEFAULT, b"k") == b"d"
    assert engine.get_cf(CF_LOCK, b"k") == b"l"
    assert engine.get_cf(CF_WRITE, b"k") == b"w"


def test_write_batch_atomic_order(engine):
    wb = WriteBatch()
    wb.put(b"a", b"1")
    wb.put(b"a", b"2")
    wb.delete(b"b")
    wb.put(b"b", b"3")
    engine.write(wb)
    assert engine.get(b"a") == b"2"
    assert engine.get(b"b") == b"3"


def test_delete_range(engine):
    for i in range(10):
        engine.put_cf(CF_DEFAULT, bytes([i]), b"v")
    wb = WriteBatch()
    wb.delete_range_cf(CF_DEFAULT, bytes([3]), bytes([7]))
    engine.write(wb)
    remaining = [k for k, _ in engine.scan_cf(CF_DEFAULT, b"", None)]
    assert remaining == [bytes([i]) for i in [0, 1, 2, 7, 8, 9]]


def test_scan_ranges(engine):
    keys = [b"a", b"b", b"c", b"d", b"e"]
    for k in keys:
        engine.put_cf(CF_DEFAULT, k, k.upper())
    assert [k for k, _ in engine.scan_cf(CF_DEFAULT, b"b", b"d")] == [b"b", b"c"]
    assert [k for k, _ in engine.scan_cf(CF_DEFAULT, b"", None)] == keys
    assert [k for k, _ in engine.scan_cf(CF_DEFAULT, b"b", b"e", reverse=True)] == [b"d", b"c", b"b"]
    assert [k for k, _ in engine.scan_cf(CF_DEFAULT, b"", None, limit=2)] == [b"a", b"b"]


def test_snapshot_isolation(engine):
    engine.put_cf(CF_DEFAULT, b"k", b"v1")
    snap = engine.snapshot()
    engine.put_cf(CF_DEFAULT, b"k", b"v2")
    engine.put_cf(CF_DEFAULT, b"new", b"x")
    assert snap.get_cf(CF_DEFAULT, b"k") == b"v1"
    assert snap.get_cf(CF_DEFAULT, b"new") is None
    assert engine.get(b"k") == b"v2"
    snap2 = engine.snapshot()
    assert snap2.get_cf(CF_DEFAULT, b"k") == b"v2"
    # old snapshot unaffected by later writes
    engine.delete_cf(CF_DEFAULT, b"k")
    assert snap.get_cf(CF_DEFAULT, b"k") == b"v1"
    assert snap2.get_cf(CF_DEFAULT, b"k") == b"v2"


def test_cursor_semantics(engine):
    for k in [b"b", b"d", b"f"]:
        engine.put_cf(CF_DEFAULT, k, b"v")
    cur = engine.snapshot().cursor_cf(CF_DEFAULT)
    assert cur.seek(b"a") and cur.key() == b"b"
    assert cur.seek(b"b") and cur.key() == b"b"
    assert cur.seek(b"c") and cur.key() == b"d"
    assert not cur.seek(b"g")
    assert cur.seek_for_prev(b"g") and cur.key() == b"f"
    assert cur.seek_for_prev(b"d") and cur.key() == b"d"
    assert cur.seek_for_prev(b"c") and cur.key() == b"b"
    assert not cur.seek_for_prev(b"a")
    assert cur.seek_to_first() and cur.key() == b"b"
    assert cur.next() and cur.key() == b"d"
    assert cur.prev() and cur.key() == b"b"
    assert not cur.prev()
    assert cur.seek_to_last() and cur.key() == b"f"
    assert not cur.next()


def test_cursor_bounds(engine):
    for k in [b"a", b"b", b"c", b"d"]:
        engine.put_cf(CF_DEFAULT, k, b"v")
    cur = engine.snapshot().cursor_cf(CF_DEFAULT, lower=b"b", upper=b"d")
    assert cur.seek_to_first() and cur.key() == b"b"
    assert cur.seek_to_last() and cur.key() == b"c"
    assert cur.seek(b"a") and cur.key() == b"b"
    assert not cur.seek(b"d")


def test_bulk_load():
    engine = BTreeEngine()
    engine.put_cf(CF_DEFAULT, b"m", b"old")
    items = [(bytes([i]), bytes([i])) for i in range(5)]
    engine.bulk_load(CF_DEFAULT, items)
    keys = [k for k, _ in engine.scan_cf(CF_DEFAULT, b"", None)]
    assert keys == [bytes([i]) for i in range(5)] + [b"m"]


def test_native_engine_full_stack():
    """The native engine drops in under MVCC + txn + coprocessor unchanged."""
    pytest.importorskip("tikv_tpu.native.engine")
    from tikv_tpu.native.engine import NativeEngine, native_available

    if not native_available():
        pytest.skip("native engine unavailable")
    import sys, os
    sys.path.insert(0, os.path.dirname(__file__))
    from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID, product_kvs
    from tikv_tpu.copr.dag import BatchExecutorsRunner, DagRequest, TableScan
    from tikv_tpu.copr.executors import MvccScanSource
    from tikv_tpu.copr.mvcc_batch import MvccBatchScanSource
    from tikv_tpu.copr.table import record_range
    from tikv_tpu.storage.kv import LocalEngine
    from tikv_tpu.storage.storage import Storage
    from tikv_tpu.storage.txn.commands import Commit, Prewrite
    from tikv_tpu.storage.txn_types import Key, Mutation

    store = Storage(engine=LocalEngine(NativeEngine()))
    for i, (rk, val) in enumerate(product_kvs()):
        ts = 10 + 2 * i
        r = store.sched_txn_command(Prewrite([Mutation.put(Key.from_raw(rk), val)], rk, ts))
        assert "errors" not in r
        store.sched_txn_command(Commit([Key.from_raw(rk)], ts, ts + 1))
    assert len(store.scan(b"", None, None, 100)) == 6
    snap = store.engine.snapshot(None)
    dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS)])
    r1 = BatchExecutorsRunner(dag, MvccScanSource(snap, 100, [record_range(TABLE_ID)])).handle_request()
    assert len(r1.iter_rows()) == 6
    r2 = BatchExecutorsRunner(
        DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS)]),
        MvccBatchScanSource(snap, 100, [record_range(TABLE_ID)]),
    ).handle_request()
    assert r2.encode() == r1.encode()


def test_native_engine_snapshot_sequence_semantics():
    pytest.importorskip("tikv_tpu.native.engine")
    from tikv_tpu.native.engine import NativeEngine, native_available

    if not native_available():
        pytest.skip("native engine unavailable")
    eng = NativeEngine()
    eng.put_cf(CF_DEFAULT, b"k", b"v1")
    s1 = eng.snapshot()
    eng.put_cf(CF_DEFAULT, b"k", b"v2")
    s2 = eng.snapshot()
    eng.delete_cf(CF_DEFAULT, b"k")
    assert s1.get_cf(CF_DEFAULT, b"k") == b"v1"
    assert s2.get_cf(CF_DEFAULT, b"k") == b"v2"
    assert eng.get(b"k") is None
    s1.release()
    s2.release()
    # after releasing snapshots, later writes compact old versions away
    eng.put_cf(CF_DEFAULT, b"k", b"v3")
    assert eng.get(b"k") == b"v3"


def test_native_bulk_load_sorted_and_random():
    """Hinted O(1) appends for ascending streams; random order falls back to
    the O(log n) path with identical content."""
    import random

    from tikv_tpu.native.engine import NativeEngine

    items = [(b"bk%06d" % i, b"v%d" % i) for i in range(5000)]
    ne = NativeEngine()
    ne.bulk_load("default", items)
    rnd = items[:]
    random.Random(3).shuffle(rnd)
    ne2 = NativeEngine()
    ne2.bulk_load("default", rnd)
    s1, s2 = ne.snapshot(), ne2.snapshot()
    assert list(s1.scan_cf("default", b"bk", b"bl")) == list(s2.scan_cf("default", b"bk", b"bl"))
    assert s1.get_cf("default", b"bk004999") == b"v4999"


def test_native_delete_range_after_hinted_inserts():
    from tikv_tpu.native.engine import NativeEngine
    from tikv_tpu.storage.engine import WriteBatch

    ne = NativeEngine()
    ne.bulk_load("default", [(b"k%02d" % i, b"v") for i in range(20)])
    wb = WriteBatch()
    wb.delete_range_cf("default", b"k05", b"k15")
    ne.write(wb)
    snap = ne.snapshot()
    got = [k for k, _ in snap.scan_cf("default", b"k", b"l")]
    assert got == [b"k%02d" % i for i in list(range(5)) + list(range(15, 20))]


def _touch(engine, op, cf):
    wb = WriteBatch()
    if op == "put":
        wb.put_cf(cf, b"k", b"v")
    elif op == "delete":
        wb.delete_cf(cf, b"k")
    else:
        wb.delete_range_cf(cf, b"a", b"z")
    engine.write(wb)


@pytest.mark.parametrize("op", ["put", "delete", "delete_range"])
def test_cf_touched_seq_moves_with_its_cf_only(engine, op):
    """The per-CF stamp (Snapshot.cf_touched_seq) is the sequence of the
    newest batch that touched that CF: it moves on put, delete and
    delete_range there, stands still under writes to other CFs, and never
    passes the engine's sequence."""
    engine.put_cf(CF_LOCK, b"k", b"v")
    stamp = engine.cf_touched_seq(CF_LOCK)
    assert stamp == engine.seq()
    _touch(engine, op, CF_WRITE)
    _touch(engine, op, CF_DEFAULT)
    assert engine.cf_touched_seq(CF_LOCK) == stamp < engine.seq()
    _touch(engine, op, CF_LOCK)
    assert stamp < engine.cf_touched_seq(CF_LOCK) == engine.seq()
    assert engine.cf_touched_seq(CF_WRITE) < engine.seq()


def test_snapshot_sequence_and_late_stamp(engine):
    """A snapshot reads at its own sequence; the stamp is read from the
    engine when asked, so it can name a batch the snapshot does not hold
    (the race the region cache's lock-free memo must survive)."""
    engine.put_cf(CF_WRITE, b"k", b"v")
    snap = engine.snapshot()
    assert snap.sequence() == engine.seq()
    assert snap.cf_touched_seq(CF_LOCK) <= snap.sequence()
    engine.put_cf(CF_LOCK, b"k", b"v")
    assert snap.sequence() < snap.cf_touched_seq(CF_LOCK) == engine.seq()
    assert snap.get_cf(CF_LOCK, b"k") is None
    assert engine.snapshot().sequence() == engine.seq()


def test_bulk_load_moves_the_stamp(engine):
    engine.bulk_load(CF_LOCK, [(b"a", b"1"), (b"b", b"2")])
    assert 0 < engine.cf_touched_seq(CF_LOCK) == engine.seq()
    assert engine.cf_touched_seq(CF_WRITE) == 0
