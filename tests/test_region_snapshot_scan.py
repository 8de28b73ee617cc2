"""RegionSnapshot's bulk scans equal the generic cursor walk they replace.

The served path reads every region through a ``RegionSnapshot``.  Without a
``scan_cf`` of its own it inherited the cursor walk (one engine seek per row:
over the native engine one FFI call and one merge of memtable and runs per
row), and without ``scan_spans`` (``scan_raw`` until PR 33) the vectorised
MVCC resolver never took its zero-copy path on a raft-backed store; chip_smoke's cold fills of 300,000-row
regions outlasted a 120 s client timeout that way (ISSUE 22)."""

import pytest

from tikv_tpu.native.engine import NativeEngine, native_available, parse_frames
from tikv_tpu.raft.raftkv import RegionSnapshot
from tikv_tpu.raft.region import Region, RegionEpoch
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_DEFAULT, CF_WRITE, Snapshot, WriteBatch
from tikv_tpu.util import keys

pytestmark = pytest.mark.skipif(not native_available(), reason="no native engine")


def _fill(engine, uniform: bool):
    wb = WriteBatch()
    for i in range(200):
        k = b"k%04d" % i if uniform else b"k%04d" % i + b"x" * (i % 3)
        wb.put_cf(CF_WRITE, keys.data_key(k), b"v%04d" % i)
        wb.put_cf(CF_DEFAULT, keys.data_key(k), b"d" * (i % 5))
    wb.put_cf(CF_WRITE, b"y-not-data", b"local")  # below the data prefix
    engine.write(wb)
    return engine


def _region_snap(engine, start=b"k0020", end=b"k0150"):
    return RegionSnapshot(engine.snapshot(), Region(7, start, end, RegionEpoch(), []))


RANGES = [(b"", None), (b"k0000", b"k9999"), (b"k0050", b"k0060"),
          (b"k0140", b"k0199"), (b"k0160", b"k0170"), (b"k0055", b"k0055"),
          (b"a", b"k0030")]


@pytest.mark.parametrize("uniform", [True, False], ids=["one-size", "mixed-size"])
@pytest.mark.parametrize("engine", [NativeEngine, BTreeEngine])
def test_scan_cf_equals_cursor_walk(engine, uniform):
    snap = _region_snap(_fill(engine(), uniform))
    for cf in (CF_WRITE, CF_DEFAULT):
        for start, end in RANGES:
            for limit, reverse in ((None, False), (7, False), (None, True), (5, True)):
                want = list(Snapshot.scan_cf(snap, cf, start, end, limit, reverse))
                got = [(bytes(k), bytes(v)) for k, v in
                       snap.scan_cf(cf, start, end, limit, reverse)]
                assert got == want, (cf, start, end, limit, reverse)
    assert all(b"k0020" <= k < b"k0150" for k, _v in snap.scan_cf(CF_WRITE, b"", None))


@pytest.mark.parametrize("uniform", [True, False], ids=["one-size", "mixed-size"])
def test_scan_spans_equal_scan_cf(uniform):
    from tikv_tpu.copr.byterows import ByteRows

    eng = _fill(NativeEngine(), uniform)
    snap = _region_snap(eng)
    for start, end in RANGES:
        buf, k_at, k_len, v_at, v_len = snap.scan_spans(CF_WRITE, start, end)
        want = list(Snapshot.scan_cf(snap, CF_WRITE, start, end))
        assert len(k_at) == len(want)
        assert list(zip(ByteRows(buf, k_at, k_len), ByteRows(buf, v_at, v_len))) == want
    # the engine's own snapshot: the same frames, the z prefix still on
    buf, k_at, k_len, v_at, v_len = eng.snapshot().scan_spans(CF_WRITE, b"z", b"{")
    n, raw = eng.snapshot().scan_raw(CF_WRITE, b"z", b"{")
    assert buf == raw and n == len(k_at) == 200
    assert list(zip(ByteRows(buf, k_at, k_len), ByteRows(buf, v_at, v_len))) == [
        (bytes(k), bytes(v)) for k, v in parse_frames(raw, n)]


def test_scan_spans_only_over_an_engine_that_has_it():
    assert not hasattr(_region_snap(_fill(BTreeEngine(), True)), "scan_spans")


def test_batch_resolver_takes_its_matrix_path_over_a_region_snapshot():
    """The vectorised MVCC resolver over a RegionSnapshot gives what the
    per-key scanner gives, through ``scan_spans``."""
    from tikv_tpu.copr.executors import MvccScanSource
    from tikv_tpu.copr.mvcc_batch import MvccBatchScanSource
    from tikv_tpu.copr.table import record_key
    from tikv_tpu.storage.txn_types import Key, Write, WriteType

    eng = NativeEngine()
    wb = WriteBatch()
    for h in range(300):
        for ts in (10, 30):
            wb.put_cf(CF_WRITE, keys.data_key(
                Key.from_raw(record_key(5, h)).append_ts(ts + 1).encoded),
                Write(WriteType.PUT, ts, short_value=b"row%04d@%02d" % (h, ts)).to_bytes())
    eng.write(wb)
    lo, hi = record_key(5, 50), record_key(5, 250)
    snap = RegionSnapshot(eng.snapshot(), Region(
        9, Key.from_raw(lo).encoded, Key.from_raw(hi).encoded, RegionEpoch(), []))
    calls = []
    real = snap.scan_spans
    snap.scan_spans = lambda *a: calls.append(a) or real(*a)
    for ts in (20, 100):
        rng = [(record_key(5, 0), record_key(5, 300))]
        got_k, got_v = MvccBatchScanSource(snap, ts, rng)._resolve_all()
        src = MvccScanSource(snap, ts, rng)
        want_k, want_v, done = src.next_batch(1000)
        assert done and len(want_k) == 200
        assert [bytes(k) for k in got_k] == want_k
        assert [bytes(v) for v in got_v] == want_v
    assert len(calls) == 2
