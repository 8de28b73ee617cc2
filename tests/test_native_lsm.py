"""LSM structure of the native engine: sorted runs, bloom/block index,
merge compaction, tombstone masking, merged reads, perf context
(engine_rocks/rocksdb role: WAL + memtable flush + SSTs + compaction).
"""

from __future__ import annotations

import os

import pytest

from tikv_tpu.native.engine import NativeEngine, native_available
from tikv_tpu.storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE, WriteBatch

pytestmark = pytest.mark.skipif(not native_available(), reason="no native engine")


def put(e, key, val, cf=CF_DEFAULT):
    wb = WriteBatch()
    wb.put_cf(cf, key, val)
    e.write(wb)


def delete(e, key, cf=CF_DEFAULT):
    wb = WriteBatch()
    wb.delete_cf(cf, key)
    e.write(wb)


def test_reads_merge_memtable_and_runs(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(100):
        put(e, b"a%03d" % i, b"gen1-%d" % i)
    e.flush()
    assert e.run_count("default") == 1
    # overwrite a subset post-flush: memtable must mask the run
    for i in range(0, 100, 10):
        put(e, b"a%03d" % i, b"gen2-%d" % i)
    for i in range(100):
        want = b"gen2-%d" % i if i % 10 == 0 else b"gen1-%d" % i
        assert e.get_cf(CF_DEFAULT, b"a%03d" % i) == want
    # scan sees the merged view in order
    got = list(e.scan_cf(CF_DEFAULT, b"", None))
    assert [k for k, _ in got] == [b"a%03d" % i for i in range(100)]
    e.close()


def test_tombstone_in_newer_run_masks_older_run(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"k1", b"v1")
    put(e, b"k2", b"v2")
    e.flush()
    delete(e, b"k1")
    e.flush()
    assert e.run_count("default") == 2
    assert e.get_cf(CF_DEFAULT, b"k1") is None
    assert e.get_cf(CF_DEFAULT, b"k2") == b"v2"
    assert [k for k, _ in e.scan_cf(CF_DEFAULT, b"", None)] == [b"k2"]
    # survives recovery
    e.close()
    e2 = NativeEngine(path=str(tmp_path / "db"))
    assert e2.get_cf(CF_DEFAULT, b"k1") is None
    assert e2.get_cf(CF_DEFAULT, b"k2") == b"v2"
    e2.close()


def test_merge_folds_runs_and_drops_bottom_tombstones(tmp_path):
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    for gen in range(4):
        for i in range(50):
            put(e, b"m%03d" % i, b"g%d-%d" % (gen, i))
        e.flush()
    delete(e, b"m007")
    e.flush()
    assert e.run_count("default") == 5
    assert e.merge_runs("default") == 1
    assert e.run_count("default") == 1
    assert e.get_cf(CF_DEFAULT, b"m007") is None
    assert e.get_cf(CF_DEFAULT, b"m008") == b"g3-8"
    # the merged run dropped the tombstone at the bottom level: the key is
    # physically gone after recovery too
    e.close()
    e2 = NativeEngine(path=d)
    assert e2.run_count("default") == 1
    assert e2.get_cf(CF_DEFAULT, b"m007") is None
    assert [k for k, _ in e2.scan_cf(CF_DEFAULT, b"", None)] == [
        b"m%03d" % i for i in range(50) if i != 7]
    e2.close()


def test_snapshot_pins_versions_across_flush(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"s1", b"old")
    snap = e.snapshot()
    put(e, b"s1", b"new")
    e.flush()
    assert snap.get_cf(CF_DEFAULT, b"s1") == b"old"
    assert e.get_cf(CF_DEFAULT, b"s1") == b"new"
    e.close()


def test_reverse_scan_and_seek_for_prev_across_runs(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(0, 100, 2):   # evens in a run
        put(e, b"r%03d" % i, b"run-%d" % i)
    e.flush()
    for i in range(1, 100, 2):   # odds in the memtable
        put(e, b"r%03d" % i, b"mem-%d" % i)
    got = [k for k, _ in e.scan_cf(CF_DEFAULT, b"", None, reverse=True)]
    assert got == [b"r%03d" % i for i in reversed(range(100))]
    got = [k for k, _ in e.scan_cf(CF_DEFAULT, b"r010", b"r020", reverse=True)]
    assert got == [b"r%03d" % i for i in range(19, 9, -1)]
    # seek_for_prev via the snapshot cursor surface
    snap = e.snapshot()
    cur = snap.cursor_cf(CF_DEFAULT)
    assert cur.seek_for_prev(b"r015")
    assert (cur.key(), cur.value()) == (b"r015", b"mem-15")
    assert cur.seek_for_prev(b"r015\xff")
    assert (cur.key(), cur.value()) == (b"r015", b"mem-15")
    assert cur.seek(b"r014")
    assert (cur.key(), cur.value()) == (b"r014", b"run-14")
    snap.release()
    e.close()


def test_deep_version_scan_limit_with_runs(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(20):
        put(e, b"w%02d" % i, b"x", cf=CF_WRITE)
    e.flush()
    got = list(e.scan_cf(CF_WRITE, b"", None, limit=5))
    assert [k for k, _ in got] == [b"w%02d" % i for i in range(5)]
    e.close()


def test_perf_context_counts_bloom_and_blocks(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(500):
        put(e, b"p%04d" % i, b"v" * 50)
    e.flush()
    base = e.perf_context()
    # present key: bloom passes, a block is read
    assert e.get_cf(CF_DEFAULT, b"p0100") == b"v" * 50
    mid = e.perf_context()
    assert mid["gets"] == base["gets"] + 1
    assert mid["blocks_read"] > base["blocks_read"]
    # absent keys: overwhelmingly skipped by the bloom filter
    for i in range(200):
        assert e.get_cf(CF_DEFAULT, b"zz%04d" % i) is None
    end = e.perf_context()
    assert end["bloom_skips"] - mid["bloom_skips"] > 150
    assert end["flushes"] >= 1
    e.close()


def test_mem_limit_keeps_memtable_flat(tmp_path):
    """The 10M-key-load shape scaled to CI: with a memtable cap, a load many
    times that size keeps resident memtable bytes bounded by flushing."""
    e = NativeEngine(path=str(tmp_path / "db"), mem_limit=256 * 1024, sync=False)
    peak = 0
    for i in range(4000):
        put(e, b"L%06d" % i, b"v" * 100)
        peak = max(peak, e.mem_bytes())
    assert peak < 2 * 256 * 1024 + 64 * 1024, f"memtable peaked at {peak}"
    assert e.run_count("default") >= 2
    assert e.perf_context()["flushes"] >= 2
    # everything still readable through the merged view
    assert e.get_cf(CF_DEFAULT, b"L000000") == b"v" * 100
    assert e.get_cf(CF_DEFAULT, b"L003999") == b"v" * 100
    # and after folding into one run
    e.merge_runs("default")
    assert e.run_count("default") == 1
    assert e.get_cf(CF_DEFAULT, b"L002000") == b"v" * 100
    e.close()


def test_partial_flush_discarded_at_recovery(tmp_path):
    """A run file without a completion marker above it is a crashed flush:
    recovery must ignore it and recover from the WAL instead."""
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    put(e, b"c1", b"v1")
    e.flush()
    put(e, b"c2", b"v2")
    e.close()
    # forge a partial flush: a run claiming seq far ahead, but no marker
    runs = [f for f in os.listdir(d) if f.startswith("run0-")]
    assert len(runs) == 1
    src = os.path.join(d, runs[0])
    forged = os.path.join(d, "run0-%016x" % (10**9))
    with open(src, "rb") as f:
        data = bytearray(f.read())
    with open(forged, "wb") as f:
        f.write(data)
    e2 = NativeEngine(path=d)
    assert not os.path.exists(forged)  # discarded
    assert e2.get_cf(CF_DEFAULT, b"c1") == b"v1"
    assert e2.get_cf(CF_DEFAULT, b"c2") == b"v2"
    e2.close()


def test_merge_leftover_inputs_cleaned_at_recovery(tmp_path):
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    put(e, b"x1", b"v1")
    e.flush()
    put(e, b"x2", b"v2")
    e.flush()
    assert e.run_count("default") == 2
    files_before = {f for f in os.listdir(d) if f.startswith("run0-")}
    e.merge_runs("default")
    e.close()
    # simulate crash-before-unlink: restore one input file alongside the
    # merged output (merge keeps the newest input's name)
    e2 = NativeEngine(path=d)
    assert e2.run_count("default") == 1
    assert e2.get_cf(CF_DEFAULT, b"x1") == b"v1"
    assert e2.get_cf(CF_DEFAULT, b"x2") == b"v2"
    e2.close()
    assert len(files_before) == 2


def test_compaction_keeps_tombstones_that_mask_runs(tmp_path):
    # memtable GC must not resurrect: a tombstone whose value lives in a
    # sorted run survives compact() and dies only at a bottom-level merge
    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"k1", b"v1")
    e.flush()
    delete(e, b"k1")
    e.compact()
    assert e.get_cf(CF_DEFAULT, b"k1") is None
    # the masking still holds across flush + recovery
    e.flush()
    e.close()
    e2 = NativeEngine(path=str(tmp_path / "db"))
    assert e2.get_cf(CF_DEFAULT, b"k1") is None
    # bottom-level merge may now drop both versions for good
    e2.merge_runs("default")
    assert e2.get_cf(CF_DEFAULT, b"k1") is None
    e2.close()


def test_delete_range_covers_flushed_runs(tmp_path):
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(20):
        put(e, b"r%02d" % i, b"v%02d" % i)
    e.flush()  # all twenty live only in a run now
    put(e, b"r25", b"vmem")  # and one memtable resident
    wb = WriteBatch()
    wb.delete_range_cf(CF_DEFAULT, b"r00", b"r10")
    e.write(wb)
    for i in range(20):
        want = None if i < 10 else b"v%02d" % i
        assert e.get_cf(CF_DEFAULT, b"r%02d" % i) == want, i
    assert e.get_cf(CF_DEFAULT, b"r25") == b"vmem"
    assert [k for k, _ in e.scan_cf(CF_DEFAULT, b"r00", b"r20")] == [
        b"r%02d" % i for i in range(10, 20)
    ]
    # durable: the range tombstones replay from the WAL
    e.close()
    e2 = NativeEngine(path=str(tmp_path / "db"))
    assert e2.get_cf(CF_DEFAULT, b"r05") is None
    assert e2.get_cf(CF_DEFAULT, b"r15") == b"v15"
    e2.close()


def test_damaged_trusted_run_refuses_open(tmp_path):
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    for i in range(50):
        put(e, b"k%03d" % i, b"v" * 100)
    e.flush()
    e.close()
    run = next(f for f in os.listdir(d) if f.startswith("run0-"))
    with open(os.path.join(d, run), "r+b") as f:
        f.seek(-8, os.SEEK_END)
        f.write(b"\xff" * 4)  # wreck the index/bloom crc: structural damage
    # the WAL covering this run is gone: opening would silently lose
    # acked writes, so the engine must refuse (like a torn WAL segment)
    with pytest.raises(RuntimeError):
        NativeEngine(path=d)


def test_range_tombstone_is_o1_and_masks_runs(tmp_path):
    # delete_range is a real range tombstone (rocksdb DeleteRange role):
    # O(1) on the write path, masking memtable + flushed keys at read time
    e = NativeEngine(path=str(tmp_path / "db"))
    for i in range(30):
        put(e, b"t%02d" % i, b"v%02d" % i)
    e.flush()
    wb = WriteBatch()
    wb.delete_range_cf(CF_DEFAULT, b"t00", b"t10")
    e.write(wb)
    assert e.mem_bytes() < 1024  # no per-key expansion into the memtable
    assert e.get_cf(CF_DEFAULT, b"t05") is None
    assert e.get_cf(CF_DEFAULT, b"t15") == b"v15"
    # re-put after the range delete: newer version wins
    put(e, b"t03", b"resurrected")
    assert e.get_cf(CF_DEFAULT, b"t03") == b"resurrected"
    got = [k for k, _ in e.scan_cf(CF_DEFAULT, b"t00", b"t99")]
    assert got == [b"t03"] + [b"t%02d" % i for i in range(10, 30)]
    # reverse scan applies the same masking
    rev = [k for k, _ in e.scan_cf(CF_DEFAULT, b"t00", b"t99", reverse=True)]
    assert rev == list(reversed(got))
    e.close()


def test_range_tombstone_survives_flush_merge_recovery(tmp_path):
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    for i in range(20):
        put(e, b"k%02d" % i, b"old%02d" % i)
    e.flush()
    snap = e.snapshot()  # pins the pre-delete state
    wb = WriteBatch()
    wb.delete_range_cf(CF_DEFAULT, b"k00", b"k10")
    e.write(wb)
    # snapshot still sees everything; live view does not
    assert snap.get_cf(CF_DEFAULT, b"k05") == b"old05"
    assert e.get_cf(CF_DEFAULT, b"k05") is None
    e.flush()  # tombstone rides into a run
    assert e.get_cf(CF_DEFAULT, b"k05") is None
    assert snap.get_cf(CF_DEFAULT, b"k05") == b"old05"
    snap.release()
    # with the snapshot gone, a merge folds the range delete for good
    e.merge_runs("default")
    assert e.run_count("default") == 1
    assert e.get_cf(CF_DEFAULT, b"k05") is None
    assert e.get_cf(CF_DEFAULT, b"k15") == b"old15"
    e.close()
    e2 = NativeEngine(path=d)
    assert e2.get_cf(CF_DEFAULT, b"k05") is None
    assert e2.get_cf(CF_DEFAULT, b"k15") == b"old15"
    e2.close()


def test_flush_with_empty_memtable_keeps_marker_chain(tmp_path):
    # a flush that produces no runs (all records since the last flush were
    # no-ops) must still advance the completion marker before truncating the
    # WAL — deleting mark-N without a successor would make recovery distrust
    # and unlink every run
    d = str(tmp_path / "db")
    e = NativeEngine(path=d)
    for i in range(10):
        put(e, b"k%d" % i, b"v%d" % i)
    e.flush()
    e.write(WriteBatch())  # advances seq, leaves the memtable empty
    e.flush()
    assert any(f.startswith("mark-") for f in os.listdir(d))
    e.close()
    e2 = NativeEngine(path=d)
    for i in range(10):
        assert e2.get_cf(CF_DEFAULT, b"k%d" % i) == b"v%d" % i
    e2.close()


def test_seek_for_prev_below_range_start(tmp_path):
    # target below the cursor's lower bound must return not-found, not a key
    # outside the range (and must not walk off the front of the memtable)
    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"b", b"1")
    put(e, b"k1", b"2")
    put(e, b"m", b"3")
    e.flush()
    put(e, b"c", b"4")  # memtable resident below the bound
    snap = e.snapshot()
    cur = snap.cursor_cf(CF_DEFAULT, lower=b"k", upper=b"z")
    assert not cur.seek_for_prev(b"a")
    assert cur.seek_for_prev(b"k5")
    assert cur.key() == b"k1"
    snap.release()
    e.close()


def test_in_memory_engine_reclaims_range_deletes_on_compact():
    # with no runs the memtable is the whole store: compact() applies and
    # drops range tombstones no snapshot can see below, reclaiming memory
    e = NativeEngine()  # in-memory
    for i in range(1000):
        put(e, b"g%04d" % i, b"v" * 100)
    high = e.mem_bytes()
    wb = WriteBatch()
    wb.delete_range_cf(CF_DEFAULT, b"g0000", b"g0900")
    e.write(wb)
    assert e.get_cf(CF_DEFAULT, b"g0500") is None
    e.compact()
    assert e.mem_bytes() < high // 5, e.mem_bytes()
    assert e.get_cf(CF_DEFAULT, b"g0500") is None
    assert e.get_cf(CF_DEFAULT, b"g0950") == b"v" * 100
    e.close()


def test_io_classification_and_throttle(tmp_path):
    """Engine IO is tagged per type (file_system role): foreground writes,
    flushes, and compaction each account their bytes, and an attached rate
    limiter sees the requests."""
    from tikv_tpu.util.io_limiter import IoRateLimiter, IoType

    lim = IoRateLimiter(bytes_per_sec=0)  # unlimited, but counts requests
    seen = []
    orig = lim.request

    def spy(nbytes, io_type=None, timeout=5.0):
        seen.append((io_type, nbytes))
        return orig(nbytes, io_type, timeout)

    lim.request = spy
    e = NativeEngine(path=str(tmp_path / "db"), sync=False, io_limiter=lim)
    for i in range(100):
        put(e, b"io%03d" % i, b"v" * 50)
    e.flush()
    for i in range(100, 200):
        put(e, b"io%03d" % i, b"v" * 50)
    e.flush()
    e.merge_runs("default")
    stats = e.io_stats()
    assert stats.get("foreground_write", 0) > 0
    assert stats.get("flush", 0) > 0
    assert stats.get("compaction", 0) > 0
    types = {t for t, _ in seen}
    assert {IoType.FOREGROUND_WRITE, IoType.FLUSH, IoType.COMPACTION} <= types
    e.close()


def test_cold_scan_does_not_block_writers(tmp_path):
    """A cold range scan's run-block IO must not hold the engine lock: a put
    issued mid-scan completes in a fraction of the scan's runtime.  Before
    the MergeIter split (init under the lock, block IO after release) the
    writer waited out the entire scan (engine.cc eng_scan)."""
    import threading
    import time

    e = NativeEngine(path=str(tmp_path / "db"), sync=False)
    val = b"v" * 384
    n_keys = 120_000
    wb = WriteBatch()
    for i in range(n_keys):
        wb.put_cf(CF_DEFAULT, b"k%07d" % i, val)
        if i % 10_000 == 9_999:
            e.write(wb)
            wb = WriteBatch()
            e.flush()  # many cold runs: the scan merges across real block IO
    snap = e.snapshot()
    started = threading.Event()
    scan_s = [0.0]

    def scanner():
        t0 = time.perf_counter()
        started.set()
        n, _ = snap.scan_raw(CF_DEFAULT, b"", None)
        scan_s[0] = time.perf_counter() - t0
        assert n == n_keys

    t = threading.Thread(target=scanner)
    t.start()
    started.wait()
    time.sleep(0.02)  # scanner is inside eng_scan (ctypes released the GIL)
    t0 = time.perf_counter()
    put(e, b"probe-mid-scan", b"x")
    put_s = time.perf_counter() - t0
    t.join()
    snap.release()
    assert e.get_cf(CF_DEFAULT, b"probe-mid-scan") == b"x"
    e.close()
    # enough runtime that a lock-held scan would provably stall the put
    if scan_s[0] <= 0.03:
        pytest.skip(f"scan too fast to measure contention: {scan_s[0]:.3f}s")
    assert put_s < max(0.01, scan_s[0] / 2), (
        f"writer stalled {put_s:.3f}s behind a {scan_s[0]:.3f}s scan"
    )


def test_chunked_scan_crosses_memtable_cap(tmp_path):
    """Scans/seeks re-init in bounded chunks once the memtable walk passes
    the native cap (65536 entries per locked walk, 1024 for seeks); results
    must be seamless across chunk boundaries, including runs of tombstones
    wider than a seek chunk and reverse iteration."""
    e = NativeEngine(path=str(tmp_path / "db"), sync=False)
    n = 100_000
    wb = WriteBatch()
    for i in range(n):
        wb.put_cf(CF_DEFAULT, b"c%06d" % i, b"v%d" % i)
    e.write(wb)  # all resident in the memtable: forces chunked walks
    # tombstone belt wider than the 1024-entry seek chunk
    wb = WriteBatch()
    for i in range(10_000, 12_500):
        wb.delete_cf(CF_DEFAULT, b"c%06d" % i)
    e.write(wb)
    snap = e.snapshot()
    n_live = n - 2_500
    got = list(snap.scan_cf(CF_DEFAULT, b"", None))
    assert len(got) == n_live
    assert got[0][0] == b"c000000" and got[-1][0] == b"c%06d" % (n - 1)
    assert got[9_999][0] == b"c009999" and got[10_000][0] == b"c012500"
    rev = list(snap.scan_cf(CF_DEFAULT, b"", None, reverse=True))
    assert [k for k, _ in rev] == [k for k, _ in got][::-1]
    # limited scan stops exactly at the limit across a chunk edge
    lim = list(snap.scan_cf(CF_DEFAULT, b"c009000", None, limit=3_000))
    assert len(lim) == 3_000 and lim[-1][0] == b"c014499"
    # seek across the tombstone belt (forward) and back over it (for_prev)
    cur = snap.cursor_cf(CF_DEFAULT)
    assert cur.seek(b"c010000")
    assert cur.key() == b"c012500"
    assert cur.seek_for_prev(b"c012499")
    assert cur.key() == b"c009999"
    snap.release()
    e.close()


def test_reads_do_not_serialize_behind_wal_sync(tmp_path):
    """The commit path's WAL append + fdatasync runs under the writer lock
    only (engine.cc write_mu): point reads and scans must keep flowing while
    a large batch is in its IO phase, instead of queueing behind the
    engine's unique lock as before."""
    import threading
    import time

    from tikv_tpu.native.engine import NativeEngine, native_available

    if not native_available():
        pytest.skip("native engine unavailable")
    eng = NativeEngine(path=str(tmp_path / "db"), sync=True)
    for i in range(200):
        eng.put_cf("default", b"seed-%04d" % i, b"v" * 100)
    snap_done = threading.Event()
    write_done = threading.Event()
    reads_during = [0]

    def reader():
        snap_done.set()
        while not write_done.is_set():
            assert eng.get_cf("default", b"seed-0100") is not None
            n = 0
            for _k, _v in eng.snapshot().scan_cf("default", b"seed-", b"seed-\xff"):
                n += 1
                if n >= 50:
                    break
            reads_during[0] += 1

    t = threading.Thread(target=reader)
    t.start()
    snap_done.wait()
    # a fat batch: its WAL write+fsync dominates its in-memory apply
    wb_val = b"x" * (1 << 20)
    t0 = time.perf_counter()
    for i in range(60):
        eng.put_cf("default", b"big-%02d" % i, wb_val)
    wt = time.perf_counter() - t0
    write_done.set()
    t.join()
    eng.close()
    # with the old single-lock commit path the reader managed ~0-2 rounds
    # while 60MB of synced batches went through; off-lock WAL IO gives it
    # hundreds.  10 is a conservative floor that still proves overlap.
    assert reads_during[0] >= 10, (reads_during[0], wt)


def test_cf_touched_seq_survives_flush_and_merge(tmp_path):
    """Flushes, run merges and compaction move versions between the memtable
    and runs; no snapshot reads anything else for them, so the per-CF stamp
    stands still through all three, and goes on counting after them."""
    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"l1", b"x", cf=CF_LOCK)
    delete(e, b"l1", cf=CF_LOCK)
    stamp = e.cf_touched_seq(CF_LOCK)
    assert stamp == e.seq()
    for gen in range(3):
        put(e, b"w%d" % gen, b"v", cf=CF_WRITE)
        e.flush()
        assert e.cf_touched_seq(CF_LOCK) == stamp < e.seq()
    assert e.run_count("lock") == 1 and e.run_count("write") == 3
    assert e.merge_runs("write") == 1
    e.compact()
    assert e.cf_touched_seq(CF_LOCK) == stamp
    assert e.cf_touched_seq(CF_WRITE) == e.seq()
    wb = WriteBatch()
    wb.delete_range_cf(CF_LOCK, b"a", b"z")
    e.write(wb)
    assert e.cf_touched_seq(CF_LOCK) == e.seq() > stamp
    e.close()


def test_cf_touched_seq_after_reopen_and_ingest(tmp_path):
    """A reopened engine cannot say which CF its runs' newest batch touched:
    every stamp starts at the recovered sequence (too high costs a scan,
    never skips one).  An ingested SST stamps the CFs it loads, no other."""
    from tikv_tpu.native.engine import build_sst

    e = NativeEngine(path=str(tmp_path / "db"))
    put(e, b"l1", b"x", cf=CF_LOCK)
    put(e, b"w1", b"v", cf=CF_WRITE)
    e.flush()
    put(e, b"w2", b"v", cf=CF_WRITE)  # replayed from the WAL on reopen
    assert e.cf_touched_seq(CF_LOCK) < e.seq()
    seq = e.seq()
    e.close()
    e = NativeEngine(path=str(tmp_path / "db"))
    assert e.seq() == seq
    assert e.cf_touched_seq(CF_LOCK) == e.cf_touched_seq(CF_WRITE) == seq
    sst = str(tmp_path / "in.sst")
    build_sst(sst, [("write", b"w3", b"v")])
    e.ingest_sst(sst)
    assert e.cf_touched_seq(CF_LOCK) == seq < e.cf_touched_seq(CF_WRITE) == e.seq()
    build_sst(sst, [("lock", b"l2", b"x")])
    e.ingest_sst(sst)
    assert e.cf_touched_seq(CF_LOCK) == e.seq() > e.cf_touched_seq(CF_WRITE)
    e.close()
