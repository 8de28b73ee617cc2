"""The native engine's two batched reads against its per-key ones on one
snapshot: ``eng_multi_get`` against ``eng_get`` (``NativeSnapshot.get_cf``),
``eng_multi_seek_newest`` against a cursor's ``eng_seek`` (the trait's
default ``Snapshot.newest_versions_cf``), with writes after the snapshot,
tombstones, a range delete, keys in flushed runs and in the memtable, bounds,
an empty batch, and a call after ``close()`` (guard.h)."""

import random

import pytest

from tikv_tpu.native import EngineClosed
from tikv_tpu.native.engine import NativeEngine, native_available
from tikv_tpu.storage.engine import CF_LOCK, CF_WRITE, Snapshot, WriteBatch
from tikv_tpu.util.codec import encode_u64_desc

pytestmark = pytest.mark.skipif(not native_available(), reason="native engine unavailable")

MAX_TS = 2**64 - 1


def uk(i: int) -> bytes:
    return b"user%04d" % i


def vk(i: int, ts: int) -> bytes:
    return uk(i) + encode_u64_desc(ts)


@pytest.fixture(params=["memtable", "runs"])
def engine(request, tmp_path):
    eng = (NativeEngine() if request.param == "memtable"
           else NativeEngine(path=str(tmp_path / "kv"), sync=False))
    yield eng
    eng.close()


def flush(eng):
    if eng.path is not None:
        eng.checkpoint()


def history(eng, rng, n=60):
    """Locks on every other key, 0-5 write versions a key, a few deletes of
    both, one range delete; half of it flushed into a run where the engine
    keeps runs, the rest in the memtable."""
    for part in range(2):
        wb = WriteBatch()
        for i in range(part, n, 2):
            if i % 4 < 2:
                wb.put_cf(CF_LOCK, uk(i), b"lock%d" % i)
            for ts in rng.sample(range(10, 200, 7), rng.randint(0, 5)):
                wb.put_cf(CF_WRITE, vk(i, ts), b"w%d@%d" % (i, ts))
        eng.write(wb)
        flush(eng)
    wb = WriteBatch()
    for i in rng.sample(range(n), 8):
        wb.delete_cf(CF_LOCK, uk(i))
        wb.delete_cf(CF_WRITE, vk(i, 10 + 7 * rng.randrange(27)))
    wb.delete_range_cf(CF_LOCK, uk(40), uk(44))
    wb.delete_range_cf(CF_WRITE, vk(30, MAX_TS), vk(32, 0))
    eng.write(wb)


def after_snapshot(eng, n=60):
    """Writes a snapshot taken before must not see."""
    wb = WriteBatch()
    for i in range(0, n, 3):
        wb.put_cf(CF_LOCK, uk(i), b"late")
        wb.put_cf(CF_WRITE, vk(i, 500), b"late")
        wb.delete_cf(CF_WRITE, vk(i, 17))
    wb.delete_range_cf(CF_LOCK, uk(0), uk(n))
    eng.write(wb)


@pytest.mark.parametrize("seed", range(4))
def test_multi_get_is_get_of_each_key(engine, seed):
    rng = random.Random(seed)
    history(engine, rng)
    snap = engine.snapshot()
    after_snapshot(engine)
    ks = [uk(i) for i in range(-2, 64)] + [uk(5), b"", b"user"]
    rng.shuffle(ks)
    for cf in (CF_LOCK, CF_WRITE):
        assert snap.multi_get_cf(cf, ks) == [snap.get_cf(cf, k) for k in ks]
    vks = [vk(i, ts) for i in range(60) for ts in range(10, 200, 7)]
    got = snap.multi_get_cf(CF_WRITE, vks)
    assert got == [snap.get_cf(CF_WRITE, k) for k in vks]
    assert any(got) and not all(got)


@pytest.mark.parametrize("bounds", [(None, None), (uk(10), uk(50)), (vk(10, 100), None),
                                    (None, vk(50, 80))])
@pytest.mark.parametrize("seed", range(3))
def test_multi_seek_newest_is_seek_of_each_key(engine, seed, bounds):
    rng = random.Random(100 + seed)
    history(engine, rng)
    snap = engine.snapshot()
    after_snapshot(engine)
    ks = [uk(i) for i in range(-2, 64)] + [b"user", uk(7)[:-1]]
    rng.shuffle(ks)
    lower, upper = bounds
    for ts in (MAX_TS, 500, 150, 66, 10, 9, 0):
        got = snap.newest_versions_cf(CF_WRITE, ks, ts, lower, upper)
        # the trait's default: one cursor seek (eng_seek) a key
        assert got == Snapshot.newest_versions_cf(snap, CF_WRITE, ks, ts, lower, upper)
    assert any(snap.newest_versions_cf(CF_WRITE, ks, MAX_TS, lower, upper))


def test_a_key_with_more_versions_than_one_locked_walk(engine):
    """64 memtable entries a key are walked under the lock; a key whose newer
    versions, all past the snapshot, outnumber them continues the walk."""
    wb = WriteBatch()
    wb.put_cf(CF_WRITE, vk(1, 5), b"old")
    wb.put_cf(CF_WRITE, vk(2, 5), b"other")
    engine.write(wb)
    snap = engine.snapshot()
    wb = WriteBatch()
    for ts in range(10, 210):
        wb.put_cf(CF_WRITE, vk(1, ts), b"new")
    engine.write(wb)
    ks = [uk(1), uk(2)]
    got = snap.newest_versions_cf(CF_WRITE, ks, MAX_TS)
    assert got == [(vk(1, 5), b"old"), (vk(2, 5), b"other")]
    assert got == Snapshot.newest_versions_cf(snap, CF_WRITE, ks, MAX_TS)
    assert engine.snapshot().newest_versions_cf(CF_WRITE, ks, MAX_TS)[0] == (vk(1, 209), b"new")


def test_an_empty_batch(engine):
    snap = engine.snapshot()
    assert snap.multi_get_cf(CF_LOCK, []) == []
    assert snap.newest_versions_cf(CF_WRITE, [], MAX_TS) == []


def test_refused_after_close(tmp_path):
    eng = NativeEngine()
    wb = WriteBatch()
    wb.put_cf(CF_LOCK, uk(1), b"l")
    eng.write(wb)
    snap = eng.snapshot()
    assert snap.multi_get_cf(CF_LOCK, [uk(1)]) == [b"l"]
    eng.close()
    with pytest.raises(EngineClosed):
        snap.multi_get_cf(CF_LOCK, [uk(1)])
    with pytest.raises(EngineClosed):
        snap.newest_versions_cf(CF_WRITE, [uk(1)], MAX_TS)
