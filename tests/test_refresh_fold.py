"""Reads under a refresh stream: the region cache folds each committed batch
into its warm image off the manager lock.

Every answer is held to the plain reference, a cache-off endpoint over the
same snapshot at the same ``start_ts``.  The writer is shaped as TPC-H's
refresh functions are: RF1 appends new rows past the image's last handle, RF2
deletes the oldest rows, each a prewrite batch (locks) then a commit batch
(writes, locks released), every batch announced through the write-through
hook as the raft apply path announces it.
"""

import threading
import time

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID
from fixtures import lock_key, put_committed

from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Limit, TableScan
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr import region_cache as rc
from tikv_tpu.copr.region_cache import RegionColumnCache, notify_region_write
from tikv_tpu.copr.rpn import col
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_LOCK, CF_WRITE, WriteBatch
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.mvcc.reader import KeyIsLockedError
from tikv_tpu.storage.txn_types import Key, Lock, LockType, Write, WriteType
from tikv_tpu.util.metrics import REGISTRY

NON_HANDLE = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
REGION = 7
N_ROWS = 300
BLOCK_ROWS = 64  # several blocks, so a fold touches some and shares the rest


def _row(i: int) -> bytes:
    name = [b"apple", b"banana", b"cherry"][i % 3]
    return encode_row(NON_HANDLE, [name, i * 7 % 23, 100 + i])


def _scan_dag():
    return DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS), Limit(1 << 20)])


def _agg_dag():
    aggs = [AggDescriptor("sum", col(2)), AggDescriptor("count", None)]
    return DagRequest(executors=[
        TableScan(TABLE_ID, PRODUCT_COLUMNS), Aggregation([col(1)], aggs)])


class _Held(LocalEngine):
    """Hands out ``held`` (a snapshot frozen earlier) while it is set."""

    held = None

    def snapshot(self, ctx=None):
        return self.held if self.held is not None else super().snapshot(ctx)


class Stream:
    """One region under an RF1/RF2-shaped writer, a warm endpoint over it
    and the cache-off reference, one timestamp oracle."""

    def __init__(self, seed: int, n: int = N_ROWS, mk_dag=_scan_dag):
        self.rng = np.random.default_rng(seed)
        self.kv = BTreeEngine()
        for i in range(n):
            put_committed(self.kv, record_key(TABLE_ID, i), _row(i), 90, 100)
        self.eng = _Held(self.kv)
        self.warm = Endpoint(self.eng, enable_device=True, block_rows=BLOCK_ROWS)
        self.cold = Endpoint(self.eng, enable_device=True, enable_region_cache=False)
        self.mk_dag = mk_dag
        self.tso = 200
        self.apply_index = 3
        self.next_handle = n
        self.oldest = 0
        self.outcomes: dict = {}

    def ts(self) -> int:
        self.tso += int(self.rng.integers(1, 4))
        return self.tso

    def batch(self, ops) -> None:
        wb = WriteBatch()
        for op, cf, key, val in ops:
            if op == "put":
                wb.put_cf(cf, key, val)
            else:
                wb.delete_cf(cf, key)
        self.kv.write(wb)
        self.apply_index += 1
        notify_region_write(REGION, ops, self.apply_index)

    def txn(self, muts) -> int:
        """Prewrite then commit ``muts`` [(handle, value | None)]: two
        batches; returns the commit_ts."""
        start = self.ts()
        keys = [Key.from_raw(record_key(TABLE_ID, h)) for h, _v in muts]
        primary = keys[0].to_raw()
        self.batch([("put", CF_LOCK, k.encoded,
                     Lock(LockType.PUT if v is not None else LockType.DELETE,
                          primary, start, 3000, short_value=v).to_bytes())
                    for k, (_h, v) in zip(keys, muts)])
        commit = self.ts()
        ops = []
        for k, (_h, v) in zip(keys, muts):
            w = (Write(WriteType.PUT, start, short_value=v) if v is not None
                 else Write(WriteType.DELETE, start))
            ops.append(("put", CF_WRITE, k.append_ts(commit).encoded, w.to_bytes()))
            ops.append(("delete", CF_LOCK, k.encoded, None))
        self.batch(ops)
        return commit

    def rf1(self, k: int) -> int:
        hs = range(self.next_handle, self.next_handle + k)
        self.next_handle += k
        return self.txn([(h, _row(h)) for h in hs])

    def rf2(self, k: int) -> int:
        hs = range(self.oldest, self.oldest + k)
        self.oldest += k
        return self.txn([(h, None) for h in hs])

    def freeze(self):
        return self.kv.snapshot(), self.apply_index

    def read(self, ts: int, frozen=None) -> str:
        snap, apply_index = frozen or (None, self.apply_index)
        req = CoprRequest(103, self.mk_dag(), [record_range(TABLE_ID)], ts, context={
            "region_id": REGION, "region_epoch": (1, 1), "apply_index": apply_index})
        self.eng.held = snap
        try:
            r = self.warm.handle_request(req)
            want = self.cold.handle_request(req)
        finally:
            self.eng.held = None
        outcome = r.metrics["region_cache"]
        assert r.from_device and r.data == want.data, (ts, apply_index, outcome)
        self.outcomes[outcome] = self.outcomes.get(outcome, 0) + 1
        return outcome

    def image(self):
        (img,) = self.warm.region_cache._images.values()
        return img


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_folded_image_equals_a_fresh_build_after_every_commit(seed):
    """Every commit of an RF1/RF2 stream, read below it (it stays pending),
    at it and after it at the reader's own apply index, and through a
    snapshot from before its batches: each answer equals the cache-off
    endpoint's, and no read walks the region's CF_WRITE."""
    s = Stream(seed)
    assert s.read(s.ts()) == "miss"
    for _ in range(8):
        for fn in (s.rf1, s.rf2):
            frozen = s.freeze()
            commit = fn(int(s.rng.integers(3, 20)))
            for ts in (commit - 1, commit, s.ts()):
                s.read(ts)
            s.read(s.ts(), frozen=frozen)
    st = s.warm.region_cache.stats
    assert st.deltas == 0, (st.to_dict(), s.outcomes)
    assert s.outcomes.get("wt_delta", 0) >= 16 and st.prefix_folds >= 16, s.outcomes
    # the appends reserved room: the block count moved once, to a power of two
    img = s.image()
    n_blocks = len(img.block_cache.blocks)
    assert n_blocks & (n_blocks - 1) == 0 and img.reserved


def test_reader_below_a_pending_commit_is_folded_the_prefix():
    """Two commits pending at the reader's apply index, one below its
    start_ts and one above: the reader is answered by folding the first
    alone — no walk — and never sees the second, which stays pending and is
    folded for the next reader above it."""
    s = Stream(11)
    s.read(s.ts())
    first = s.rf1(5)
    reader_ts = s.ts()
    second = s.rf2(7)
    assert first < reader_ts < second
    walks = s.warm.region_cache.stats.deltas
    assert s.read(reader_ts) == "wt_delta"
    img = s.image()
    assert img.apply_index == s.apply_index and img.max_commit_ts == first
    pending = [w for _i, ws, _l in img.chain for w in ws]
    assert pending and all(c == second for _h, c, _v in pending)
    assert s.read(s.ts()) == "wt_delta"
    assert img.max_commit_ts == second and not img.chain
    assert s.warm.region_cache.stats.deltas == walks


def test_a_structural_fold_builds_no_new_program():
    """Appends and deletes keep the block count (the room reserved at the
    first append) and every column's encoding, so the programs a read runs
    are the ones it ran before: once the stream is going, a read after a
    structural fold compiles nothing."""
    from benchmark.counters import CompileCount

    s = Stream(21, mk_dag=_agg_dag)
    s.read(s.ts())
    for _ in range(3):
        s.rf1(9)
        s.rf2(9)
        s.read(s.ts())
    compiles = CompileCount()
    compiles.listen()
    for _ in range(3):
        s.rf1(9)
        s.rf2(9)
        assert s.read(s.ts()) == "wt_delta"
    assert compiles.programs == 0
    assert s.warm.region_cache.stats.deltas == 0


def _fallbacks() -> float:
    return REGISTRY.counter("tikv_coprocessor_device_fallback_total", "").get()


def test_a_lock_met_on_the_device_path_answers_locked():
    """A reader that meets a writer's lock inside the device path is
    answered ``locked`` — what the client backs off and retries on — and it
    is no device fault: no CPU fallback, the breaker stays closed."""
    kv = BTreeEngine()
    for t in (TABLE_ID, TABLE_ID + 1):
        for i in range(40):
            put_committed(kv, record_key(t, i), _row(i), 90, 100)
    ep = Endpoint(LocalEngine(kv), enable_device=True)

    def req(table, region, ts):
        dag = DagRequest(executors=[
            TableScan(table, PRODUCT_COLUMNS),
            Aggregation([col(1)], [AggDescriptor("count", None)])])
        return CoprRequest(103, dag, [record_range(table)], ts, context={
            "region_id": region, "region_epoch": (1, 1), "apply_index": 3})

    ep.handle_batch([req(TABLE_ID, 7, 200), req(TABLE_ID + 1, 8, 200)])
    lock_key(kv, record_key(TABLE_ID, 4), record_key(TABLE_ID, 4), 250)
    before = _fallbacks()
    with pytest.raises(KeyIsLockedError):
        ep.handle_request(req(TABLE_ID, 7, 300))
    results, errors = ep.handle_batch_errors(
        [req(TABLE_ID, 7, 310), req(TABLE_ID + 1, 8, 310)])
    assert isinstance(errors[0], KeyIsLockedError) and results[0] is None
    assert errors[1] is None and results[1].from_device
    assert ep.device_fallbacks == 0 and _fallbacks() == before
    assert all(ep.breaker.state_of(p) == "closed" for p in ("unary", "xregion"))


def test_a_repair_holds_up_neither_other_images_nor_apply_write(monkeypatch):
    """One image's repair runs under that image's own lock: while a walk of
    region 7 is held inside its CF_WRITE scan, a reader of region 8 is
    served and a write-through notify for region 7 is taken in."""
    kv = BTreeEngine()
    for t in (TABLE_ID, TABLE_ID + 1):
        for i in range(40):
            put_committed(kv, record_key(t, i), _row(i), 90, 100)
    cache = RegionColumnCache()

    def serve(region, table, ts, apply_index):
        ctx = {"region_id": region, "region_epoch": (1, 1), "apply_index": apply_index}
        return cache.serve(kv.snapshot(), ctx, PRODUCT_COLUMNS,
                           [record_range(table)], ts)[1]

    assert serve(7, TABLE_ID, 200, 3) == "miss"
    assert serve(8, TABLE_ID + 1, 200, 3) == "miss"
    # a write no notify announced: region 7's next reader walks
    put_committed(kv, record_key(TABLE_ID, 100), _row(100), 210, 220)
    in_scan, release = threading.Event(), threading.Event()
    real = rc.scan_delta

    def held_scan(*a, **k):
        in_scan.set()
        assert release.wait(30)
        return real(*a, **k)

    monkeypatch.setattr(rc, "scan_delta", held_scan)
    walked = []
    walker = threading.Thread(target=lambda: walked.append(serve(7, TABLE_ID, 300, 4)))
    walker.start()
    try:
        assert in_scan.wait(30)
        done = []
        other = threading.Thread(target=lambda: done.append(serve(8, TABLE_ID + 1, 300, 3)))
        other.start()
        other.join(10)
        assert done == ["hit"], "a reader of another image waited on the repair"
        k = Key.from_raw(record_key(TABLE_ID, 101))
        notifier = threading.Thread(target=lambda: notify_region_write(7, [(
            "put", CF_WRITE, k.append_ts(240).encoded,
            Write(WriteType.PUT, 230, short_value=_row(101)).to_bytes())], 5))
        notifier.start()
        notifier.join(10)
        assert not notifier.is_alive(), "apply_write waited on the repair"
        assert not release.is_set() and walker.is_alive()
    finally:
        release.set()
        walker.join(30)
    assert walked == ["delta"]


def test_a_snapshot_taken_while_a_batch_is_announced_holds_none_of_it():
    """The raft apply path announces a batch to the region cache before the
    batch reaches the engine, and advances its apply index with the engine
    write as one step to a snapshot: a snapshot taken in between claims the
    previous index and holds none of the batch; one taken after claims the
    batch's index and holds all of it."""
    from tikv_tpu.util import keys
    from tikv_tpu.raft.cluster import FIRST_REGION_ID, Cluster
    from tikv_tpu.raft.raftkv import _applied_snapshot

    c = Cluster(1)
    c.run()
    kv = c.raftkv(1)
    peer = c.stores[1].peers[FIRST_REGION_ID]
    engine = c.stores[1].engine
    key = Key.from_raw(record_key(TABLE_ID, 1))
    stored = keys.data_key(key.append_ts(100).encoded)
    seen = []

    class Watcher(RegionColumnCache):
        def apply_write(self, region_id, parse_once, apply_index, token=None):
            if region_id == FIRST_REGION_ID:
                claimed, snap = _applied_snapshot(peer, engine)
                seen.append((apply_index, claimed, snap.get_cf(CF_WRITE, stored)))

    watcher = Watcher()
    wb = WriteBatch()
    wb.put_cf(CF_WRITE, key.append_ts(100).encoded,
              Write(WriteType.PUT, 90, short_value=_row(1)).to_bytes())
    kv.write({"region_id": FIRST_REGION_ID}, wb)
    assert seen, "the batch was not announced"
    index, claimed, held = seen[-1]
    assert claimed < index and held is None
    deadline = time.monotonic() + 10
    while peer.apply_index < index and time.monotonic() < deadline:
        time.sleep(0.01)
    claimed, snap = _applied_snapshot(peer, engine)
    assert claimed >= index
    assert snap.get_cf(CF_WRITE, stored) is not None
    del watcher


def test_readers_of_two_images_under_a_writer_all_equal_the_reference():
    """Stress: eight reader threads over two regions' images and a writer
    committing RF1/RF2-shaped transactions to both, the interpreter switching
    threads every 10 us.  The writer announces each batch before it reaches
    the engine and advances the apply index with the write as one step, as
    the raft apply path does; a reader samples (apply index, snapshot) as one
    step, as RaftKv does.  Every answer equals the cache-off endpoint's over
    the same snapshot — a fold that tore another reader's version, or lost a
    batch, would not."""
    import sys

    kv = BTreeEngine()
    tables = {7: TABLE_ID, 8: TABLE_ID + 1}
    for t in tables.values():
        for i in range(N_ROWS):
            put_committed(kv, record_key(t, i), _row(i), 90, 100)
    held = threading.local()

    class PerThread(LocalEngine):
        def snapshot(self, ctx=None):
            return held.snap

    eng = PerThread(kv)
    warm = Endpoint(eng, enable_device=True, block_rows=BLOCK_ROWS)
    cold = Endpoint(eng, enable_device=True, enable_region_cache=False)
    step = threading.Lock()
    state = {"tso": 200, "apply": {7: 3, 8: 3}, "next": {7: N_ROWS, 8: N_ROWS},
             "oldest": {7: 0, 8: 0}}
    stop = time.monotonic() + 1.5
    wrong: list = []
    served: dict = {}

    def ts() -> int:
        with step:
            state["tso"] += 1
            return state["tso"]

    def batch(region, ops) -> None:
        index = state["apply"][region] + 1
        notify_region_write(region, ops, index)
        wb = WriteBatch()
        for op, cf, key, val in ops:
            if op == "put":
                wb.put_cf(cf, key, val)
            else:
                wb.delete_cf(cf, key)
        with step:
            kv.write(wb)
            state["apply"][region] = index

    def writer():
        rng = np.random.default_rng(5)
        try:
            while time.monotonic() < stop:
                region = int(rng.choice([7, 8]))
                k = int(rng.integers(2, 9))
                if rng.random() < 0.5:
                    hs = range(state["next"][region], state["next"][region] + k)
                    state["next"][region] += k
                    muts = [(h, _row(h)) for h in hs]
                else:
                    hs = range(state["oldest"][region], state["oldest"][region] + k)
                    state["oldest"][region] += k
                    muts = [(h, None) for h in hs]
                start = ts()
                keys = [Key.from_raw(record_key(tables[region], h)) for h, _v in muts]
                batch(region, [("put", CF_LOCK, key.encoded,
                                Lock(LockType.PUT, keys[0].to_raw(), start, 3000).to_bytes())
                               for key in keys])
                commit = ts()
                ops = []
                for key, (_h, v) in zip(keys, muts):
                    w = (Write(WriteType.PUT, start, short_value=v) if v is not None
                         else Write(WriteType.DELETE, start))
                    ops.append(("put", CF_WRITE, key.append_ts(commit).encoded, w.to_bytes()))
                    ops.append(("delete", CF_LOCK, key.encoded, None))
                batch(region, ops)
        except Exception as e:  # noqa: BLE001 — a dead writer must fail the test
            wrong.append(("writer", repr(e)))

    def reader(seed):
        rng = np.random.default_rng(seed)
        while time.monotonic() < stop:
            region = int(rng.choice([7, 8]))
            start_ts = ts()
            with step:
                apply_index, held.snap = state["apply"][region], kv.snapshot()
            dag = DagRequest(executors=[
                TableScan(tables[region], PRODUCT_COLUMNS), Limit(1 << 20)])
            req = CoprRequest(103, dag, [record_range(tables[region])], start_ts, context={
                "region_id": region, "region_epoch": (1, 1), "apply_index": apply_index})
            try:
                r = warm.handle_request(req)
            except KeyIsLockedError:
                continue
            except Exception as e:  # noqa: BLE001
                wrong.append((start_ts, repr(e)))
                continue
            want = cold.handle_request(req)
            outcome = r.metrics.get("region_cache")
            served[outcome] = served.get(outcome, 0) + 1
            if r.data != want.data:
                wrong.append((region, start_ts, apply_index, outcome))

    for region, table in tables.items():
        held.snap = kv.snapshot()
        warm.handle_request(CoprRequest(103, DagRequest(executors=[
            TableScan(table, PRODUCT_COLUMNS), Limit(1 << 20)]), [record_range(table)],
            ts(), context={"region_id": region, "region_epoch": (1, 1), "apply_index": 3}))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=writer)] + [
            threading.Thread(target=reader, args=(s,)) for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not wrong, wrong[:5]
    assert served.get("wt_delta", 0) > 0, served


def test_an_insert_into_a_full_block_repacks_and_keeps_the_block_count():
    """An insert between two rows of a full block has nowhere to go in that
    block: the image repacks from its resident columns into a new version
    that keeps the blocks the first append reserved, and every answer still
    equals the cache-off endpoint's."""
    s = Stream(31, n=0)
    for i in range(0, 2 * 2 * BLOCK_ROWS, 2):  # two full blocks, odd handles free
        put_committed(s.kv, record_key(TABLE_ID, i), _row(i), 90, 100)
    s.next_handle = 4 * BLOCK_ROWS
    assert s.read(s.ts()) == "miss"
    img = s.image()
    assert [b.n_valid for b in img.block_cache.blocks] == [BLOCK_ROWS, BLOCK_ROWS]
    s.rf1(3)
    assert s.read(s.ts()) == "wt_delta"
    reserved = len(img.block_cache.blocks)
    assert reserved == 8 and img.block_cache.blocks[0].n_valid == BLOCK_ROWS
    commit = s.txn([(51, _row(51)), (53, _row(53))])
    assert s.read(s.ts()) == "wt_delta"
    blocks = img.block_cache.blocks
    assert len(blocks) == reserved
    assert sum(b.n_valid for b in blocks) == 2 * BLOCK_ROWS + 5
    s.read(commit - 1)
    s.rf2(5)
    assert s.read(s.ts()) == "wt_delta"
    assert s.warm.region_cache.stats.deltas == 0
