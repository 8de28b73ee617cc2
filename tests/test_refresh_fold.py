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
    and the cache-off reference, one timestamp oracle.  ``row`` makes row
    ``i``'s value (``bump`` makes another one for the same handle);
    ``handles`` are the loaded rows' handles."""

    def __init__(self, seed: int, n: int = N_ROWS, mk_dag=_scan_dag, row=None,
                 block_rows: int = BLOCK_ROWS, encode: bool = True, handles=None):
        self.rng = np.random.default_rng(seed)
        self.row = row or (lambda i, bump=0: _row(i + bump))
        self.kv = BTreeEngine()
        for i in (range(n) if handles is None else handles):
            put_committed(self.kv, record_key(TABLE_ID, i), self.row(i), 90, 100)
        self.eng = _Held(self.kv)
        self.warm = Endpoint(self.eng, enable_device=True, block_rows=block_rows,
                             encode_columns=encode)
        self.cold = Endpoint(self.eng, enable_device=True, enable_region_cache=False)
        self.fresh_args = {"block_rows": block_rows, "encode_columns": encode}
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
        return self.txn([(h, self.row(h)) for h in hs])

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
    snapshot from before its batches (folded onto a kept earlier version at
    that snapshot's apply index, never a fresh scan): each answer equals the
    cache-off endpoint's, and no read walks the region's CF_WRITE."""
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
    assert st.deltas == 0 and st.stale == 0, (st.to_dict(), s.outcomes)
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


# -- a fold re-places the rows it changed, on the host and on the device ------

from tikv_tpu.copr.datatypes import ColumnInfo, FieldType  # noqa: E402

LAYOUT_COLUMNS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),  # one of three words: dictionary-coded
    ColumnInfo(3, FieldType.int64()),    # 0..22, no runs: bitpacked
    ColumnInfo(4, FieldType.int64()),    # handle // 50: run-length
]


def _layout_row(i: int, bump: int = 0) -> bytes:
    """Row ``i``; ``bump`` changes its word and its number, never its run."""
    name = [b"apple", b"banana", b"cherry"][(i + bump) % 3]
    return encode_row(LAYOUT_COLUMNS[1:], [name, (i * 7 + bump) % 23, i // 50])


def _layout_dag(group: bool, summed):
    aggs = ([AggDescriptor("sum", col(summed))] if summed is not None else []) + [
        AggDescriptor("count", None)]
    return lambda: DagRequest(executors=[
        TableScan(TABLE_ID, LAYOUT_COLUMNS), Aggregation([col(1)] if group else [], aggs)])


# layout -> (images encoded, the plan (what its stacked pin ships), how a
# small edit is re-placed: a pin with a run-length leaf ships the block)
LAYOUTS = {
    "plain": (False, _layout_dag(True, 2), "edit"),
    "bitpacked": (True, _layout_dag(False, 2), "edit"),
    "dictionary": (True, _layout_dag(True, None), "edit"),
    "run_length": (True, _layout_dag(False, 3), "block"),
}
FOLD_EDITS = ("tail_append", "append_into_next_block", "head_delete",
              "mid_block_update", "mid_block_insert", "scattered_insert", "large_insert",
              "fork")


def _placed() -> dict:
    c = REGISTRY.counter("tikv_coprocessor_region_cache_fold_place_total", "")
    return {how: c.get(how=how) for how in ("edit", "block")}


def _pins_equal_a_fresh_build(ep, dag, cache) -> None:
    """The stacked pins and the n_valid/offset pin ``cache`` carries equal
    the ones the evaluator builds afresh from the same host blocks."""
    import jax

    from tikv_tpu.copr import encoding
    from tikv_tpu.copr.cache import ColumnBlockCache, _Block

    ev, _params = ep._bind(dag)
    fresh = ColumnBlockCache(cache.key)
    fresh.blocks = [_Block(b.cols, b.n_valid) for b in cache.blocks]
    fresh.filled, fresh.enc_version = True, cache.enc_version
    pins = dict(cache.blocks[0].device)
    kinds = {sig[0] for sig in pins if isinstance(sig[0], str)}
    assert "nvoff" in kinds and kinds & {"stacked", "stackedenc"}, kinds
    for sig, entry in pins.items():
        if sig[0] == "nvoff":
            want = ev._nvoff_device(fresh, fresh.blocks)
        elif sig[0] in ("stacked", "stackedenc"):
            plan = (None if sig[0] == "stacked"
                    else encoding.device_plan(fresh, list(sig[1]), list(sig[2])))
            want = ev._stacked_device(fresh, fresh.blocks, list(sig[1]), list(sig[2]),
                                      plan=plan)[:2]
            entry = entry[:2]
        else:
            continue
        got, want = jax.tree.leaves(entry), jax.tree.leaves(want)
        assert len(got) == len(want)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(sig))


def _image_equals_a_fresh_build(s) -> None:
    """The warm image's rows, row arrays and fingerprints equal those of an
    image built afresh from the engine at the same timestamp, and the
    scrubber's deep check of it passes."""
    from tikv_tpu.copr import integrity

    img = s.image()
    cache = s.warm.region_cache
    (key,) = cache._images
    assert integrity.verify_image(cache, key, s.kv.snapshot())["outcome"] == "ok"
    fresh_ep = Endpoint(s.eng, enable_device=True, **s.fresh_args)
    req = CoprRequest(103, s.mk_dag(), [record_range(TABLE_ID)], img.snapshot_ts, context={
        "region_id": REGION, "region_epoch": (1, 1), "apply_index": s.apply_index})
    assert fresh_ep.handle_request(req).metrics["region_cache"] == "miss"
    (fresh,) = fresh_ep.region_cache._images.values()
    for name in ("handles", "row_commit_ts", "row_fp", "row_nbytes"):
        np.testing.assert_array_equal(getattr(img, name), getattr(fresh, name), err_msg=name)
    assert (img.fp_value, img.fp_integrity) == (fresh.fp_value, fresh.fp_integrity)
    assert img.checksum_parts() == fresh.checksum_parts()
    for ci in range(len(img.schema)):
        def rows(image):
            data, nulls = [], []
            for b in image.block_cache.blocks:
                c = b.cols[ci].decoded()
                data += np.asarray(c.data)[: b.n_valid].tolist()
                nulls += np.asarray(c.nulls)[: b.n_valid].tolist()
            return data, nulls
        assert rows(img) == rows(fresh), ci


@pytest.mark.parametrize("edit", FOLD_EDITS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_folded_image_and_its_pins_equal_a_fresh_build(layout, edit, monkeypatch):
    """After each commit of an RF1/RF2-shaped stream, in each column layout:
    the folded image equals an image built afresh (rows, row arrays,
    fingerprints, the scrubber's deep check), and the device pins it carries
    (stacked, n_valid/offset) equal the ones built afresh from its blocks.
    The commit of each case re-places its changed blocks the way its shape
    and layout call for: an edit of one run of deletes, one run of inserts
    and a few updated rows sends the device those rows; a pin with a
    run-length leaf, inserts scattered between rows, or more new rows than
    the largest bucket ship the block."""
    encode, mk_dag, small = LAYOUTS[layout]
    forks = []
    if edit == "fork":
        # one 4,096-row block; a base every 225 chain writes
        monkeypatch.setattr(rc, "_BASE_EVERY", 225)
        real_fork = rc.RegionImage.fork
        monkeypatch.setattr(rc.RegionImage, "fork", lambda self, state, delta: forks.append(
            real_fork(self, state, delta)) or forks[-1])
        s = Stream(41, mk_dag=mk_dag, row=_layout_row, block_rows=4096, encode=encode)
    elif edit in ("mid_block_insert", "scattered_insert"):
        s = Stream(41, mk_dag=mk_dag, row=_layout_row, encode=encode,
                   handles=range(0, 10 * N_ROWS, 10))
        s.next_handle = 10 * N_ROWS
    else:
        s = Stream(41, mk_dag=mk_dag, row=_layout_row, encode=encode)
    assert s.read(s.ts()) == "miss"
    # the stream going: the first append reserves room (the tail block of
    # 64 rows holds 44, then 49), and the stacked pins ride every version
    for k in (3, 2):
        s.rf1(k)
        assert s.read(s.ts()) == "wt_delta"
    s.rf2(2)
    assert s.read(s.ts()) == "wt_delta"
    before = _placed()
    if edit == "tail_append":
        s.rf1(6)
        want = {small: 1}
    elif edit == "append_into_next_block":
        s.rf1(12)
        assert s.read(s.ts()) == "wt_delta"
        before = _placed()
        s.rf1(10)  # three rows close the tail block, seven open the next
        want = {small: 2}
    elif edit == "head_delete":
        s.rf2(10)
        want = {small: 1}
    elif edit == "mid_block_update":
        s.txn([(h, s.row(h, bump=1)) for h in range(80, 85)])
        want = {small: 1}
    elif edit in ("mid_block_insert", "scattered_insert"):
        s.rf2(60)  # every tenth handle: six rows out of the head block
        assert s.read(s.ts()) == "wt_delta"
        before = _placed()
        if edit == "mid_block_insert":  # one run of rows between two
            s.txn([(h, s.row(h)) for h in (71, 72, 73, 74)])
            want = {small: 1}
        else:  # rows between several: not one run, so the block ships whole
            s.txn([(h, s.row(h)) for h in (71, 81, 91, 101)])
            want = {"block": 1}
    elif edit == "large_insert":
        s.rf1(15)  # closes the tail block
        assert s.read(s.ts()) == "wt_delta"
        before = _placed()
        s.rf1(17)  # into the next, past the 16-row bucket
        want = {"block": 1}
    else:
        for _ in range(9 + 8):
            s.rf1(25)
            assert s.read(s.ts()) == "wt_delta"
        reader_ts = s.ts()
        s.rf1(25)
        assert s.read(s.ts()) == "wt_delta"  # the 18th: a base at 450 writes
        before = _placed()
        assert s.read(reader_ts) == "wt_delta"  # below it: 200 rows onto the base at 225
        assert len(forks) == 1 and sum(b.n_valid for b in forks[0].blocks) == N_ROWS + 5 - 2 + 17 * 25
        assert {h: n - before[h] for h, n in _placed().items() if n > before[h]} == {small: 1}
        _pins_equal_a_fresh_build(s.warm, s.mk_dag(), forks[0])
        _image_equals_a_fresh_build(s)
        return
    assert s.read(s.ts()) == "wt_delta"
    assert {h: n - before[h] for h, n in _placed().items() if n > before[h]} == want
    _pins_equal_a_fresh_build(s.warm, s.mk_dag(), s.image().block_cache)
    _image_equals_a_fresh_build(s)


def test_a_fold_shares_what_it_did_not_touch_and_leaves_the_old_version_whole():
    """Across a fold that appends, deletes at the head and updates: every
    block it did not touch is the previous version's own object, with its
    per-row pieces and its pins; a reader holding the previous version reads
    it whole afterwards — its host arrays and its device pins as they were,
    and its answer unchanged — though the appends wrote into the room behind
    the tail block's arrays."""
    import jax

    from tikv_tpu.copr.dag import TopN

    s = Stream(51, mk_dag=_agg_dag)
    s.read(s.ts())
    for _ in range(2):
        s.rf1(4)
        s.rf2(3)
        s.read(s.ts())
        # a TopN over the same image pins its blocks one by one
        s.mk_dag = lambda: DagRequest(executors=[
            TableScan(TABLE_ID, PRODUCT_COLUMNS), TopN([(col(3), True)], 5)])
        s.read(s.ts())
        s.mk_dag = _agg_dag
    img = s.image()
    old, old_rows = img.block_cache, img.rows
    ev, params = s.warm._bind(_agg_dag())
    answer = ev.run(None, cache=old, params=params).encode()

    def held(cache, rows):
        host = [[np.array(a) for c in b.cols for a in (
            getattr(c, "packed", None) if getattr(c, "packed", None) is not None else c.data,
            c.nulls)] for b in cache.blocks]
        pieces = [np.array(a) for a in rows.h + rows.cts + rows.fp + rows.nb]
        pins = {sig: [np.asarray(x) for x in jax.tree.leaves(e)]
                for sig, e in cache.blocks[0].device.items()}
        return host, pieces, pins

    before = held(old, old_rows)
    assert any(sig[0] in ("stacked", "stackedenc") for sig in before[2])
    s.rf1(5)
    s.rf2(4)
    s.txn([(h, _row(h + 1)) for h in (150, 151)])
    assert s.read(s.ts()) == "wt_delta"
    new, new_rows = img.block_cache, img.rows
    touched = {0, 2, 4}  # the head, the update, the tail
    assert [b.n_valid for b in new.blocks[:5]] != [b.n_valid for b in old.blocks[:5]]
    for b in range(1, len(old.blocks)):
        if b in touched:
            assert new.blocks[b] is not old.blocks[b]
            continue
        assert new.blocks[b] is old.blocks[b]
        assert all(getattr(new_rows, name)[b] is getattr(old_rows, name)[b]
                   for name in ("h", "cts", "fp", "nb"))
    assert any(old.blocks[b].device for b in range(1, len(old.blocks)) if b not in touched)
    after = held(old, old_rows)
    for x, y in zip(before[0], after[0]):
        for a, b in zip(x, y):
            np.testing.assert_array_equal(a, b)
    for a, b in zip(before[1], after[1]):
        np.testing.assert_array_equal(a, b)
    assert before[2].keys() == after[2].keys()
    for sig in before[2]:
        for a, b in zip(before[2][sig], after[2][sig]):
            np.testing.assert_array_equal(a, b)
    assert ev.run(None, cache=old, params=params).encode() == answer
    # the image's bytes count the room's spare rows behind the tail block
    assert img._room.nbytes() > 0
    assert img.nbytes == new.nbytes() + 16 * new_rows.n + img._room.nbytes()


def test_fifty_folds_leave_checksum_and_scrub_equal_to_a_fresh_build():
    """Fifty folds of RF1/RF2 commits: the warm Checksum answer
    (``checksum_parts``), the fingerprints and the scrubber's deep check all
    agree with an image built afresh from the engine."""
    s = Stream(61, mk_dag=_agg_dag)
    s.read(s.ts())
    for i in range(25):
        s.rf1(int(s.rng.integers(3, 12)))
        s.read(s.ts())
        s.rf2(int(s.rng.integers(2, 9)))
        s.read(s.ts())
    assert s.outcomes.get("wt_delta", 0) == 50
    _image_equals_a_fresh_build(s)


def test_the_place_counter_and_fold_edit_share():
    """A 50-row commit re-places its block by edit, one that fills most of a
    256-row block ships it whole; ``fold_edit_share`` reads the share of
    edits over the window, and None where nothing was re-placed."""
    from benchmark import counters
    from benchmark.layer_metrics import fold_edit_share

    s = Stream(71, n=800, mk_dag=_agg_dag, block_rows=256)
    s.read(s.ts())
    s.rf1(3)
    s.read(s.ts())
    s.rf2(2)
    s.read(s.ts())
    start = counters.snapshot()
    assert fold_edit_share.read({"before": start, "after": counters.snapshot()}) is None
    before = _placed()
    s.rf1(50)
    assert s.read(s.ts()) == "wt_delta"
    mid = counters.snapshot()
    assert _placed() == {"edit": before["edit"] + 1, "block": before["block"]}
    assert fold_edit_share.read({"before": start, "after": mid}) == 100.0
    s.rf1(150)  # 85 rows in the tail block, 150 more: most of the block
    assert s.read(s.ts()) == "wt_delta"
    assert _placed() == {"edit": before["edit"] + 1, "block": before["block"] + 1}
    assert fold_edit_share.read({"before": start, "after": counters.snapshot()}) == 50.0
    _pins_equal_a_fresh_build(s.warm, s.mk_dag(), s.image().block_cache)
