"""The rule for readers below the snapshot, against a plain MVCC reference.

``RegionColumnCache._hit_fresh_locked``: a reader with ``start_ts <
img.snapshot_ts`` is a hit iff its snapshot is at the image's ``apply_index``
and ``start_ts >= img.max_commit_ts`` (docs/region_column_cache.md, "Readers
below the snapshot").  The reference here is a dict of versions and a dict of
locks, nothing of the program: what snapshot isolation says a reader at
``start_ts`` sees.  Two sessions draw timestamps from one oracle while commits,
deletes and locks land between them, their tasks reach the region in either
order, and every answer is held to the reference at the reader's OWN
``start_ts``.  The planted fault (the bound loosened by one) must be caught by
the same driver.
"""

import copy
import functools

import numpy as np
import pytest

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID
from fixtures import lock_key

from tikv_tpu.copr.dag import DagRequest, Limit, SelectResponse, TableScan
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.region_cache import RegionColumnCache, notify_region_write
from tikv_tpu.copr.table import encode_row, record_key, record_range
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_LOCK, CF_WRITE, WriteBatch
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType

NON_HANDLE = [c for c in PRODUCT_COLUMNS if not c.is_pk_handle]
REGION = 7
N_HANDLES = 24


class Mvcc:
    """The plain reference: every version ever committed, every lock held."""

    def __init__(self):
        self.versions: dict = {}   # handle -> [(commit_ts, row | None)]
        self.locks: dict = {}      # handle -> the locking txn's start_ts

    def read(self, ts: int):
        """What a reader at ``ts`` sees: ``"locked"`` where a lock at or
        below ``ts`` stands in its range, else the newest version at or below
        ``ts`` of every key that is not a delete, by handle."""
        if any(lock_ts <= ts for lock_ts in self.locks.values()):
            return "locked"
        rows = []
        for handle in sorted(self.versions):
            seen = [v for v in self.versions[handle] if v[0] <= ts]
            if seen:
                row = max(seen, key=lambda v: v[0])[1]
                if row is not None:
                    rows.append([handle, *row])
        return rows


class _Held(LocalEngine):
    """Hands out ``held`` (a snapshot frozen earlier) while it is set."""

    held = None

    def snapshot(self, ctx=None):
        return self.held if self.held is not None else super().snapshot(ctx)


class Sessions:
    """One region, one warm endpoint, one timestamp oracle, and the reference
    kept beside the engine write for write."""

    def __init__(self, seed: int):
        self.rng = np.random.default_rng(seed)
        # the apply path announces every batch or none (a store with
        # write-through emission off): a gap in the chain is another matter
        # (`notify_region_write_lost`)
        self.write_through = seed % 2 == 1
        self.kv = BTreeEngine()
        self.eng = _Held(self.kv)
        self.warm = Endpoint(self.eng, enable_device=True)
        self.ref = Mvcc()
        self.tso = 100
        self.apply_index = 3
        self.serial = 0
        self.outcomes: dict = {}
        for handle in range(0, N_HANDLES, 2):
            self.commit(handle, delete=False)

    def ts(self) -> int:
        self.tso += int(self.rng.integers(1, 6))
        return self.tso

    def commit(self, handle: int, delete: bool) -> None:
        """One transaction on one key: put or delete, committed."""
        start, commit = self.ts(), self.ts()
        key = Key.from_raw(record_key(TABLE_ID, handle))
        if delete:
            row, write = None, Write(WriteType.DELETE, start)
        else:
            self.serial += 1  # no two versions read alike
            name = [b"apple", b"banana", b"fig"][self.serial % 3]
            row = [name, self.serial, (1000 + self.serial, 2)]
            val = encode_row(NON_HANDLE, [name, self.serial, 1000 + self.serial])
            write = Write(WriteType.PUT, start, short_value=val)
        ops = [("put", CF_WRITE, key.append_ts(commit).encoded, write.to_bytes())]
        wb = WriteBatch()
        wb.put_cf(*ops[0][1:])
        self.kv.write(wb)
        self.apply_index += 1
        if self.write_through:
            # the apply path's hook: the image gets a pending chain
            notify_region_write(REGION, ops, self.apply_index)
        self.ref.versions.setdefault(handle, []).append((commit, row))

    def lock(self, handle: int) -> None:
        raw = record_key(TABLE_ID, handle)
        start = self.ts()
        lock_key(self.kv, raw, raw, start)
        self.ref.locks[handle] = start

    def unlock(self, handle: int) -> None:
        self.kv.delete_cf(CF_LOCK, Key.from_raw(record_key(TABLE_ID, handle)).encoded)
        del self.ref.locks[handle]

    def image(self):
        images = list(self.warm.region_cache._images.values())
        return images[0] if images else None

    def read(self, ts: int, frozen=None) -> str:
        """One task at ``ts`` (through ``frozen``'s older snapshot if given),
        held to the reference; its region-cache outcome."""
        snap, ref, apply_index = frozen or (None, self.ref, self.apply_index)
        dag = DagRequest(executors=[TableScan(TABLE_ID, PRODUCT_COLUMNS),
                                    Limit(1 << 20)])
        req = CoprRequest(103, dag, [record_range(TABLE_ID)], ts, context={
            "region_id": REGION, "region_epoch": (1, 1), "apply_index": apply_index})
        self.eng.held = snap
        try:
            r = self.warm.handle_request(req)
        except Exception as e:  # noqa: BLE001 — KeyIsLocked, by its text
            assert "locked" in str(e).lower(), e
            got, outcome = "locked", "locked"
        else:
            got = [list(row) for row in SelectResponse.decode(
                r.data, encode_type=r.encode_type).iter_rows()]
            # "cpu": after a few readers met a lock the endpoint's breaker
            # stands open for a while and tasks bypass the cache altogether
            outcome = r.metrics.get("region_cache", "cpu")
        finally:
            self.eng.held = None
        want = ref.read(ts)
        assert got == want, (ts, outcome, apply_index, got, want)
        self.outcomes[outcome] = self.outcomes.get(outcome, 0) + 1
        return outcome

    def freeze(self):
        return self.kv.snapshot(), copy.deepcopy(self.ref), self.apply_index


def _drive(seed: int, rounds: int = 30) -> Sessions:
    s = Sessions(seed)
    rng = s.rng
    s.read(s.ts())  # the build
    for _ in range(rounds):
        frozen = s.freeze() if rng.random() < 0.3 else None
        # two sessions draw their timestamps; writers commit, delete and lock
        # between and around them
        stamps = []
        for _session in range(2):
            if rng.random() < 0.35:
                handle = int(rng.integers(N_HANDLES))
                if handle not in s.ref.locks:
                    s.commit(handle, delete=rng.random() < 0.35)
            if rng.random() < 0.1 and len(s.ref.locks) < 2:
                s.lock(int(rng.integers(N_HANDLES)))
            stamps.append(s.ts())
        # the tasks reach the region in either order
        for i in rng.permutation(2):
            s.read(stamps[int(i)])
        img = s.image()
        if img is not None and img.apply_index == s.apply_index:
            bound, snapshot_ts = img.max_commit_ts, img.snapshot_ts
            if bound < snapshot_ts and not s.ref.locks:
                # AT the bound: that commit is the reader's to see
                assert s.read(bound) == "hit"
                # one below it: the image holds a commit the reader may not
                # see (or lacks the row a delete took), so it never serves
                # it as it stands: a fresh scan does, or an earlier version
                # of the image with the reader's own writes folded in
                assert s.read(bound - 1) in ("stale", "wt_delta")
                assert (img.snapshot_ts, img.max_commit_ts) == (snapshot_ts, bound)
        if frozen is not None:
            # a reader through a snapshot taken before the round's writes:
            # right whatever the image holds, and no hit where the image has
            # folded a batch that snapshot predates
            outcome = s.read(int(rng.integers(100, s.tso)), frozen=frozen)
            if img is not None and img.apply_index != frozen[2]:
                assert outcome != "hit"
        for handle in list(s.ref.locks):
            if rng.random() < 0.5:
                s.unlock(handle)
    return s


SEEDS = (1, 2, 3, 4, 5, 6)
_driven = functools.lru_cache(maxsize=None)(_drive)  # one drive a seed


@pytest.mark.parametrize("seed", SEEDS)
def test_every_reader_equals_the_plain_reference(seed):
    s = _driven(seed)
    st = s.warm.region_cache.stats
    # the interleavings reached what they are for: readers below a snapshot
    # another raised, hits, and readers that the image as it stands cannot
    # answer (folded onto a kept earlier version where their snapshot is
    # older than the image, or a fresh scan)
    assert st.below_snapshot > 0, (st.to_dict(), s.outcomes)
    assert s.outcomes.get("hit", 0) > 0
    assert st.below_image + s.outcomes.get("stale", 0) > 0, (st.to_dict(), s.outcomes)


def test_the_seeds_cover_chains_locks_and_older_snapshots():
    seen: dict = {}
    for seed in SEEDS:
        for k, v in _driven(seed).outcomes.items():
            seen[k] = seen.get(k, 0) + v
    for outcome in ("hit", "stale", "locked", "wt_delta", "delta"):
        assert seen.get(outcome, 0) > 0, seen
    # readers whose snapshot the image had passed, folded onto a kept version
    assert sum(_driven(seed).warm.region_cache.stats.below_image for seed in SEEDS) > 0


def test_the_bound_loosened_by_one_is_caught(monkeypatch):
    """The planted fault: ``start_ts >= max_commit_ts - 1``.  A reader one
    below the newest commit is then served an image that holds that commit,
    and the reference says so."""
    real = RegionColumnCache._hit_fresh_locked

    def loosened(self, img, apply_index, start_ts, *rest):
        bound = img.max_commit_ts
        if start_ts < img.snapshot_ts:
            img.max_commit_ts = bound - 1
        try:
            return real(self, img, apply_index, start_ts, *rest)
        finally:
            img.max_commit_ts = bound

    monkeypatch.setattr(RegionColumnCache, "_hit_fresh_locked", loosened)
    caught = 0
    for seed in (1, 2, 3):
        try:
            _drive(seed)
        except AssertionError:
            caught += 1
    assert caught == 3
