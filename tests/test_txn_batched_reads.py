"""Prewrite and commit read all of a command's keys in one batch per column
family (``MvccReader.load_locks``/``seek_writes``): the WriteBatch each builds,
its result and its errors are what the per-key walk gave, over random
histories on the btree engine and the native one (memtable and flushed
runs), directly and through a region's view; and the counters say how often
the batch sufficed."""

import random

import pytest

from tikv_tpu.native.engine import NativeEngine, native_available
from tikv_tpu.raft.raftkv import RegionSnapshot
from tikv_tpu.raft.region import Region
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE, WriteBatch
from tikv_tpu.storage.mvcc.reader import KeyIsLockedError, MvccReader, WriteConflictError
from tikv_tpu.storage.mvcc.txn import (
    AlreadyExistsError,
    MvccTxn,
    PessimisticLockNotFoundError,
    PrewriteContext,
    TxnError,
    commit_key,
)
from tikv_tpu.storage.storage import Storage
from tikv_tpu.storage.txn import commands
from tikv_tpu.storage.txn.commands import AcquirePessimisticLock, Commit, Prewrite
from tikv_tpu.storage.txn_types import (
    MAX_TS,
    SHORT_VALUE_MAX_LEN,
    Key,
    Lock,
    LockType,
    Mutation,
    Write,
    WriteType,
)
from tikv_tpu.util import keys as data_keys

START = 1000  # the commands' start_ts
N_KEYS = 24


# -- the per-key walk, as prewrite was before its reads were batched --------

def walk_prewrite_key(txn, reader, mutation, ctx, is_pessimistic_lock):
    """PR 34's ``prewrite_key``: a lock get, a seek, the insert walk and the
    commit-record walk, each its own read of the snapshot."""
    key = mutation.key
    lock = reader.load_lock(key)
    if lock is not None:
        if lock.ts != ctx.start_ts:
            if ctx.is_pessimistic and is_pessimistic_lock:
                raise PessimisticLockNotFoundError(f"pessimistic lock lost on {key!r}")
            raise KeyIsLockedError(key.to_raw(), lock)
        if lock.lock_type != LockType.PESSIMISTIC:
            return lock.min_commit_ts
    elif ctx.is_pessimistic and is_pessimistic_lock:
        raise PessimisticLockNotFoundError(f"pessimistic lock missing on {key!r}")
    if not (ctx.is_pessimistic and is_pessimistic_lock):
        rec = reader.seek_write(key, MAX_TS)
        if rec is not None and rec[0] >= ctx.start_ts:
            raise WriteConflictError(key.to_raw(), ctx.start_ts, rec[1].start_ts, rec[0])
    if mutation.should_not_exists():
        rec = reader.seek_write(key, MAX_TS)
        while rec is not None:
            if rec[1].write_type == WriteType.PUT:
                raise AlreadyExistsError(key.to_raw())
            if rec[1].write_type == WriteType.DELETE:
                break
            rec = reader.seek_write(key, rec[0] - 1)
    for commit_ts, write in reader.get_txn_commit_record(key, ctx.start_ts):
        if write.write_type == WriteType.ROLLBACK:
            raise WriteConflictError(key.to_raw(), ctx.start_ts, ctx.start_ts, commit_ts)
    if mutation.mutation_type.value == "check_not_exists":
        return 0
    lock = Lock(mutation.lock_type(), ctx.primary, ctx.start_ts, ttl=ctx.lock_ttl,
                txn_size=ctx.txn_size, min_commit_ts=ctx.min_commit_ts,
                use_async_commit=ctx.use_async_commit,
                secondaries=list(ctx.secondaries) if key.to_raw() == ctx.primary else [])
    if mutation.value is not None:
        if len(mutation.value) <= SHORT_VALUE_MAX_LEN:
            lock.short_value = mutation.value
        else:
            txn.put_value(key, ctx.start_ts, mutation.value)
    min_commit_ts = 0
    if ctx.use_async_commit:
        min_commit_ts = max(ctx.min_commit_ts, ctx.start_ts + 1)
        lock.min_commit_ts = min_commit_ts
    txn.put_lock(key, lock)
    return min_commit_ts


def walk_prewrite(cmd, snapshot):
    txn, reader = MvccTxn(cmd.start_ts), MvccReader(snapshot)
    ctx = PrewriteContext(
        primary=cmd.primary, start_ts=cmd.start_ts, lock_ttl=cmd.lock_ttl,
        txn_size=cmd.txn_size, min_commit_ts=cmd.min_commit_ts,
        use_async_commit=cmd.use_async_commit, secondaries=cmd.secondaries,
        is_pessimistic=cmd.is_pessimistic)
    min_commit_ts, errors = 0, []
    for i, m in enumerate(cmd.mutations):
        flag = cmd.pessimistic_flags[i] if i < len(cmd.pessimistic_flags) else False
        try:
            min_commit_ts = max(min_commit_ts, walk_prewrite_key(txn, reader, m, ctx, flag))
        except (KeyIsLockedError, WriteConflictError, TxnError) as e:
            errors.append(e)
    if errors:
        return txn, {"errors": errors, "min_commit_ts": min_commit_ts}
    return txn, {"min_commit_ts": min_commit_ts}


def walk_commit(cmd, snapshot):
    txn, reader = MvccTxn(cmd.start_ts), MvccReader(snapshot)
    for k in cmd.keys:
        commit_key(txn, reader, k, cmd.start_ts, cmd.commit_ts)  # reads its own lock
    return txn, {"commit_ts": cmd.commit_ts}


# -- histories ----------------------------------------------------------------

def w(wt, start_ts, short=None):
    return Write(wt, start_ts, short_value=short)


def chain_below(rng):
    """1-4 records of other txns, all committed below START, newest first."""
    out, ts = [], START - rng.randint(1, 50)
    for _ in range(rng.randint(1, 4)):
        wt = rng.choice([WriteType.PUT, WriteType.DELETE, WriteType.LOCK, WriteType.ROLLBACK])
        start = ts if wt == WriteType.ROLLBACK else ts - rng.randint(1, 5)
        out.append((ts, w(wt, start, b"old" if wt == WriteType.PUT else None)))
        ts -= rng.randint(10, 40)
    return out


def other_lock(rng):
    ts = rng.choice([START - 7, START + 7])
    return Lock(rng.choice([LockType.PUT, LockType.DELETE, LockType.LOCK]), b"other", ts,
                ttl=3000, short_value=b"o")


HISTORIES = {
    # name: rng -> (write records [(commit_ts, Write)], lock or None)
    "none": lambda rng: ([], None),
    "below": lambda rng: (chain_below(rng), None),
    "above": lambda rng: ([(START + rng.randint(0, 30), w(WriteType.PUT, START - 3, b"new"))]
                          + chain_below(rng) * rng.randint(0, 1), None),
    "above_later_txn": lambda rng: ([(START + 40, w(WriteType.PUT, START + 20, b"x"))], None),
    "own_rollback": lambda rng: ([(START, Write.new_rollback(START, rng.random() < 0.5))]
                                 + chain_below(rng) * rng.randint(0, 1), None),
    "own_commit": lambda rng: ([(START + 5, w(WriteType.PUT, START, b"mine"))], None),
    "other_lock": lambda rng: (chain_below(rng) * rng.randint(0, 1), other_lock(rng)),
    "own_lock": lambda rng: ([], Lock(LockType.PUT, b"k0", START, ttl=3000, short_value=b"v",
                                      min_commit_ts=rng.choice([0, START + 3]))),
    "own_pessimistic": lambda rng: (chain_below(rng) * rng.randint(0, 1),
                                    Lock(LockType.PESSIMISTIC, b"k0", START, ttl=3000,
                                         for_update_ts=START + 2)),
    # a pessimistic lock left behind by a txn another one rolled back
    "own_pessimistic_rolled_back": lambda rng: (
        [(START, Write.new_rollback(START, True))],
        Lock(LockType.PESSIMISTIC, b"k0", START, ttl=3000, for_update_ts=START)),
}


def user_key(i: int) -> Key:
    return Key.from_raw(b"k%03d" % i)


def write_history(engine, enc, histories):
    """Each key's records straight into the engine (``enc`` maps an encoded
    key to the engine's key: itself, or the region's data key); half of them
    before a flush where the engine keeps runs."""
    batches = [WriteBatch(), WriteBatch()]
    for i, (writes, lock) in enumerate(histories):
        wb = batches[i % 2]
        k = user_key(i)
        for commit_ts, wr in writes:
            wb.put_cf(CF_WRITE, enc(k.append_ts(commit_ts).encoded), wr.to_bytes())
            if wr.write_type == WriteType.PUT and wr.short_value is None:
                wb.put_cf(CF_DEFAULT, enc(k.append_ts(wr.start_ts).encoded), b"d")
        if lock is not None:
            wb.put_cf(CF_LOCK, enc(k.encoded), lock.to_bytes())
    engine.write(batches[0])
    if getattr(engine, "path", None) is not None:
        engine.checkpoint()
    engine.write(batches[1])


def random_mutation(rng, k):
    kind = rng.choice(["put", "put_long", "delete", "lock", "insert", "check_not_exists"])
    if kind == "put":
        return Mutation.put(k, b"v%d" % rng.randint(0, 99))
    if kind == "put_long":
        return Mutation.put(k, b"L" * (SHORT_VALUE_MAX_LEN + 1))
    if kind == "insert":
        return Mutation.insert(k, b"ins")
    return getattr(Mutation, kind)(k)


ENGINES = ["btree", "native_memtable", "native_runs"]
VIEWS = ["engine", "region"]


def make_engine(kind, tmp_path):
    if kind == "btree":
        return BTreeEngine()
    if not native_available():
        pytest.skip("native engine unavailable")
    if kind == "native_memtable":
        return NativeEngine()
    return NativeEngine(path=str(tmp_path / "kv"), sync=False)


def snapshot_of(engine, view):
    """The engine's snapshot, or a region's view of it that holds keys 4..19:
    keys 0-3 and 20-23 lie at and past its bounds."""
    snap = engine.snapshot()
    if view == "engine":
        return snap
    region = Region(1, start_key=user_key(4).encoded, end_key=user_key(20).encoded)
    return RegionSnapshot(snap, region)


def encoder(view):
    return (lambda e: e) if view == "engine" else data_keys.data_key


def outcome(fn, cmd, snap):
    """(WriteBatch ops, result with errors told by type and text) or the
    error that ended the command."""
    try:
        txn, result = fn(cmd, snap)
    except (KeyIsLockedError, WriteConflictError, TxnError) as e:
        return ("raised", type(e), str(e))
    result = dict(result)
    if "errors" in result:
        result["errors"] = [(type(e), str(e)) for e in result["errors"]]
    return txn.wb.ops, result


def history_for(rng, i, names):
    return HISTORIES[names[i % len(names)]](rng)


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("view", VIEWS)
@pytest.mark.parametrize("engine_kind", ENGINES)
@pytest.mark.parametrize("variant", ["optimistic", "pessimistic", "async_commit"])
def test_prewrite_batch_equals_walk(variant, engine_kind, view, seed, tmp_path):
    rng = random.Random(f"{variant}/{engine_kind}/{view}/{seed}")
    names = list(HISTORIES)
    rng.shuffle(names)
    histories = [history_for(rng, i, names) for i in range(N_KEYS)]
    engine = make_engine(engine_kind, tmp_path)
    write_history(engine, encoder(view), histories)
    muts = [random_mutation(rng, user_key(i)) for i in range(N_KEYS)]
    # a duplicate key in one command reads what the first read
    muts.append(random_mutation(rng, user_key(rng.randrange(N_KEYS))))
    cmd = Prewrite(muts, b"k000", START, secondaries=[b"k001"] if variant == "async_commit" else [],
                   use_async_commit=variant == "async_commit", min_commit_ts=START + 2,
                   is_pessimistic=variant == "pessimistic",
                   pessimistic_flags=[rng.random() < 0.5 for _ in muts]
                   if variant == "pessimistic" else [])
    snap = snapshot_of(engine, view)
    # writes after the snapshot are seen by neither
    late = WriteBatch()
    late.put_cf(CF_LOCK, encoder(view)(user_key(5).encoded),
                Lock(LockType.PUT, b"late", START + 1).to_bytes())
    engine.write(late)
    got, want = outcome(Prewrite.process_write, cmd, snap), outcome(walk_prewrite, cmd, snap)
    assert got == want
    assert got[0], "some key of every history prewrites"


COMMIT_OK = {
    "own_lock": lambda rng: ([], Lock(rng.choice([LockType.PUT, LockType.DELETE, LockType.LOCK]),
                                      b"k0", START, ttl=3000,
                                      short_value=b"v" if rng.random() < 0.7 else None,
                                      min_commit_ts=rng.choice([0, START + 1]))),
    "own_pessimistic": HISTORIES["own_pessimistic"],
    "own_commit": HISTORIES["own_commit"],
}
COMMIT_FAIL = {
    "none": HISTORIES["none"],
    "other_lock": HISTORIES["other_lock"],
    "own_rollback": HISTORIES["own_rollback"],
    "expired": lambda rng: ([], Lock(LockType.PUT, b"k0", START, min_commit_ts=START + 99)),
}


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("view", VIEWS)
@pytest.mark.parametrize("engine_kind", ENGINES)
@pytest.mark.parametrize("fails", list(COMMIT_FAIL) + ["never"])
def test_commit_batch_equals_walk(fails, engine_kind, view, seed, tmp_path):
    rng = random.Random(f"commit/{fails}/{engine_kind}/{view}/{seed}")
    names = list(COMMIT_OK)
    histories = [history_for(rng, i, names) for i in range(N_KEYS)]
    if fails != "never":
        histories[rng.randrange(4, 20)] = COMMIT_FAIL[fails](rng)
    engine = make_engine(engine_kind, tmp_path)
    write_history(engine, encoder(view), histories)
    # the keys past the region's bounds have no lock there: a view commits
    # the keys inside it
    inside = range(N_KEYS) if view == "engine" else range(4, 20)
    cmd = Commit([user_key(i) for i in inside], START, START + 10)
    snap = snapshot_of(engine, view)
    got, want = outcome(Commit.process_write, cmd, snap), outcome(walk_commit, cmd, snap)
    assert got == want
    assert (got[0] == "raised") == (fails not in ("never",)), got


@pytest.mark.parametrize("engine_kind", ENGINES)
def test_keys_past_the_region_read_nothing(engine_kind, tmp_path):
    """A region's view answers the batch as its per-key reads do: keys at and
    past its bounds hold a lock and a record in the engine, and the view
    sees neither."""
    engine = make_engine(engine_kind, tmp_path)
    histories = [(chain_below(random.Random(i)), other_lock(random.Random(i)))
                 for i in range(N_KEYS)]
    write_history(engine, data_keys.data_key, histories)
    snap = snapshot_of(engine, "region")
    reader = MvccReader(snap)
    ks = [user_key(i) for i in range(N_KEYS)]
    assert reader.load_locks(ks) == [reader.load_lock(k) for k in ks]
    assert reader.seek_writes(ks, MAX_TS) == [reader.seek_write(k, MAX_TS) for k in ks]
    assert reader.seek_writes(ks, START - 30) == [reader.seek_write(k, START - 30) for k in ks]
    seen = [i for i, lock in enumerate(reader.load_locks(ks)) if lock is not None]
    assert seen == list(range(4, 20))


# -- counters -----------------------------------------------------------------

def moved(before):
    c = commands._BATCHED_READ_KEYS
    return {k: c.get(cmd=k[0], how=k[1]) - v for k, v in before.items()}


def counts():
    c = commands._BATCHED_READ_KEYS
    return {(cmd, how): c.get(cmd=cmd, how=how)
            for cmd in ("prewrite", "commit") for how in ("batch", "walk")}


def test_a_load_batch_is_read_in_one_batch():
    store = Storage()
    ks = [Key.from_raw(b"row%05d" % i) for i in range(2000)]
    before = counts()
    secs = {c: commands._ACTIONS_SECONDS.get(cmd=c) for c in ("prewrite", "commit")}
    n = {c: commands._ACTIONS_KEYS.get(cmd=c) for c in ("prewrite", "commit")}
    r = store.sched_txn_command(Prewrite([Mutation.put(k, b"v" * 160) for k in ks], ks[0].to_raw(), 10))
    assert "errors" not in r
    store.sched_txn_command(Commit(ks, 10, 11))
    assert moved(before) == {("prewrite", "batch"): 2000, ("prewrite", "walk"): 0,
                             ("commit", "batch"): 2000, ("commit", "walk"): 0}
    for c in ("prewrite", "commit"):
        assert commands._ACTIONS_KEYS.get(cmd=c) - n[c] == 2000
        assert commands._ACTIONS_SECONDS.get(cmd=c) > secs[c]
    assert store.get(ks[1234].to_raw(), 12) == b"v" * 160


def test_pessimistic_keys_walk():
    store = Storage()
    ks = [Key.from_raw(b"p%02d" % i) for i in range(8)]
    store.sched_txn_command(AcquirePessimisticLock([(k, False) for k in ks], b"p00", 20, 20))
    before = counts()
    r = store.sched_txn_command(Prewrite([Mutation.put(k, b"v") for k in ks], b"p00", 20,
                                         is_pessimistic=True, pessimistic_flags=[True] * 8,
                                         for_update_ts=20))
    assert "errors" not in r
    # a commit whose keys have no lock looks for their commit records
    store.sched_txn_command(Commit(ks, 20, 25))
    with pytest.raises(TxnError):
        store.sched_txn_command(Commit([Key.from_raw(b"never")], 20, 25))
    assert moved(before) == {("prewrite", "batch"): 0, ("prewrite", "walk"): 8,
                             ("commit", "batch"): 8, ("commit", "walk"): 1}
