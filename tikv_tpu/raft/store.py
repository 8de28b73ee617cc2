"""Multi-Raft store: one raft group per region, multiplexed on a node.

Re-expression of ``components/raftstore`` (store/fsm/{store,peer}.rs +
store/fsm/apply.rs + batch-system): a ``Store`` owns every region peer placed
on one node; peers propose serialized commands through their raft group and
apply committed entries to the shared engine; the store routes messages,
drives ticks, and executes admin commands (split, conf change).

Data layout on the shared engine matches keys.py: user data under the ``z``
prefix, raft log + states under store-local keys — so one engine hosts many
regions, exactly like the reference's single RocksDB with a raft CF.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable

from ..analysis.sanitizer import make_lock, make_rlock
from ..storage.btree_engine import BTreeEngine
from ..util.failpoint import fail_point
from ..storage.engine import CF_DEFAULT, CF_LOCK, CF_RAFT, CF_WRITE, WriteBatch
from ..util import codec, keys
from ..util import logger as slog


def _notify_region_cache(region_id: int, reason: str) -> None:
    """Coprocessor region-column-cache invalidation on epoch change (split /
    merge / conf change).  Lazy import: the raft layer must stay importable
    without the coprocessor stack."""
    try:
        from ..copr.region_cache import notify_region_epoch_change
    except ImportError:
        return
    notify_region_epoch_change(region_id, reason=reason)


def _notify_region_write_lost(region_id: int, apply_index: int,
                              token=None) -> None:
    """Region data changed by means the write-through path cannot express
    (raft snapshot apply, emission disabled): caches drop pending deltas and
    repair through scan_delta (docs/write_path.md)."""
    try:
        from ..copr.region_cache import notify_region_write_lost
    except ImportError:
        return
    notify_region_write_lost(region_id, apply_index, token=token)


def _count_consistency(result: str) -> None:
    """Consistency-check observability (docs/integrity.md): compute_hash
    applies count ``compute``, verify_hash applies count ``match`` or
    ``mismatch`` — the series the divergence alert fires on."""
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_raft_consistency_check_total",
        "Raft consistency-check applies, by result",
    ).inc(result=result)
from .core import Entry, Message, MsgType, RaftNode, Role
from .core import Snapshot as RaftSnapshot
from .region import EpochError, KeyNotInRegionError, NotLeaderError, Peer as RegionPeer, Region, RegionEpoch

_LOG = slog.get_logger("raftstore")

DATA_CFS = (CF_DEFAULT, CF_LOCK, CF_WRITE)


# ---------------------------------------------------------------------------
# Command codec (RaftCmdRequest equivalent, deterministic bytes)
# ---------------------------------------------------------------------------

def encode_cmd(cmd: dict) -> bytes:
    """Commands: {"epoch": (cv, v), "ops": [(op, cf, key, val)]} or
    {"epoch":…, "admin": ("split", split_key, new_region_id, [new_peer_ids])
                        | ("conf_change", op, peer_id, store_id)}."""
    out = bytearray()
    cv, v = cmd["epoch"]
    out += codec.encode_var_u64(cv)
    out += codec.encode_var_u64(v)
    admin = cmd.get("admin")
    if admin is None:
        out.append(0)
        ops = cmd["ops"]
        out += codec.encode_var_u64(len(ops))
        for op, cf, key, val in ops:
            out.append({"put": 1, "delete": 2, "delete_range": 3}[op])
            out += codec.encode_compact_bytes(cf.encode())
            out += codec.encode_compact_bytes(key)
            out += codec.encode_compact_bytes(val if val is not None else b"")
    elif admin[0] == "split":
        out.append(1)
        out += codec.encode_compact_bytes(admin[1])
        out += codec.encode_var_u64(admin[2])
        out += codec.encode_var_u64(len(admin[3]))
        for pid in admin[3]:
            out += codec.encode_var_u64(pid)
    elif admin[0] == "conf_change":
        out.append(2)
        out += codec.encode_compact_bytes(admin[1].encode())
        out += codec.encode_var_u64(admin[2])
        out += codec.encode_var_u64(admin[3])
    elif admin[0] == "compute_hash":
        out.append(5)
    elif admin[0] == "verify_hash":
        out.append(6)
        out += codec.encode_var_u64(admin[1])  # apply index of the hash
        out += codec.encode_var_u64(admin[2])  # expected hash
        # derived-plane image fingerprints (docs/integrity.md): replicas
        # cross-check their device images against the leader's at the same
        # apply index — sorted so the entry bytes stay deterministic
        fps = admin[3] if len(admin) > 3 and admin[3] else {}
        out += codec.encode_var_u64(len(fps))
        for kid in sorted(fps):
            rec = fps[kid]
            out += codec.encode_compact_bytes(kid.encode())
            out += codec.encode_var_u64(max(int(rec["apply_index"]), 0))
            out += codec.encode_var_u64(max(int(rec["snapshot_ts"]), 0))
            out += codec.encode_var_u64(max(int(rec["max_commit_ts"]), 0))
            out += codec.encode_var_u64(int(rec["fingerprint"]))
    elif admin[0] == "prepare_merge":
        out.append(3)
        out += codec.encode_var_u64(admin[1])  # target region id
    elif admin[0] == "commit_merge":
        out.append(4)
        out += codec.encode_var_u64(admin[1])  # source region id
        out += codec.encode_compact_bytes(admin[2])  # source end key
        out += codec.encode_var_u64(admin[3])  # source epoch version
        out += codec.encode_var_u64(admin[4])  # source commit index
        entries = admin[5]  # CatchUpLogs payload: encoded source entries
        out += codec.encode_var_u64(len(entries))
        for eb in entries:
            out += codec.encode_compact_bytes(eb)
    elif admin[0] == "ingest_sst":
        # the staged file's entries ride in the log entry itself, so every
        # replica — current and future (log/snapshot catch-up) — applies the
        # same bytes (fsm/apply.rs:1427-1445 exec_ingest_sst role)
        out.append(7)
        out += codec.encode_compact_bytes(admin[1])
    else:
        raise ValueError(admin)
    return bytes(out)


def scan_region_states(snapshot):
    """Yield (region_id, raw_state_bytes) for every persisted region meta —
    THE region-enumeration idiom (fsm/store.rs init scan), shared by
    recovery, the debugger and offline tooling instead of each re-deriving
    the prefix arithmetic."""
    prefix = keys.LOCAL_PREFIX + keys.REGION_META_PREFIX
    for k, v in snapshot.scan_cf(CF_RAFT, prefix,
                                 prefix[:-1] + bytes([prefix[-1] + 1])):
        yield codec.decode_u64(k, 2), v


def decode_cmd(b: bytes) -> dict:
    cv, off = codec.decode_var_u64(b, 0)
    v, off = codec.decode_var_u64(b, off)
    kind = b[off]
    off += 1
    cmd: dict = {"epoch": (cv, v)}
    if kind == 0:
        n, off = codec.decode_var_u64(b, off)
        ops = []
        for _ in range(n):
            op = {1: "put", 2: "delete", 3: "delete_range"}[b[off]]
            off += 1
            cf, off = codec.decode_compact_bytes(b, off)
            key, off = codec.decode_compact_bytes(b, off)
            val, off = codec.decode_compact_bytes(b, off)
            ops.append((op, cf.decode(), key, val))
        cmd["ops"] = ops
    elif kind == 1:
        split_key, off = codec.decode_compact_bytes(b, off)
        new_id, off = codec.decode_var_u64(b, off)
        n, off = codec.decode_var_u64(b, off)
        pids = []
        for _ in range(n):
            pid, off = codec.decode_var_u64(b, off)
            pids.append(pid)
        cmd["admin"] = ("split", split_key, new_id, pids)
    elif kind == 2:
        op, off = codec.decode_compact_bytes(b, off)
        pid, off = codec.decode_var_u64(b, off)
        sid, off = codec.decode_var_u64(b, off)
        cmd["admin"] = ("conf_change", op.decode(), pid, sid)
    elif kind == 5:
        cmd["admin"] = ("compute_hash",)
    elif kind == 6:
        idx, off = codec.decode_var_u64(b, off)
        h, off = codec.decode_var_u64(b, off)
        fps: dict = {}
        if off < len(b):  # pre-integrity-plane log entries carry no payload
            n, off = codec.decode_var_u64(b, off)
            for _ in range(n):
                kid, off = codec.decode_compact_bytes(b, off)
                ai, off = codec.decode_var_u64(b, off)
                sts, off = codec.decode_var_u64(b, off)
                mct, off = codec.decode_var_u64(b, off)
                fp, off = codec.decode_var_u64(b, off)
                fps[kid.decode()] = {"apply_index": ai, "snapshot_ts": sts,
                                     "max_commit_ts": mct, "fingerprint": fp}
        cmd["admin"] = ("verify_hash", idx, h, fps)
    elif kind == 3:
        tid, off = codec.decode_var_u64(b, off)
        cmd["admin"] = ("prepare_merge", tid)
    elif kind == 4:
        sid, off = codec.decode_var_u64(b, off)
        end, off = codec.decode_compact_bytes(b, off)
        sv, off = codec.decode_var_u64(b, off)
        scommit, off = codec.decode_var_u64(b, off)
        n, off = codec.decode_var_u64(b, off)
        entries = []
        for _ in range(n):
            eb, off = codec.decode_compact_bytes(b, off)
            entries.append(eb)
        cmd["admin"] = ("commit_merge", sid, end, sv, scommit, entries)
    elif kind == 7:
        blob, off = codec.decode_compact_bytes(b, off)
        cmd["admin"] = ("ingest_sst", blob)
    return cmd


def erase_region_state(engine, region_id: int, wb: WriteBatch | None = None) -> None:
    """THE one definition of wiping a region's persisted identity (region
    meta, raft state, apply state, log) — shared by tombstone destruction,
    commit-merge source cleanup, and the debugger's offline tombstone."""
    own_wb = wb is None
    if own_wb:
        wb = WriteBatch()
    wb.delete_cf(CF_RAFT, keys.region_state_key(region_id))
    wb.delete_cf(CF_RAFT, keys.raft_state_key(region_id))
    wb.delete_cf(CF_RAFT, keys.apply_state_key(region_id))
    log_prefix = keys.region_raft_prefix(region_id) + keys.RAFT_LOG_SUFFIX
    wb.delete_range_cf(CF_RAFT, log_prefix, log_prefix[:-1] + bytes([log_prefix[-1] + 1]))
    if own_wb:
        engine.write(wb)


def _decode_ingest_entries(blob: bytes):
    """Yield (cf, key, value) from an ingest_sst admin payload."""
    off = 0
    n, off = codec.decode_var_u64(blob, off)
    for _ in range(n):
        cf, off = codec.decode_compact_bytes(blob, off)
        key, off = codec.decode_compact_bytes(blob, off)
        val, off = codec.decode_compact_bytes(blob, off)
        yield cf.decode(), key, val


def _ingest_key_outside(blob: bytes, region) -> bytes | None:
    """First payload key outside the region's range, or None."""
    for _cf, key, _val in _decode_ingest_entries(blob):
        if not region.contains(key):
            return key
    return None


# ---------------------------------------------------------------------------
# Transport
# ---------------------------------------------------------------------------

@dataclass
class RaftMessage:
    """Envelope for peer-to-peer raft traffic (kvproto RaftMessage)."""

    region_id: int
    from_peer: RegionPeer
    to_peer: RegionPeer
    msg: Message
    region_epoch: RegionEpoch = field(default_factory=RegionEpoch)
    # region carried on snapshot/first-contact messages so the receiver can
    # bootstrap the peer (raftstore maybe_create_peer)
    region: Region | None = None
    # kvproto RaftMessage.is_tombstone: "you have been removed by a committed
    # conf change at this epoch — destroy yourself".  Sent by the leader on
    # applying RemovePeer, and by any member contacted by a peer that a newer
    # epoch excludes (raftstore's stale-peer GC), so a lagging removed peer
    # is destroyed even though it never receives its own removal entry.
    is_tombstone: bool = False


class Transport:
    def send(self, to_store: int, rmsg: RaftMessage) -> None:
        raise NotImplementedError


class Filter:
    """Message filter for fault injection (transport_simulate.rs:34)."""

    def before(self, rmsg: RaftMessage) -> bool:
        """False = drop."""
        return True


class DropPacketFilter(Filter):
    def __init__(self, region_id: int | None = None, rate: float = 1.0, rng=None):
        import random

        self.region_id = region_id
        self.rate = rate
        self.rng = rng or random.Random(0)

    def before(self, rmsg: RaftMessage) -> bool:
        if self.region_id is not None and rmsg.region_id != self.region_id:
            return True
        return self.rng.random() >= self.rate


class PartitionFilter(Filter):
    def __init__(self, stores_a: set[int], stores_b: set[int]):
        self.a = stores_a
        self.b = stores_b

    def before(self, rmsg: RaftMessage) -> bool:
        fa, ta = rmsg.from_peer.store_id, rmsg.to_peer.store_id
        return not ((fa in self.a and ta in self.b) or (fa in self.b and ta in self.a))


class RegionPacketFilter(Filter):
    def __init__(self, region_id: int, store_id: int | None = None, msg_types: set | None = None):
        self.region_id = region_id
        self.store_id = store_id
        self.msg_types = msg_types

    def before(self, rmsg: RaftMessage) -> bool:
        if rmsg.region_id != self.region_id:
            return True
        if self.store_id is not None and rmsg.to_peer.store_id != self.store_id:
            return True
        if self.msg_types is not None and rmsg.msg.type not in self.msg_types:
            return True
        return False


class ChannelTransport(Transport):
    """In-memory transport wiring stores directly (test_raftstore NodeCluster)."""

    def __init__(self):
        self.stores: dict[int, "Store"] = {}
        self.filters: list[Filter] = []
        self._mu = make_lock("raft.transport")

    def register(self, store: "Store") -> None:
        self.stores[store.store_id] = store

    def send(self, to_store: int, rmsg: RaftMessage) -> None:
        for f in self.filters:
            if not f.before(rmsg):
                return
        store = self.stores.get(to_store)
        if store is not None:
            store.enqueue_message(rmsg)


# ---------------------------------------------------------------------------
# Peer (region replica)
# ---------------------------------------------------------------------------

class Proposal:
    def __init__(self, index: int, term: int, cb: Callable):
        self.index = index
        self.term = term
        self.cb = cb


class StorePeer:
    """One region replica on this store (PeerFsm + ApplyDelegate merged)."""

    def __init__(self, store: "Store", region: Region, peer_id: int):
        self.store = store
        self.region = region
        self.peer_id = peer_id
        self.node = RaftNode(peer_id, region.voter_ids())
        self.node.learners = set(region.learner_ids())
        self.node.witnesses = set(region.witness_ids())
        self.proposals: list[Proposal] = []
        # ctx -> (cb, expiry deadline); see read_index / _expire_stale_reads
        self.pending_reads: dict[bytes, tuple[Callable, float]] = {}
        self._was_leader = False  # stepdown-transition detector (handle_ready)
        self._read_seq = 0
        self.merging = False  # PrepareMerge applied: no more data proposals
        # Completed apply progress.  node.applied advances when ready()
        # DRAINS committed entries (they are handed to the apply pipeline);
        # apply_index advances when their effects are IN the engine.  Reads,
        # snapshot generation, and log GC gate on apply_index (the
        # reference's ApplyState vs RaftLocalState split, peer_storage.rs).
        self.apply_index = 0
        # a failed apply latches the region: advancing past the gap would
        # persist an ApplyState covering entries the engine never saw
        self.apply_broken = False
        # guards proposals / pending_reads / pending_read_states: proposers
        # run on service threads, acks on apply workers, reads on the raft
        # thread
        self._cb_mu = make_lock("raft.peer.cb", label=f"region-{region.id}")
        # an applied run's engine write and its apply_index advance are one
        # step to a snapshot: RaftKv samples (apply_index, engine snapshot)
        # under this lock, so a snapshot never holds data its index does
        # not claim (docs/write_path.md)
        self.apply_mu = make_lock("raft.peer.apply", label=f"region-{region.id}")
        self.pending_read_states: list[tuple[bytes, int]] = []

    # -- raft driving ------------------------------------------------------

    def propose_cmd(self, cmd: dict, cb: Callable) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_raftstore_proposal_total", "Proposals entering raft, by kind"
        ).inc(kind=cmd.get("type", "data"))
        if not self.node.is_leader():
            cb(NotLeaderError(self.region.id, self.store.leader_store_of(self.region.id)))
            return
        if not self._epoch_ok(cmd):
            cb(EpochError(self.region.clone()))
            return
        if self.merging:
            # a merging region rejects ALL proposals (data, split, conf
            # change) until CommitMerge resolves it — raftstore's rule
            cb(EpochError(self.region.clone()))
            return
        admin = cmd.get("admin")
        if admin is not None and admin[0] == "ingest_sst":
            # range check at propose time (exec_ingest_sst rejects SSTs whose
            # range exceeds the region): out-of-range keys would ride this
            # region's log but be excluded from its range-bounded snapshots,
            # silently diverging any replica that catches up via snapshot
            bad = _ingest_key_outside(admin[1], self.region)
            if bad is not None:
                cb(KeyError(f"ingest key {bad!r} outside region "
                            f"{self.region.start_key!r}..{self.region.end_key!r}"))
                return
        if admin is not None and admin[0] == "conf_change_v2":
            # atomic multi-peer change via joint consensus: admin carries
            # [(op, peer_id, store_id), ...] — placement rides IN the entry
            # so any future leader knows where new peers live, not just the
            # proposing store
            # propose + register atomically under _cb_mu: an apply worker's
            # ack sweep (which takes the same lock) must not observe the
            # entry committed before its proposal is in self.proposals
            with self._cb_mu:
                index = self.node.propose_conf_change(("enter_joint", tuple(admin[1])))
                if index is not None:
                    self.proposals.append(Proposal(index, self.node.term, cb))
            if index is None:
                cb(NotLeaderError(self.region.id, None))
            else:
                self.store.notify_region(self.region.id)
            return
        if admin is not None and admin[0] == "conf_change":
            # placement (store id) rides in the entry, like the reference's
            # ConfChange carrying the full Peer message
            with self._cb_mu:
                index = self.node.propose_conf_change((admin[1], admin[2], admin[3]))
                if index is not None:
                    self.proposals.append(Proposal(index, self.node.term, cb))
            if index is None:
                cb(NotLeaderError(self.region.id, None))
            else:
                self.store.notify_region(self.region.id)
            return
        with self._cb_mu:
            index = self.node.propose(encode_cmd(cmd))
            if index is not None:
                self.proposals.append(Proposal(index, self.node.term, cb))
        if index is None:
            cb(NotLeaderError(self.region.id, None))
        else:
            self.store.notify_region(self.region.id)

    def _epoch_ok(self, cmd: dict) -> bool:
        """Data commands only care about the range (version); admin commands
        also require membership (conf_ver) to be current — the reference's
        util::check_region_epoch rules."""
        cv, v = cmd["epoch"]
        if cmd.get("admin") is not None:
            return (cv, v) == (self.region.epoch.conf_ver, self.region.epoch.version)
        return v == self.region.epoch.version

    def propose_split(self, split_key: bytes, new_region_id: int, new_pids: list[int], cb: Callable) -> None:
        """Propose the split admin command (shared by auto-split, the
        cluster harness, and the split_region RPC — ONE definition of the
        admin tuple shape + epoch capture).  ``split_key`` must already be
        in engine key space (memcomparable-encoded for txn data)."""
        self.propose_cmd(
            {
                "epoch": (self.region.epoch.conf_ver, self.region.epoch.version),
                "ops": [],
                "admin": ("split", split_key, new_region_id, new_pids),
            },
            cb,
        )

    # follower replica-read waiters whose READ_INDEX (or its RESP) vanished
    # — leader stepdown mid-round, partition — are failed after this long
    # so pending_reads can never grow without bound on a live follower
    READ_WAIT_TTL = 15.0

    def read_index(self, cb: Callable) -> None:
        """Linearizable read barrier; cb() fires once safe to read locally.
        Works on followers too (replica read): the ctx forwards to the
        leader and the RESP releases it here."""
        if not self.node.is_leader() and self.node.leader_id is None:
            # no known leader (election window): the raft core would drop
            # the forward on the floor — fail fast so the caller retries
            # instead of burning its whole timeout
            cb(NotLeaderError(self.region.id, None))
            return
        with self._cb_mu:
            self._read_seq += 1
            # ctx must be unique CLUSTER-wide: every peer starts its seq at
            # 0, so without the peer id two forwarding followers collide in
            # the leader's pending-read table and one waiter never fires
            ctx = (codec.encode_u64(self.region.id)
                   + codec.encode_u64(self.peer_id)
                   + codec.encode_u64(self._read_seq))
            self.pending_reads[ctx] = (cb, time.monotonic() + self.READ_WAIT_TTL)
        self.node.read_index(ctx)
        self.store.notify_region(self.region.id)

    def _expire_stale_reads(self) -> None:
        if not self.pending_reads:
            return
        now = time.monotonic()
        fire = []
        with self._cb_mu:
            for ctx, (cb, deadline) in list(self.pending_reads.items()):
                if now >= deadline:
                    del self.pending_reads[ctx]
                    fire.append(cb)
        for cb in fire:
            cb(NotLeaderError(self.region.id, self.store.leader_store_of(self.region.id)))

    def handle_ready(self, sync_apply: bool = False) -> bool:
        is_leader = self.node.is_leader()
        self._expire_stale_reads()
        if (self._was_leader or self.proposals) and not is_leader:
            # stepped DOWN (transition, not merely "is a follower" — a
            # follower legitimately parks replica-read waiters here): fail
            # every pending proposal and read-index waiter NOW (the
            # reference notifies on leader change rather than leaving
            # callers to time out — a deposed leader never produces the
            # awaited read states either).  This also keeps self.proposals
            # sorted by index — the invariant _ack's front-pop relies on —
            # because a re-election on this store starts from an empty list.
            with self._cb_mu:
                stale, self.proposals = self.proposals, []
                stale_reads = [cb for cb, _dl in self.pending_reads.values()]
                self.pending_reads.clear()
                self.pending_read_states.clear()
            leader = self.store.leader_store_of(self.region.id)
            for p in stale:
                p.cb(NotLeaderError(self.region.id, leader))
            for cb in stale_reads:
                cb(NotLeaderError(self.region.id, leader))
        self._was_leader = is_leader
        rd = self.node.ready()
        if rd.is_empty():
            return False
        eng = self.store.engine
        # persist raft log + hard state (PeerStorage: RaftLocalState)
        if rd.entries or rd.hard_state_changed:
            rl = self.store.raft_log
            if rl is not None:
                # one group-committed batch: entries + state (raftlog.cc)
                rl.append(
                    self.region.id,
                    rd.entries[0].index if rd.entries else 0,
                    [_encode_entry(e) for e in rd.entries],
                    state=self._encode_raft_state(),
                )
            else:
                wb = WriteBatch()
                for e in rd.entries:
                    wb.put_cf(CF_RAFT, keys.raft_log_key(self.region.id, e.index), _encode_entry(e))
                wb.put_cf(CF_RAFT, keys.raft_state_key(self.region.id), self._encode_raft_state())
                eng.write(wb)
        if rd.snapshot is not None:
            if self.store.apply_system is not None:
                # queued runs reference the pre-snapshot region: drain them
                # before the snapshot swaps region/engine state underneath
                self.store.apply_system.flush(self.region.id)
            self._apply_snapshot(rd.snapshot)
        apply_sys = None if sync_apply else self.store.apply_system
        if rd.committed_entries:
            if apply_sys is None:
                self._apply_entries_inline(rd.committed_entries)
            else:
                self._schedule_apply(rd.committed_entries, apply_sys)
        if rd.read_states:
            # enqueue under the lock FIRST, then sweep: checking apply_index
            # before appending loses the wakeup if the apply worker advances
            # and sweeps in between (_flush_pending_reads re-checks under
            # the same lock, so one of the two sweeps always fires the cb)
            with self._cb_mu:
                self.pending_read_states.extend(rd.read_states)
            self._flush_pending_reads()
        for m in rd.messages:
            self._send_raft_msg(m)
        return True

    def _apply_entries_inline(self, entries: list[Entry]) -> None:
        eng = self.store.engine
        applied = entries[0].index - 1
        saw_admin = False
        try:
            for e in entries:
                cmd = self._apply_entry(e)
                if e.conf_change is not None or (cmd or {}).get("admin") is not None:
                    saw_admin = True
                applied = e.index
        except BaseException:
            # a fault mid-apply (e.g. an injected failpoint) must not
            # lose committed entries: ready() advanced node.applied to
            # commit when it drained them, so rewind to the last entry
            # actually applied — the next ready() re-delivers the rest
            self.node.applied = applied
            self.apply_index = max(self.apply_index, applied)
            eng.put_cf(
                CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(applied)
            )
            raise
        # ApplyState: recovery resumes application after this index
        self.apply_index = max(self.apply_index, self.node.applied)
        eng.put_cf(
            CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(self.node.applied)
        )
        if saw_admin:
            self.store.sync_kv_wal()  # see _schedule_apply's admin barrier
        self._flush_pending_reads()

    def _schedule_apply(self, entries: list[Entry], apply_sys) -> None:
        """Route committed entries into the apply pipeline (apply.rs:920).

        Plain data entries stream to the region's apply worker in FIFO runs;
        admin / conf-change entries are a BARRIER: they mutate raft and store
        state owned by this thread, so the queue drains, then they apply
        inline.  Decode happens once, here, and the decoded command rides
        into the worker."""
        run: list = []
        for e in entries:
            cmd = None
            if e.conf_change is None and e.data:
                cmd = decode_cmd(e.data)
            if e.conf_change is None and (cmd is None or cmd.get("admin") is None):
                run.append((e, cmd))
                continue
            # admin or conf entry: flush the pipeline, apply inline
            if run:
                self._submit_run(run, apply_sys)
                run = []
            apply_sys.flush(self.region.id)
            self._apply_entry(e)
            self.apply_index = max(self.apply_index, e.index)
            self.store.engine.put_cf(
                CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(e.index)
            )
            # admin mutations (split/merge/conf) rewrite region meta that
            # recovery cannot re-derive from the raft log alone — close any
            # buffered-apply window immediately (no-op otherwise)
            self.store.sync_kv_wal()
            self._flush_pending_reads()  # reads waiting on this admin index
        if run:
            self._submit_run(run, apply_sys)

    def _submit_run(self, run: list, apply_sys) -> None:
        apply_sys.submit(self.region.id, lambda run=run: self._apply_run(run))

    def _apply_run(self, run: list) -> None:
        """Executed on an apply worker: data commands only (no admin, no
        conf change — those applied inline under the barrier).

        The whole run — data ops AND the ApplyState advance — folds into ONE
        engine WriteBatch (apply.rs likewise commits a committed-entry batch
        as one atomic RocksDB write): acks fire after the combined write
        lands, observers see each command in order.

        A failure LATCHES the peer broken (apply_broken): later runs must
        not advance apply_index past a gap whose effects never reached the
        engine — that would persist an ApplyState recovery believes, silently
        diverging the replica from its log (the reference panics the store
        here, apply.rs; we stop the region and surface the error)."""
        if self.apply_broken:
            return
        import time as _time

        from ..util.metrics import REGISTRY

        t0 = _time.perf_counter()
        try:
            self._apply_run_inner(run)
        except BaseException:
            self.apply_broken = True
            raise  # the worker records the error (batch_system errors list)
        REGISTRY.histogram(
            "tikv_raftstore_apply_duration_seconds",
            "Committed-entry batch apply latency",
        ).observe(_time.perf_counter() - t0)
        REGISTRY.histogram(
            "tikv_raftstore_apply_batch_entries", "Entries per apply batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256),
        ).observe(len(run))

    def _apply_run_inner(self, run: list) -> None:
        eng = self.store.engine
        applied = None
        is_witness = self.peer_id in self.node.witnesses
        wb = WriteBatch()
        executed: list = []  # (entry, cmd) whose ops are in wb
        acks: list = []  # deferred until the batch is durable in the engine
        for e, cmd in run:
            if cmd is None:
                applied = e.index  # leader noop: nothing to execute
                continue
            if not self._epoch_ok(cmd):
                acks.append((e, None, EpochError(self.region.clone())))
                applied = e.index
                continue
            fail_point("apply_before_exec")
            if is_witness:
                # witnesses replicate and vote on the LOG but never
                # materialize data
                acks.append((e, {"applied_index": e.index}, None))
                applied = e.index
                continue
            for op, cf, key, val in cmd["ops"]:
                dkey = keys.data_key(key)
                if op == "put":
                    wb.put_cf(cf, dkey, val)
                elif op == "delete":
                    wb.delete_cf(cf, dkey)
                elif op == "delete_range":
                    wb.delete_range_cf(cf, dkey, keys.data_key(val))
            executed.append((e, cmd))
            acks.append((e, {"applied_index": e.index}, None))
            applied = e.index
        if applied is not None:
            new_apply = max(self.apply_index, applied)
            wb.put_cf(CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(new_apply))
            if executed:
                # write-through delta BEFORE the batch reaches the engine: a
                # snapshot carrying new_apply can then only be taken after
                # the region cache buffered this batch (no gap window), and
                # one carrying an older index holds none of it
                self._emit_write_delta(
                    [op for _e, c in executed for op in c["ops"]], new_apply
                )
            with self.apply_mu:
                eng.write(wb)
                self.apply_index = new_apply
        elif wb.ops:
            eng.write(wb)
        for _e, cmd in executed:
            self.store.on_applied(self.region, cmd)
        for e, result, err in acks:
            self._ack(e, result, err)
        self._flush_pending_reads()

    def _flush_pending_reads(self) -> None:
        fire = []
        with self._cb_mu:
            rest = []
            for ctx, index in self.pending_read_states:
                if self.apply_index >= index:
                    ent = self.pending_reads.pop(ctx, None)
                    if ent is not None:
                        fire.append(ent[0])
                else:
                    rest.append((ctx, index))
            self.pending_read_states = rest
        for cb in fire:
            cb(None)

    def _send_raft_msg(self, m: Message) -> None:
        to_peer = self.region.peer_by_id(m.to)
        if to_peer is None or to_peer.store_id == 0:
            # placement unknown (region metadata lags the conf entry that
            # carries it) — drop; retries resolve once the entry applies
            return
        if m.type == MsgType.SNAPSHOT and m.snapshot is None:
            m.snapshot = self._generate_snapshot(for_witness=m.to in self.node.witnesses)
        rmsg = RaftMessage(
            region_id=self.region.id,
            from_peer=RegionPeer(self.peer_id, self.store.store_id),
            to_peer=to_peer,
            msg=m,
            region_epoch=RegionEpoch(self.region.epoch.conf_ver, self.region.epoch.version),
            region=self.region.clone(),
        )
        self.store.transport.send(to_peer.store_id, rmsg)

    # -- apply -------------------------------------------------------------

    def _apply_entry(self, e: Entry):
        """Apply one committed entry; returns the decoded cmd (None for conf
        changes / noops) so callers can inspect it without re-decoding."""
        if e.conf_change is not None:
            self._apply_conf_change(e)
            self._ack(e, None, None)
            return None
        if not e.data:
            return None  # leader noop
        cmd = decode_cmd(e.data)
        if not self._epoch_ok(cmd):
            self._ack(e, None, EpochError(self.region.clone()))
            return cmd
        admin = cmd.get("admin")
        if admin is not None and admin[0] == "split":
            self._apply_split(admin)
            self._ack(e, {"split": True}, None)
            return cmd
        if admin is not None and admin[0] == "compute_hash":
            # witnesses hold no data: they ack but never hash or verify —
            # their empty-range hash would flag a bogus divergence
            if self.peer_id not in self.node.witnesses:
                self._apply_compute_hash(e)
            self._ack(e, {"compute_hash": True}, None)
            return cmd
        if admin is not None and admin[0] == "verify_hash":
            if self.peer_id not in self.node.witnesses:
                self._apply_verify_hash(
                    admin[1], admin[2], admin[3] if len(admin) > 3 else None)
            self._ack(e, {"verify_hash": True}, None)
            return cmd
        if admin is not None and admin[0] == "prepare_merge":
            self.merging = True
            self.region.epoch.version += 1
            self.store.persist_region(self.region, merging=True)
            _notify_region_cache(self.region.id, "prepare_merge")
            self._ack(e, {"prepare_merge": True}, None)
            return cmd
        if admin is not None and admin[0] == "commit_merge":
            self._apply_commit_merge(admin)
            self._ack(e, {"commit_merge": True}, None)
            return cmd
        if admin is not None and admin[0] == "ingest_sst":
            # every non-witness replica materializes the staged entries from
            # the log payload (fsm/apply.rs exec_ingest_sst): a replica that
            # was down replays this entry (or receives it in a snapshot) and
            # converges without any side-channel file transfer
            if self.peer_id not in self.node.witnesses:
                self._apply_ingest_sst(admin[1], apply_index=e.index)
            self._ack(e, {"ingest_sst": True, "applied_index": e.index}, None)
            return cmd
        fail_point("apply_before_exec")
        if self.peer_id in self.node.witnesses:
            # witnesses replicate and vote on the LOG but never materialize
            # data (raftstore witness feature); acking keeps apply advancing
            self._ack(e, {"applied_index": e.index}, None)
            return cmd
        self._exec_data_cmd(cmd, self.region, apply_index=e.index)
        self._ack(e, {"applied_index": e.index}, None)
        return cmd

    def _emit_write_delta(self, ops, apply_index: int) -> None:
        """Write-through delta emission (ISSUE 4): after a committed data
        batch is IN the engine — and before ``apply_index`` becomes visible
        to new snapshots — hand the batch's ops to the coprocessor region
        column cache, so warm reads under write load fold the change in
        without re-scanning CF_WRITE.  The ``apply_emit_write_delta``
        failpoint (and any emission failure) degrades to a lost-marker: the
        cache then repairs through its scan_delta fallback, never through a
        gapped delta chain."""
        try:
            from ..copr.region_cache import (
                notify_region_write,
                notify_region_write_lost,
            )
        except ImportError:
            return
        token = self.store.data_token  # matches RegionSnapshot.data_token
        try:
            fail_point("apply_emit_write_delta")
        except Exception:  # noqa: BLE001 — emission off: content unknown
            notify_region_write_lost(self.region.id, apply_index, token=token)
            return
        try:
            notify_region_write(self.region.id, ops, apply_index,
                                get_default=self._get_default_value,
                                token=token)
        except Exception:  # noqa: BLE001 — a cache-side fault must never
            # break apply: degrade to the lost-marker (scan_delta repairs)
            notify_region_write_lost(self.region.id, apply_index, token=token)

    def _get_default_value(self, enc_key_with_ts: bytes) -> bytes | None:
        return self.store.engine.get_cf(CF_DEFAULT, keys.data_key(enc_key_with_ts))

    def _apply_ingest_sst(self, blob: bytes, apply_index: int | None = None) -> None:
        """Write the ingest payload — encoded (cf, key, value) entries, keys
        already in their final (rewritten) form — under the region prefix.
        Keys outside the region range are dropped identically on every
        replica (the propose-time check rejects them; this keeps a replayed
        entry deterministic even across a racing split)."""
        wb = WriteBatch()
        ops = []
        for cf, key, val in _decode_ingest_entries(blob):
            if not self.region.contains(key):
                continue
            wb.put_cf(cf, keys.data_key(key), val)
            ops.append(("put", cf, key, val))
        self.store.engine.write(wb)
        if ops and apply_index is not None:
            self._emit_write_delta(ops, apply_index)
        # apply observers (CDC, resolved-ts) must see ingested writes like
        # any other applied command — a change feed that silently misses an
        # imported batch is data loss downstream
        self.store.on_applied(self.region, {"ops": ops, "ingest_sst": True})

    def _exec_data_cmd(self, cmd: dict, region: Region,
                       apply_index: int | None = None) -> None:
        """Execute a data command's write ops against the engine (shared by
        the normal apply path and commit-merge catch-up).  With an
        ``apply_index``, the committed batch also flows into the region
        column cache as a write-through delta — emission runs BEFORE the
        batch reaches the engine, and the engine write and the apply_index
        advance are one step to a snapshot (``apply_mu``): a snapshot that
        reports this index can only exist after its delta was buffered, and
        one that reports an older index holds none of it."""
        wb = WriteBatch()
        for op, cf, key, val in cmd["ops"]:
            dkey = keys.data_key(key)
            if op == "put":
                wb.put_cf(cf, dkey, val)
            elif op == "delete":
                wb.delete_cf(cf, dkey)
            elif op == "delete_range":
                wb.delete_range_cf(cf, dkey, keys.data_key(val))
        if cmd["ops"] and apply_index is not None:
            self._emit_write_delta(cmd["ops"], apply_index)
        with self.apply_mu:
            # lint: allow(lock-blocking-call) -- the write and the index are one step
            self.store.engine.write(wb)
            if apply_index is not None:
                self.apply_index = max(self.apply_index, apply_index)
        self.store.on_applied(region, cmd)

    def _ack(self, e: Entry, result, err) -> None:
        # proposals append in index order, so everything relevant to this
        # entry sits at the FRONT: pop while index <= e.index instead of
        # rescanning the whole in-flight window per committed entry (that
        # rescan made the ack path O(window²) across a batch)
        fire = []
        with self._cb_mu:
            props = self.proposals
            i = 0
            n = len(props)
            while i < n and props[i].index <= e.index:
                p = props[i]
                if p.index == e.index and p.term == e.term:
                    fire.append((p.cb, err if err is not None else result))
                else:
                    # behind the applied index, or overwritten by a
                    # different term's entry at the same index
                    fire.append((p.cb, NotLeaderError(self.region.id, None)))
                i += 1
            if i:
                del props[:i]
        for cb, arg in fire:
            cb(arg)

    def _notify_removed_peer(self, pid: int, applied_index: int) -> None:
        """Final notification to a peer leaving the config: push the commit
        index covering its own removal before it stops hearing from us (the
        reference relies on PD stale-peer GC as the backstop)."""
        if pid != self.peer_id and self.node.is_leader() and self.region.peer_by_id(pid) is not None:
            self._send_raft_msg(
                Message(
                    MsgType.HEARTBEAT, self.peer_id, pid, self.node.term,
                    commit=min(applied_index, self.node.match_index.get(pid, 0)),
                )
            )

    # -- consistency check (coprocessor/consistency_check.rs + mvcc) --------

    def _region_hash(self) -> int:
        """crc64 over every (cf, key, value) of the region's data range at
        the CURRENT apply point — every replica applying the compute_hash
        entry at the same log index must produce the same value (the raw +
        mvcc hash of consistency_check.rs, one pass over the data CFs)."""
        from ..copr.analyze import crc64

        eng = self.store.engine
        start = keys.data_key(self.region.start_key)
        end = keys.data_end_key(self.region.end_key)
        h = 0
        for cf in DATA_CFS:
            for k, v in eng.scan_cf(cf, start, end):
                h = crc64(cf.encode(), h)
                h = crc64(k, h)
                h = crc64(v, h)
        return h

    def _apply_compute_hash(self, e: Entry) -> None:
        """Every replica hashes its region data at this entry's apply point
        (ConsistencyCheckObserver).  The LEADER follows up by replicating
        its own hash in a verify_hash entry, so replicas compare against
        the leader at the exact same index.

        Integrity ride-along (docs/integrity.md): the same apply point is
        the perfect pin for the DERIVED plane — every replica scrubs its
        resident device images of this region against its own engine here,
        and the leader's verify_hash additionally carries its image
        fingerprints so replicas holding an image at the same apply index
        literally cross-check device-image hashes alongside the mvcc hash."""
        h = self._region_hash()
        self.store.consistency_hashes[self.region.id] = (e.index, h)
        _count_consistency("compute")
        img_fps: dict = {}
        try:
            from ..copr import integrity as _copr_integrity
            from .raftkv import RegionSnapshot

            snap = RegionSnapshot(
                self.store.engine.snapshot(), self.region.clone(),
                apply_index=e.index, data_token=self.store.data_token,
            )
            _copr_integrity.scrub_region_on_consistency_check(
                self.region.id, self.store.data_token, snap)
            img_fps = _copr_integrity.region_image_fingerprints(
                self.region.id, self.store.data_token)
        except Exception as exc:  # noqa: BLE001 — the derived plane must
            # never poison raft apply; the scrubber re-covers it.  But a
            # FATAL-mode mismatch must not vanish silently either: log it
            # (the quarantine + mismatch counters already fired inside
            # verify_image before the raise)
            from ..copr.integrity import IntegrityMismatch

            if isinstance(exc, IntegrityMismatch):
                _LOG.error("fatal integrity mismatch at consistency check",
                           region=self.region.id, error=repr(exc))
        if self.node.is_leader():
            self.propose_cmd(
                {
                    "epoch": (self.region.epoch.conf_ver, self.region.epoch.version),
                    "ops": [],
                    "admin": ("verify_hash", e.index, h, img_fps),
                },
                lambda r: None,
            )

    def _apply_verify_hash(self, index: int, expected: int,
                           image_fps: dict | None = None) -> None:
        rec = self.store.consistency_hashes.get(self.region.id)
        if rec is None or rec[0] != index:
            return  # this replica joined after the compute entry (snapshot)
        if rec[1] != expected:
            _count_consistency("mismatch")
            # divergence: the reference panics the store; we record the
            # region as inconsistent and surface it via the debug service
            self.store.inconsistent_regions[self.region.id] = {
                "index": index,
                "local_hash": rec[1],
                "leader_hash": expected,
            }
        else:
            _count_consistency("match")
        if image_fps:
            # derived-plane replica cross-check: local images pinned at the
            # leader's recorded apply index compare fingerprints; divergence
            # quarantines the LOCAL image (the mvcc hash above adjudicates
            # the region — the derived plane just rebuilds)
            try:
                from ..copr import integrity as _copr_integrity

                _copr_integrity.cross_check_image_fps(
                    self.region.id, self.store.data_token, image_fps)
            except Exception as exc:  # noqa: BLE001 — never poison apply,
                # but never let a fatal-mode signal vanish unlogged either
                _LOG.error("image fingerprint cross-check failed",
                           region=self.region.id, error=repr(exc))

    def schedule_consistency_check(self, cb: Callable | None = None) -> None:
        """Leader-side: replicate a compute_hash point (the periodic
        CONSISTENCY_CHECK tick of raftstore)."""
        self.propose_cmd(
            {
                "epoch": (self.region.epoch.conf_ver, self.region.epoch.version),
                "ops": [],
                "admin": ("compute_hash",),
            },
            cb or (lambda r: None),
        )

    def transfer_leader_to(self, target_peer_id: int) -> bool:
        """PD-ordered transfer (MsgTransferLeader -> MsgTimeoutNow): tell the
        target to campaign with stickiness bypassed."""
        if not self.node.is_leader():
            return False
        target = self.region.peer_by_id(target_peer_id)
        if target is None or target.role != "voter":
            return False
        # only transfer to a fully caught-up target (raft-rs gates
        # MsgTimeoutNow on matched progress): a lagging target would lose
        # the forced election and cost a leaderless round for nothing
        if self.node.match_index.get(target_peer_id, 0) < self.node.log.last_index():
            return False
        self._send_raft_msg(
            Message(MsgType.TIMEOUT_NOW, self.peer_id, target_peer_id, self.node.term)
        )
        return True

    def _send_tombstone(self, to_peer: RegionPeer) -> None:
        """Explicit destroy order for a peer a committed conf change removed
        (kvproto is_tombstone; raftstore gc of stale peers).  Carries the
        POST-change epoch, which excludes the target — the receiver verifies
        before destroying.  Lossy delivery is fine: a surviving stale peer
        campaigns eventually, and members answer those contacts with fresh
        tombstones (Store.process_messages)."""
        self.store.transport.send(
            to_peer.store_id,
            RaftMessage(
                region_id=self.region.id,
                from_peer=RegionPeer(self.peer_id, self.store.store_id),
                to_peer=to_peer,
                msg=Message(MsgType.HEARTBEAT, self.peer_id, to_peer.peer_id, self.node.term),
                region_epoch=RegionEpoch(self.region.epoch.conf_ver, self.region.epoch.version),
                region=self.region.clone(),
                is_tombstone=True,
            ),
        )

    def _sync_added_peer(self, pid: int, sid: int = 0) -> None:
        """Region bookkeeping for a peer entering the config: record its
        placement (from the replicated entry) and role, and seed brand-new
        peers by snapshot, never by full log replay (peer_storage.rs:
        uninitialized peers wait for one).

        Keeps region metadata in lockstep with the raft node's view:
        add_learner on an existing VOTER is a role no-op there, so it is
        here too (single-step demotion goes remove → add_learner; joint
        demotion flips the node's sets first, so the role follows)."""
        existing = self.region.peer_by_id(pid)
        if pid in self.node.witnesses:
            role = "witness"
        elif pid in self.node.learners:
            role = "learner"
        else:
            role = "voter"
        if existing is None:
            self.region.peers.append(RegionPeer(pid, sid, role))
            if self.node.is_leader() and pid != self.peer_id:
                self.node.force_snapshot.add(pid)
        else:
            existing.role = role

    def _persist_conf_change_state(self, e: Entry) -> None:
        """Membership changed at apply time: region meta, the raft-state blob
        (which embeds the ConfState — the copy written earlier in this ready
        is PRE-change), and the apply index covering this entry go down in
        ONE WriteBatch.  Atomicity matters: a new ConfState persisted with a
        stale apply index would replay the conf entry on recovery against the
        already-updated voter set (enter_joint replay would corrupt outgoing
        to C_new and double-bump conf_ver)."""
        wb = WriteBatch()
        wb.put_cf(
            CF_RAFT, keys.region_state_key(self.region.id), encode_region(self.region, self.merging)
        )
        rl = self.store.raft_log
        if rl is not None:
            rl.put_state(self.region.id, self._encode_raft_state())
        else:
            wb.put_cf(CF_RAFT, keys.raft_state_key(self.region.id), self._encode_raft_state())
        wb.put_cf(CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(e.index))
        self.store.engine.write(wb)

    def _apply_conf_change(self, e: Entry) -> None:
        op, pid = e.conf_change[0], e.conf_change[1]
        if op in ("enter_joint", "leave_joint"):
            to_tombstone = self._apply_conf_change_v2(e, op, pid)
            if to_tombstone is None:
                return  # we left the config and erased our own state
            self.region.epoch.conf_ver += 1
            self._persist_conf_change_state(e)
            _notify_region_cache(self.region.id, "conf_change")
            for p in to_tombstone:
                self._send_tombstone(p)  # after the bump: epoch must exclude them
            return
        removed_peer = self.region.peer_by_id(pid) if op == "remove" else None
        if op == "remove":
            self._notify_removed_peer(pid, e.index)
        was_witness = pid in self.node.witnesses
        self.node.apply_conf_change(e.conf_change)
        if op in ("add", "add_learner", "add_witness"):
            self._sync_added_peer(pid, e.conf_change[2] if len(e.conf_change) > 2 else 0)
            if op == "add" and was_witness:
                # witness -> data voter conversion: the peer has NO data and
                # must be reseeded with a full snapshot before serving
                if self.node.is_leader() and pid != self.peer_id:
                    self.node.force_snapshot.add(pid)
                    self.node._send_append(pid)  # queue the snapshot now
                elif pid == self.peer_id:
                    # we are the converted peer: accept the reseed snapshot
                    # even though our log/commit look fully caught up
                    self.node.force_accept_snapshot = True
        elif op == "promote":
            existing = self.region.peer_by_id(pid)
            if existing is not None:
                existing.role = "voter"
        else:
            self.region.peers = [p for p in self.region.peers if p.peer_id != pid]
            if pid == self.peer_id:
                # applying our own removal: erase persisted identity — a
                # plain destroy would let recover() resurrect the replica
                self.store.destroy_peer_tombstone(self.region.id)
                return
        self.region.epoch.conf_ver += 1
        self._persist_conf_change_state(e)
        _notify_region_cache(self.region.id, "conf_change")
        if removed_peer is not None and self.node.is_leader() and removed_peer.peer_id != self.peer_id:
            # the removed peer may never receive its own removal entry (the
            # leader stops replicating to it the moment it leaves the
            # config) — an explicit tombstone at the NEW epoch destroys it
            self._send_tombstone(removed_peer)

    def _apply_conf_change_v2(self, e: Entry, op: str, changes) -> "list[RegionPeer] | None":
        """Joint membership change (raft thesis 4.3; raft-rs ConfChangeV2,
        applied by components/raftstore/src/store/peer.rs on_admin): the
        enter_joint entry reshapes the incoming config atomically while the
        old voters remain a second quorum; leave_joint retires them.  The
        leader auto-proposes leave_joint as soon as enter_joint applies
        (raft-rs auto_leave); if leadership changes in between, the NEW
        leader re-proposes it from _become_leader.  Region metadata mirrors
        the node's view; peers absent from both configs after leaving are
        destroyed."""
        node = self.node
        if op == "enter_joint":
            node.apply_conf_change(e.conf_change)
            for ch in changes:
                sop, pid = ch[0], ch[1]
                if sop != "remove":
                    self._sync_added_peer(pid, ch[2] if len(ch) > 2 else 0)
                # peers removed-in-joint stay listed as voters: they still
                # vote via the outgoing config until leave_joint
            if node.is_leader():
                node.propose_conf_change(("leave_joint", ()))
            return []
        # leave_joint
        dropped = (node.outgoing or set()) - node.voters - node.learners
        dropped_peers = [p for p in self.region.peers if p.peer_id in dropped]
        for pid in dropped:
            self._notify_removed_peer(pid, e.index)
        node.apply_conf_change(e.conf_change)
        members = node.voters | node.learners
        self.region.peers = [p for p in self.region.peers if p.peer_id in members]
        for p in self.region.peers:
            if p.peer_id in node.witnesses:
                p.role = "witness"
            elif p.peer_id in node.learners:
                p.role = "learner"
            else:
                p.role = "voter"
        if self.peer_id in dropped:
            self.store.destroy_peer_tombstone(self.region.id)
            return None  # self-destroyed: caller must not re-persist us
        return dropped_peers if node.is_leader() else []

    def _apply_split(self, admin) -> None:
        _, split_key, new_region_id, new_pids = admin
        _LOG.info(
            "region split applied",
            region=self.region.id,
            new_region=new_region_id,
            split_key=slog.key(split_key),
        )
        old = self.region
        new_peers = [
            RegionPeer(pid, p.store_id, p.role) for pid, p in zip(new_pids, old.peers)
        ]
        new_region = Region(
            id=new_region_id,
            start_key=split_key,
            end_key=old.end_key,
            epoch=RegionEpoch(old.epoch.conf_ver, old.epoch.version + 1),
            peers=new_peers,
        )
        old.end_key = split_key
        old.epoch.version += 1
        self.store.persist_region(old)
        self.store.create_peer(new_region)
        _notify_region_cache(old.id, "split")
        _notify_region_cache(new_region.id, "split")
        self.store.on_split(old, new_region)

    def _encode_raft_state(self) -> bytes:
        n = self.node
        out = bytearray(
            codec.encode_u64(n.term)
            + codec.encode_u64(n.vote or 0)
            + codec.encode_u64(n.commit)
            + codec.encode_u64(n.log.snapshot_index)
            + codec.encode_u64(n.log.snapshot_term)
        )
        # membership (ConfState): region roles alone can't reconstruct a
        # joint config after a crash — C_old ∩ C_new is ambiguous — so the
        # three sets ride in RaftLocalState
        out += encode_conf_state(n.voters, n.learners, n.outgoing, n.witnesses)
        return bytes(out)

    def _apply_commit_merge(self, admin) -> None:
        """Absorb the (frozen) right-neighbor source region: catch a lagging
        local source replica up from the entries carried in the command
        (raftstore's CatchUpLogs — peer.rs on_catch_up_logs_for_merge), then
        extend our range, bump version above both, and destroy the local
        source peer (CommitMerge)."""
        _, source_id, source_end, source_version, source_commit, carried = admin
        src = self.store.peers.get(source_id)
        if src is not None:
            self._catch_up_source(src, source_commit, carried)
        self.region.end_key = source_end
        self.region.epoch.version = max(self.region.epoch.version, source_version) + 1
        self.store.persist_region(self.region)
        if src is not None:
            self.store.destroy_peer(source_id)
        self.store.erase_region_state(source_id)
        _notify_region_cache(self.region.id, "merge")
        _notify_region_cache(source_id, "merge")
        self.store.on_merge(self.region, source_id)

    def _catch_up_source(self, src: "StorePeer", source_commit: int, carried: list) -> None:
        """CatchUpLogs: a source replica that trails source_commit splices the
        carried (canonical, committed) entries into its OWN raft log and
        applies them through its normal apply path — epoch checks, admin
        entries (splits committed before the freeze), acks and observers all
        behave exactly as they would have without the lag, so the replica
        cannot diverge from the ones that applied these entries live.  This
        removes the quiesce-before-CommitMerge requirement
        (peer.rs on_catch_up_logs_for_merge)."""
        # drain what the replica itself knows to be committed first —
        # synchronously: the assertions below need the engine caught up
        if self.store.apply_system is not None:
            self.store.apply_system.flush(src.region.id)
        src.handle_ready(sync_apply=True)
        node = src.node
        if node.applied >= source_commit:
            return
        for eb in carried:
            e = _decode_entry(eb)
            if e.index <= node.commit or e.index > source_commit:
                continue  # below: already canonical locally; above: not needed
            t = node.log.term_at(e.index)
            if t is None:
                if e.index > node.log.last_index() + 1:
                    raise AssertionError(
                        f"catch-up gap on region {src.region.id}: log ends at "
                        f"{node.log.last_index()}, next carried entry {e.index} "
                        "(source log compacted below this replica — needs snapshot)"
                    )
                node.log.append([e])
            elif t != e.term:
                # local uncommitted leftovers of an old term lose to the
                # committed history
                node.log.truncate_from(e.index)
                node.log.append([e])
        if node.log.last_index() < source_commit:
            raise AssertionError(
                f"catch-up incomplete on region {src.region.id}: log reaches "
                f"{node.log.last_index()} of {source_commit}"
            )
        node.commit = max(node.commit, source_commit)
        src.handle_ready(sync_apply=True)  # normal apply: epoch checks, splits, observers
        if node.applied < source_commit:
            raise AssertionError(
                f"catch-up applied {node.applied} of {source_commit} on region {src.region.id}"
            )

    # -- snapshots ---------------------------------------------------------

    def _generate_snapshot(self, for_witness: bool = False) -> RaftSnapshot:
        """Full region-range snapshot of the data CFs + region meta
        (store/snap.rs; meta rides along like SnapshotMeta).  Witness
        targets get META ONLY — they vote but never store data."""
        fail_point("region_gen_snapshot")
        if self.store.apply_system is not None:
            # the engine scan below must contain every entry the snapshot
            # index claims — drain in-flight applies first (apply.rs
            # observes the same barrier through its FSM ordering)
            self.store.apply_system.flush(self.region.id)
        eng = self.store.engine
        out = bytearray()
        out += codec.encode_compact_bytes(encode_region(self.region, self.merging))
        if not for_witness:
            start = keys.data_key(self.region.start_key)
            end = keys.data_end_key(self.region.end_key)
            for cf in DATA_CFS:
                items = list(eng.scan_cf(cf, start, end))
                out += codec.encode_compact_bytes(cf.encode())
                out += codec.encode_var_u64(len(items))
                for k, v in items:
                    out += codec.encode_compact_bytes(k)
                    out += codec.encode_compact_bytes(v)
        return RaftSnapshot(
            # apply_index, not node.applied: the data scanned above is only
            # guaranteed complete up to what actually finished applying
            index=self.apply_index,
            term=self.node.log.term_at(self.apply_index) or self.node.term,
            data=bytes(out),
            voters=tuple(self.node.voters),
            learners=tuple(self.node.learners),
            outgoing=tuple(self.node.outgoing or ()),
            witnesses=tuple(self.node.witnesses),
        )

    def _apply_snapshot(self, snap: RaftSnapshot) -> None:
        eng = self.store.engine
        b = snap.data
        meta, off = codec.decode_compact_bytes(b, 0)
        self.region, self.merging = decode_region(meta)
        wb = WriteBatch()
        start = keys.data_key(self.region.start_key)
        end = keys.data_end_key(self.region.end_key)
        for cf in DATA_CFS:
            wb.delete_range_cf(cf, start, end)
        while off < len(b):
            cf, off = codec.decode_compact_bytes(b, off)
            n, off = codec.decode_var_u64(b, off)
            for _ in range(n):
                k, off = codec.decode_compact_bytes(b, off)
                v, off = codec.decode_compact_bytes(b, off)
                wb.put_cf(cf.decode(), k, v)
        eng.write(wb)
        self.store.persist_region(self.region)
        wb2 = WriteBatch()
        rl = self.store.raft_log
        if rl is not None:
            rl.put_state(self.region.id, self._encode_raft_state())
            # log below the snapshot point is obsolete; purge lets the log
            # engine drop/unlink dead segments (engine.rs gc on snapshot).
            # The snapshot data itself must outlive the purged entries.
            self.store.sync_kv_wal()
            rl.purge(self.region.id, self.node.log.snapshot_index)
        else:
            wb2.put_cf(CF_RAFT, keys.raft_state_key(self.region.id), self._encode_raft_state())
        wb2.put_cf(CF_RAFT, keys.apply_state_key(self.region.id), codec.encode_u64(self.node.applied))
        eng.write(wb2)
        # a snapshot replaces region data wholesale — no per-batch deltas
        # exist for it, so pending write-through chains must not survive
        _notify_region_write_lost(self.region.id, self.node.applied,
                                  token=self.store.data_token)
        self.apply_index = max(self.apply_index, self.node.applied)


def encode_region(region: Region, merging: bool = False) -> bytes:
    out = bytearray()
    out += codec.encode_var_u64(region.id)
    out += codec.encode_compact_bytes(region.start_key)
    out += codec.encode_compact_bytes(region.end_key)
    out += codec.encode_var_u64(region.epoch.conf_ver)
    out += codec.encode_var_u64(region.epoch.version)
    out += codec.encode_var_u64(len(region.peers))
    for p in region.peers:
        out += codec.encode_var_u64(p.peer_id)
        out += codec.encode_var_u64(p.store_id)
        out.append({"voter": 0, "learner": 1, "witness": 2}.get(p.role, 0))
    out.append(1 if merging else 0)
    return bytes(out)


def decode_region(b: bytes) -> tuple[Region, bool]:
    """Returns (region, merging)."""
    rid, off = codec.decode_var_u64(b, 0)
    start, off = codec.decode_compact_bytes(b, off)
    end, off = codec.decode_compact_bytes(b, off)
    cv, off = codec.decode_var_u64(b, off)
    v, off = codec.decode_var_u64(b, off)
    n, off = codec.decode_var_u64(b, off)
    peers = []
    for _ in range(n):
        pid, off = codec.decode_var_u64(b, off)
        sid, off = codec.decode_var_u64(b, off)
        role = {0: "voter", 1: "learner", 2: "witness"}.get(b[off], "voter")
        off += 1
        peers.append(RegionPeer(pid, sid, role))
    merging = off < len(b) and b[off] == 1
    return Region(rid, start, end, RegionEpoch(cv, v), peers), merging


def encode_conf_state(voters, learners, outgoing, witnesses=()) -> bytes:
    """The ConfState tail of the raft-state blob: varint-counted u64 groups
    (voters, learners, outgoing, witnesses).  Shared by persistence,
    recovery, and the Debugger's unsafe-recover so the layout has exactly
    one definition."""
    out = bytearray()
    for group in (voters, learners, outgoing or set(), witnesses or set()):
        out += codec.encode_var_u64(len(group))
        for pid in sorted(group):
            out += codec.encode_u64(pid)
    return bytes(out)


def decode_conf_state(state: bytes, off: int = 40) -> tuple[set, set, set, set]:
    """Inverse of encode_conf_state, reading at ``off`` (after the 40-byte
    fixed term/vote/commit/snapshot header).  The witness group is optional
    for blobs persisted before it existed."""
    groups = []
    for gi in range(4):
        if gi == 3 and off >= len(state):
            groups.append(set())
            break
        cnt, off = codec.decode_var_u64(state, off)
        ids = set()
        for _ in range(cnt):
            ids.add(codec.decode_u64(state, off))
            off += 8
        groups.append(ids)
    return groups[0], groups[1], groups[2], groups[3]


def _encode_entry(e: Entry) -> bytes:
    out = bytearray()
    out += codec.encode_var_u64(e.term)
    out += codec.encode_var_u64(e.index)
    out += codec.encode_compact_bytes(e.data)
    if e.conf_change and e.conf_change[0] in ("enter_joint", "leave_joint"):
        out.append(2)
        out += codec.encode_compact_bytes(e.conf_change[0].encode())
        changes = e.conf_change[1]
        out += codec.encode_var_u64(len(changes))
        for ch in changes:
            out += codec.encode_compact_bytes(ch[0].encode())
            out += codec.encode_var_u64(ch[1])
            out += codec.encode_var_u64(ch[2] if len(ch) > 2 else 0)
    elif e.conf_change:
        out.append(3)  # (op, peer_id, store_id) — placement rides in the log
        out += codec.encode_compact_bytes(e.conf_change[0].encode())
        out += codec.encode_var_u64(e.conf_change[1])
        out += codec.encode_var_u64(e.conf_change[2] if len(e.conf_change) > 2 else 0)
    else:
        out.append(0)
    return bytes(out)


def _decode_entry(b: bytes) -> Entry:
    term, off = codec.decode_var_u64(b, 0)
    index, off = codec.decode_var_u64(b, off)
    data, off = codec.decode_compact_bytes(b, off)
    conf = None
    if b[off] == 1:
        op, off2 = codec.decode_compact_bytes(b, off + 1)
        pid, _ = codec.decode_var_u64(b, off2)
        conf = (op.decode(), pid)
    elif b[off] == 3:
        op, off2 = codec.decode_compact_bytes(b, off + 1)
        pid, off2 = codec.decode_var_u64(b, off2)
        sid, _ = codec.decode_var_u64(b, off2)
        conf = (op.decode(), pid, sid)
    elif b[off] == 2:
        op, off2 = codec.decode_compact_bytes(b, off + 1)
        n, off2 = codec.decode_var_u64(b, off2)
        changes = []
        for _ in range(n):
            sop, off2 = codec.decode_compact_bytes(b, off2)
            pid, off2 = codec.decode_var_u64(b, off2)
            sid, off2 = codec.decode_var_u64(b, off2)
            changes.append((sop.decode(), pid, sid))
        conf = (op.decode(), tuple(changes))
    return Entry(term, index, data, conf)


# ---------------------------------------------------------------------------
# Store
# ---------------------------------------------------------------------------

class Store:
    """All region peers on one node + message routing (StoreFsm + router)."""

    def __init__(self, store_id: int, transport: Transport, engine: BTreeEngine | None = None,
                 raft_log=None):
        self.store_id = store_id
        self.transport = transport
        self.engine = engine or BTreeEngine()
        # optional purpose-built raft log engine (native/raftlog.cc — the
        # raft_log_engine role, selected per-store like the reference at
        # components/server/src/server.rs:153-157).  When set, raft entries +
        # hard state live there; region meta + apply state stay in CF_RAFT of
        # the KV engine so they remain crash-atomic with applied data.
        self.raft_log = raft_log
        # True when the KV engine's WAL runs buffered because the raft log is
        # the durable source of truth (the reference applies with sync=false
        # and flushes kvdb before purging raft logs).  Set by the server
        # assembly; gates the sync_kv_wal() barriers below.
        self.kv_buffered = False
        self.peers: dict[int, StorePeer] = {}
        self._inbox: list[RaftMessage] = []
        self._compact_requested = threading.Event()
        self._mu = make_rlock("raft.store", label=f"store-{store_id}")
        self.split_observers: list[Callable] = []
        self.merge_observers: list[Callable] = []
        self.apply_observers: list[Callable] = []
        # apply pipeline (batch-system shape): None = inline apply on the
        # raft thread (deterministic test clusters); enabled by server nodes
        self.apply_system = None
        # generic FSM batch system (fsm_system.Router): when attached, raft
        # messages route to per-region mailboxes and poller threads drive
        # step/ready — the synchronous inbox path is only for test clusters
        self.fsm_router = None
        # consistency check (consistency_check.rs): per-region (index, hash)
        # recorded at compute_hash apply; divergences land in
        # inconsistent_regions for the debug service / operator
        self.consistency_hashes: dict[int, tuple[int, int]] = {}
        self.inconsistent_regions: dict[int, dict] = {}

    @property
    def data_token(self):
        """THE identity of this store's data (docs/write_path.md): stamped
        on RegionSnapshots, carried by write-through notifies, bound by the
        region column cache.  One definition — a mismatch anywhere silently
        drops every delta as foreign."""
        return id(self.engine)

    def enable_apply_pipeline(self, workers: int = 2) -> None:
        """Apply committed data entries off the raft thread (apply.rs
        ApplyBatchSystem): append of entry N+1 overlaps apply of entry N."""
        from .batch_system import ApplySystem

        if self.apply_system is None:
            self.apply_system = ApplySystem(workers, name=f"apply-{self.store_id}")

    def stop_apply_pipeline(self) -> None:
        if self.apply_system is not None:
            self.apply_system.stop()
            self.apply_system = None

    # -- lifecycle ---------------------------------------------------------

    def create_peer(self, region: Region) -> StorePeer:
        with self._mu:
            me = region.peer_on_store(self.store_id)
            assert me is not None, f"store {self.store_id} not in region {region.id}"
            peer = StorePeer(self, region.clone(), me.peer_id)
            self.peers[region.id] = peer
            # a peer born after the replication status arrived still needs
            # the label-group config (set_replication_mode no-ops on repeats)
            last_repl = getattr(self, "_last_repl_status", None)
            if last_repl is not None:
                self._apply_repl_to_peer(peer, last_repl)
            self.persist_region(peer.region)
            # under buffered apply the meta write above is not yet durable,
            # but the peer may durably VOTE (raft log) before any admin
            # barrier flushes it — recovery only enumerates KV region meta,
            # so a crash would forget the vote.  Region creation is rare;
            # pay one fdatasync here.
            self.sync_kv_wal()
            if self.fsm_router is not None:
                self.fsm_router.register(region.id)
                self.fsm_router.send(region.id, ("ready",))
            return peer

    def destroy_peer(self, region_id: int) -> None:
        self.peers.pop(region_id, None)
        if self.fsm_router is not None:
            self.fsm_router.close(region_id)

    def destroy_peer_tombstone(self, region_id: int) -> None:
        """Destroy a peer AND erase its persisted identity (the reference
        writes PeerState::Tombstone): recovery must not resurrect a replica
        the config no longer contains."""
        if self.apply_system is not None:
            # an in-flight apply run would re-write data + apply_state for
            # the region AFTER the erase, leaving orphaned keys recovery
            # could mistake for live state — drain first
            self.apply_system.flush(region_id)
        self.peers.pop(region_id, None)
        if self.fsm_router is not None:
            self.fsm_router.close(region_id)
        self.erase_region_state(region_id)

    def erase_region_state(self, region_id: int) -> None:
        erase_region_state(self.engine, region_id)
        if self.raft_log is not None:
            # ordering matters under buffered apply: the CF_RAFT tombstone
            # deletes must be durable BEFORE the log engine forgets the
            # region's vote/term — a crash in between would otherwise
            # resurrect the peer with term=0 and let it double-vote
            self.sync_kv_wal()
            self.raft_log.clean(region_id)

    def set_replication_mode(self, status: dict) -> None:
        """Apply the PD ReplicationStatus (replication_mode.rs) to every
        peer's raft node: DrAutoSync in ``sync`` state turns label-group
        commit on; ``async``/``sync_recover`` (or Majority mode) turn it
        off.  Safe to call from the heartbeat thread — flag/dict swaps are
        atomic under the GIL and the raft thread re-evaluates commit on its
        next tick.  No-ops when the status is unchanged (it rides EVERY
        heartbeat) so the common majority-mode path costs one comparison."""
        if status == getattr(self, "_last_repl_status", None):
            return
        self._last_repl_status = dict(status)
        with self._mu:
            peers = list(self.peers.values())
        for peer in peers:
            self._apply_repl_to_peer(peer, status)
            self.notify_region(peer.region.id)

    def _apply_repl_to_peer(self, peer, status: dict) -> None:
        node = peer.node
        if status.get("mode") == "dr_auto_sync":
            labels = status.get("labels") or {}
            node.peer_groups = {
                p.peer_id: labels.get(p.store_id) for p in peer.region.peers
            }
            node.group_commit = status.get("state") == "sync"
        else:
            node.group_commit = False
            node.peer_groups = {}

    def sync_kv_wal(self) -> None:
        """Make every buffered apply write durable (kvdb flush before raft-log
        purge, and after rare admin mutations whose loss recovery could not
        re-derive).  No-op unless the server opted into buffered apply."""
        if self.kv_buffered:
            # closing the unsynced window = one fdatasync of the engine WAL
            self.engine.set_sync(True)
            self.engine.set_sync(False)

    def persist_region(self, region: Region, merging: bool = False) -> None:
        self.engine.put_cf(
            CF_RAFT, keys.region_state_key(region.id), encode_region(region, merging)
        )

    def recover(self) -> int:
        """Rebuild every peer from persisted state after a restart
        (fsm/store.rs init: scan region states, restore PeerStorage).
        Returns the number of recovered peers."""
        snap = self.engine.snapshot()
        prefix = keys.LOCAL_PREFIX + keys.REGION_META_PREFIX
        recovered = 0
        for k, v in snap.scan_cf(CF_RAFT, prefix, prefix[:-1] + bytes([prefix[-1] + 1])):
            region, merging = decode_region(v)
            me = region.peer_on_store(self.store_id)
            if me is None or region.id in self.peers:
                continue
            peer = StorePeer(self, region, me.peer_id)
            peer.merging = merging
            node = peer.node
            if self.raft_log is not None:
                state = self.raft_log.state(region.id)
                if state is None:
                    # store predates the log engine (or it was switched on):
                    # migrate this region's CF_RAFT log + state into the log
                    # engine, or recovery would come up amnesiac (term=0,
                    # no entries) while the real state sits in CF_RAFT
                    state = self._migrate_region_log(snap, region.id)
            else:
                state = snap.get_cf(CF_RAFT, keys.raft_state_key(region.id))
            if state is not None:
                node.term = codec.decode_u64(state, 0)
                vote = codec.decode_u64(state, 8)
                node.vote = vote or None
                node.commit = codec.decode_u64(state, 16)
                node.log.snapshot_index = codec.decode_u64(state, 24)
                node.log.snapshot_term = codec.decode_u64(state, 32)
                node.log.offset = node.log.snapshot_index + 1
                if len(state) > 40:  # persisted ConfState (incl. joint config)
                    voters, learners, outgoing, witnesses = decode_conf_state(state)
                    node.voters, node.learners = voters, learners
                    node.outgoing = outgoing or None
                    node.witnesses = witnesses
            applied_raw = snap.get_cf(CF_RAFT, keys.apply_state_key(region.id))
            applied = codec.decode_u64(applied_raw) if applied_raw else 0
            entries = []
            if self.raft_log is not None:
                for _idx, blob in self.raft_log.entries(region.id):
                    e = _decode_entry(blob)
                    if e.index > node.log.snapshot_index:
                        entries.append(e)
            else:
                log_prefix = keys.region_raft_prefix(region.id) + keys.RAFT_LOG_SUFFIX
                for lk, lv in snap.scan_cf(
                    CF_RAFT, log_prefix, log_prefix[:-1] + bytes([log_prefix[-1] + 1])
                ):
                    e = _decode_entry(lv)
                    if e.index > node.log.snapshot_index:
                        entries.append(e)
                entries.sort(key=lambda e: e.index)
            node.log.entries = entries
            node.applied = max(applied, node.log.snapshot_index)
            node.commit = max(node.commit, node.applied)
            peer.apply_index = node.applied
            self.peers[region.id] = peer
            recovered += 1
        return recovered

    def _migrate_region_log(self, snap, region_id: int) -> bytes | None:
        """One-shot CF_RAFT -> log-engine migration for a region persisted
        before the raft log engine was enabled.  Returns the legacy raft
        state blob (also written into the log engine), or None if the region
        never persisted one."""
        state = snap.get_cf(CF_RAFT, keys.raft_state_key(region_id))
        log_prefix = keys.region_raft_prefix(region_id) + keys.RAFT_LOG_SUFFIX
        legacy = []
        for lk, lv in snap.scan_cf(
            CF_RAFT, log_prefix, log_prefix[:-1] + bytes([log_prefix[-1] + 1])
        ):
            e = _decode_entry(lv)
            legacy.append((e.index, lv))
        legacy.sort()
        if state is None and not legacy:
            return None
        # contiguous runs (splits/compactions can leave gaps in CF_RAFT)
        run_start = 0
        for i in range(1, len(legacy) + 1):
            if i == len(legacy) or legacy[i][0] != legacy[i - 1][0] + 1:
                run = legacy[run_start:i]
                if run:
                    self.raft_log.append(region_id, run[0][0], [b for _, b in run])
                run_start = i
        if state is not None:
            self.raft_log.put_state(region_id, state)
        # drop the legacy copies so the two stores never diverge
        wb = WriteBatch()
        wb.delete_range_cf(
            CF_RAFT,
            log_prefix + codec.encode_u64(0),
            log_prefix + codec.encode_u64(1 << 62),
        )
        wb.delete_cf(CF_RAFT, keys.raft_state_key(region_id))
        self.engine.write(wb)
        return state

    # -- routing -----------------------------------------------------------

    def region_for_key(self, key: bytes) -> StorePeer | None:
        with self._mu:
            for peer in self.peers.values():
                if peer.region.contains(key):
                    return peer
        return None

    def leader_store_of(self, region_id: int) -> int | None:
        peer = self.peers.get(region_id)
        if peer is None:
            return None
        lid = peer.node.leader_id
        if lid is None:
            return None
        p = peer.region.peer_by_id(lid)
        return p.store_id if p else None

    def enqueue_message(self, rmsg: RaftMessage) -> None:
        router = self.fsm_router
        if router is None:
            with self._mu:
                self._inbox.append(rmsg)
            return
        # batch-system mode: peer traffic lands in the region mailbox; store-
        # level work (tombstones, first contact for an unknown region) goes
        # to the control FSM (router.rs send vs control_box)
        if not rmsg.is_tombstone and rmsg.region_id in self.peers:
            if router.send(rmsg.region_id, ("raft", rmsg)):
                return
        router.send_control(("route", rmsg))

    def notify_region(self, region_id: int) -> None:
        """Wake a region FSM (propose/read just added work for its poller)."""
        if self.fsm_router is not None:
            self.fsm_router.send(region_id, ("ready",))

    def attach_fsm_router(self, router) -> None:
        """Enter batch-system mode: register every live peer's mailbox and
        hand any messages that arrived pre-attach to the control FSM."""
        self.fsm_router = router
        with self._mu:
            for rid in self.peers:
                router.register(rid)
                router.send(rid, ("ready",))
            backlog, self._inbox = self._inbox, []
        for rmsg in backlog:
            router.send_control(("route", rmsg))

    # -- driving -----------------------------------------------------------

    def _route_one(self, rmsg: RaftMessage) -> "StorePeer | None":
        """Store-level routing (fsm/store.rs maybe_create_peer): tombstone
        destruction and first-contact bootstrap.  Returns the peer the
        message should be stepped into, or None if consumed/dropped."""
        peer = self.peers.get(rmsg.region_id)
        if rmsg.is_tombstone:
            # a committed conf change removed us at this epoch: verify
            # and self-destruct (raftstore handling of is_tombstone)
            if (
                peer is not None
                and peer.peer_id == rmsg.to_peer.peer_id
                and rmsg.region_epoch.conf_ver >= peer.region.epoch.conf_ver
                and (rmsg.region is None or rmsg.region.peer_by_id(peer.peer_id) is None)
            ):
                self.destroy_peer_tombstone(rmsg.region_id)
            return None
        if peer is None and rmsg.region is not None:
            # first contact for a new peer (conf change / snapshot):
            # bootstrap it if we're in the carried region
            if rmsg.region.peer_on_store(self.store_id) is not None or rmsg.to_peer.store_id == self.store_id:
                region = rmsg.region.clone()
                if region.peer_on_store(self.store_id) is None:
                    region.peers.append(RegionPeer(rmsg.to_peer.peer_id, self.store_id))
                with self._mu:
                    peer = self.peers.get(rmsg.region_id)
                    if peer is None:
                        peer = StorePeer(self, region, rmsg.to_peer.peer_id)
                        self.peers[rmsg.region_id] = peer
                if self.fsm_router is not None:
                    self.fsm_router.register(rmsg.region_id)
        if peer is not None and rmsg.to_peer.peer_id == peer.peer_id:
            return peer
        return None

    def _step_checked(self, peer: "StorePeer", rmsg: RaftMessage) -> None:
        """Step with the stale-sender GC check (raftstore is_msg_stale):
        a sender a NEWER committed epoch excludes gets a tombstone back
        instead of a vote/step — the retry path when the removal-time
        tombstone was lost."""
        if (
            rmsg.region_epoch.conf_ver < peer.region.epoch.conf_ver
            and peer.region.peer_by_id(rmsg.from_peer.peer_id) is None
            and rmsg.from_peer.peer_id != peer.peer_id
        ):
            peer._send_tombstone(rmsg.from_peer)
            return
        peer.node.step(rmsg.msg)

    def process_messages(self) -> bool:
        with self._mu:
            inbox, self._inbox = self._inbox, []
        moved = bool(inbox)
        for rmsg in inbox:
            peer = self._route_one(rmsg)
            if peer is not None:
                self._step_checked(peer, rmsg)
        return moved

    def handle_readies(self) -> bool:
        moved = False
        for peer in list(self.peers.values()):
            if peer.handle_ready():
                moved = True
        return moved

    def tick(self) -> None:
        for peer in list(self.peers.values()):
            peer.node.tick()
        if self._compact_requested.is_set():
            self._compact_requested.clear()
            self.compact_raft_logs()

    def request_log_compaction(self) -> None:
        """Ask the raft-driving thread to compact at its next tick — log
        state is single-writer (the raft loop); other threads must not
        mutate it concurrently."""
        self._compact_requested.set()

    # -- raft log GC (store/worker/raftlog_gc.rs) ---------------------------

    def compact_raft_logs(self, threshold: int = 1024, slack: int = 64) -> int:
        """Truncate each region's applied log prefix once it exceeds
        ``threshold`` entries.  ``slack`` recent entries always stay for
        cheap catch-up; followers lagging more than ``threshold`` behind are
        abandoned to snapshot seeding (which the append path already
        handles).  Must run on the raft-driving thread (see
        request_log_compaction) — or per region on its own poller in
        batch-system mode.  Returns entries dropped."""
        dropped = 0
        for peer in list(self.peers.values()):
            dropped += self.compact_peer_log(peer, threshold, slack)
        return dropped

    def compact_peer_log(self, peer: "StorePeer", threshold: int = 1024, slack: int = 64) -> int:
        """One region's log truncation; must run on whatever thread owns the
        region's raft state (raft loop, or its FSM poller)."""
        node = peer.node
        # compact at COMPLETED apply: with the pipeline, node.applied may
        # run ahead of the engine — compacting past apply_index would
        # strand recovery (persisted ApplyState behind a truncated log)
        applied = min(node.applied, peer.apply_index)
        first = node.log.offset
        if applied - first + 1 <= threshold:
            return 0
        compact_to = applied - slack
        if node.is_leader():
            # don't compact below followers that are close enough to catch
            # up from the log; stragglers further behind than the
            # threshold are abandoned to snapshot seeding (raftlog_gc.rs)
            near_matches = [
                m
                for p in node._replicas()
                if (m := node.match_index.get(p, 0)) >= applied - threshold
            ]
            if near_matches:
                compact_to = min(compact_to, min(near_matches))
        if compact_to <= first - 1:
            return 0
        term = node.log.term_at(compact_to)
        if term is None:
            return 0
        node.log.compact_to(compact_to, term)
        if self.raft_log is not None:
            # applied data must be durable before the entries that produced
            # it disappear (the reference flushes kvdb before raft-engine
            # purge), and the raft state carrying the new truncated index
            # must be durable before purge unlinks segments — recovery with
            # the OLD snapshot_index against a purged log would misalign
            # RaftLog's positional entry indexing (core.py:135).  Then a
            # logical purge marker, not a range delete — the log engine
            # unlinks whole dead segments (raftlog.cc gc/rewrite).
            self.sync_kv_wal()
            self.raft_log.put_state(peer.region.id, peer._encode_raft_state())
            self.raft_log.purge(peer.region.id, compact_to)
        else:
            wb = WriteBatch()
            log_prefix = keys.region_raft_prefix(peer.region.id) + keys.RAFT_LOG_SUFFIX
            wb.delete_range_cf(
                CF_RAFT,
                log_prefix + codec.encode_u64(0),
                log_prefix + codec.encode_u64(compact_to + 1),
            )
            wb.put_cf(CF_RAFT, keys.raft_state_key(peer.region.id), peer._encode_raft_state())
            self.engine.write(wb)
        return compact_to - first + 1

    def on_split(self, old: Region, new: Region) -> None:
        for cb in self.split_observers:
            cb(self, old, new)

    def on_merge(self, target: Region, source_id: int) -> None:
        for cb in self.merge_observers:
            cb(self, target, source_id)

    def on_applied(self, region: Region, cmd: dict) -> None:
        for cb in self.apply_observers:
            cb(self, region, cmd)


class StoreFsmDelegate:
    """PollHandler driving one store's region FSMs (fsm/peer.rs PeerFsmDelegate
    + fsm/store.rs StoreFsmDelegate, on fsm_system.BatchSystem).

    Region mailbox messages: ("raft", rmsg) step + ready, ("tick",) election/
    heartbeat timers, ("ready",) wake after a propose/read, ("compact",) log
    GC.  Control mailbox: ("route", rmsg) store-level routing (bootstrap /
    tombstone), after which the message is forwarded to the now-live region.
    """

    def __init__(self, store: Store):
        self.store = store

    def begin(self, batch_size: int) -> None:
        pass

    def end(self, addrs: list) -> None:
        pass

    def handle(self, region_id: int, msgs: list) -> None:
        store = self.store
        peer = store.peers.get(region_id)
        if peer is None:
            return
        for m in msgs:
            kind = m[0]
            if kind == "raft":
                rmsg = m[1]
                if rmsg.to_peer.peer_id == peer.peer_id:
                    store._step_checked(peer, rmsg)
            elif kind == "tick":
                peer.node.tick()
            elif kind == "compact":
                store.compact_peer_log(peer)
            elif kind == "tombstone":
                # destruction runs HERE, on the poller that owns this FSM —
                # never on the control poller, which could otherwise erase
                # region state concurrently with a handle_ready persist
                rmsg = m[1]
                if (
                    peer.peer_id == rmsg.to_peer.peer_id
                    and rmsg.region_epoch.conf_ver >= peer.region.epoch.conf_ver
                    and (rmsg.region is None or rmsg.region.peer_by_id(peer.peer_id) is None)
                ):
                    store.destroy_peer_tombstone(region_id)
                    return
            # ("ready",) carries no action: the unconditional ready sweep
            # below is the point of the wakeup
        while peer.handle_ready():
            if store.peers.get(region_id) is not peer:
                break  # destroyed mid-sweep (merge source / tombstone)

    def handle_control(self, msgs: list) -> None:
        store = self.store
        for m in msgs:
            if m[0] != "route":
                continue
            rmsg = m[1]
            if rmsg.is_tombstone:
                # forward to the owning FSM: destruction must not run on the
                # control poller (it would race the region poller's persists)
                if rmsg.region_id in store.peers:
                    store.fsm_router.send(rmsg.region_id, ("tombstone", rmsg))
                continue
            peer = store._route_one(rmsg)
            if peer is None:
                continue
            # peer now exists (possibly just bootstrapped): its own FSM
            # processes the message so per-region state stays single-owner
            if not store.fsm_router.send(rmsg.region_id, ("raft", rmsg)):
                # mailbox raced closed — drop, sender will retry
                pass
