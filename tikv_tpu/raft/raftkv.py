"""RaftKv: the Engine implementation that routes through raft consensus.

Re-expression of ``src/server/raftkv.rs`` (:214 exec_snapshot, :244
exec_write_requests, :378/:435): writes become proposed commands applied by
quorum; snapshots are linearizable views obtained after a ReadIndex barrier
(leader lease local reads are the fast path in the reference; ReadIndex keeps
the same correctness with less machinery).

``RegionSnapshot`` exposes the store engine under the region's range with the
``z`` data prefix applied transparently, so the whole txn/coprocessor stack
works unchanged over raft-replicated data (store/region_snapshot.rs).
"""

from __future__ import annotations

import threading
import time
from typing import Callable

from ..analysis.sanitizer import note_blocking
from ..storage.engine import Cursor, Snapshot, WriteBatch
from ..storage.kv import Engine
from ..util import keys
from .region import NotLeaderError, Region
from .store import Store


class _PrefixCursor(Cursor):
    """Cursor over data keys with the z-prefix stripped (region-bounded)."""

    def __init__(self, inner: Cursor):
        self._c = inner

    def seek(self, key: bytes) -> bool:
        return self._c.seek(keys.data_key(key))

    def seek_for_prev(self, key: bytes) -> bool:
        return self._c.seek_for_prev(keys.data_key(key))

    def seek_to_first(self) -> bool:
        return self._c.seek_to_first()

    def seek_to_last(self) -> bool:
        return self._c.seek_to_last()

    def next(self) -> bool:
        return self._c.next()

    def prev(self) -> bool:
        return self._c.prev()

    def valid(self) -> bool:
        return self._c.valid()

    def key(self) -> bytes:
        return keys.origin_key(self._c.key())

    def value(self) -> bytes:
        return self._c.value()


def _applied_snapshot(peer, engine):
    """``(apply_index, engine snapshot)`` sampled as one step: the apply
    path writes a run and advances the peer's apply_index under
    ``apply_mu``, so the snapshot holds exactly the applied entries up to
    the index it is given — neither fewer (a too-high claim would mark
    missing writes as present) nor more (a later commit the index does not
    claim would be read under an image that lacks it)."""
    mu = getattr(peer, "apply_mu", None)
    if mu is None:
        return peer.apply_index, engine.snapshot()
    with mu:
        # lint: allow(lock-blocking-call) -- a snapshot is a refcount, not I/O
        return peer.apply_index, engine.snapshot()


class RegionSnapshot(Snapshot):
    def __init__(self, engine_snapshot: Snapshot, region: Region,
                 apply_index: int | None = None, data_token=None):
        self._snap = engine_snapshot
        self.region = region
        # data version this snapshot reflects (the peer's apply_index at
        # snapshot time): the coprocessor's region column cache keys on
        # (region epoch, apply_index) and reads both straight off the
        # snapshot, so serving paths need no extra context plumbing
        self.apply_index = apply_index
        # identity of the underlying store engine: the region cache binds to
        # the first token it serves and drops write-through notifies from
        # any OTHER engine — region ids alone are not process-unique
        # (embedded endpoints, multi-store test processes)
        self.data_token = data_token
        # stale-read provenance (docs/stale_reads.md): the stale path stamps
        # ``stale=True`` plus the RegionReadProgress pair it was admitted
        # under, so serving layers can count follower-served reads and
        # assert the pairing invariant (apply_index >= required index)
        self.stale = False
        self.read_progress: tuple[int, int] | None = None
        self._lower = keys.data_key(region.start_key)
        self._upper = keys.data_end_key(region.end_key)
        if hasattr(engine_snapshot, "scan_spans"):
            # offered only over an engine that has it: callers probe for it
            self.scan_spans = self._scan_spans

    def sequence(self) -> int | None:
        return self._snap.sequence()

    def cf_touched_seq(self, cf: str) -> int | None:
        # per CF and per STORE: another region's write to the CF moves it too
        return self._snap.cf_touched_seq(cf)

    def _clamp(self, start: bytes, end: bytes | None) -> tuple[bytes, bytes]:
        lo = max(keys.data_key(start), self._lower)
        hi = self._upper if end is None else min(keys.data_key(end), self._upper)
        return lo, hi

    def scan_cf(self, cf: str, start: bytes, end: bytes | None,
                limit: int | None = None, reverse: bool = False):
        """The underlying snapshot's own range scan, clamped to the region
        and with the z prefix stripped.  The generic cursor walk this
        overrides costs one engine seek per row, which over the native
        engine is one FFI call and one merge of memtable and runs per row:
        a region's worth of rows took minutes that way."""
        lo, hi = self._clamp(start, end)
        if lo >= hi:
            return
        for k, v in self._snap.scan_cf(cf, lo, hi, limit, reverse):
            yield keys.origin_key(k), v

    def _scan_spans(self, cf: str, start: bytes, end: bytes | None):
        """The native snapshot's ``scan_spans`` (one FFI crossing for the
        range, nothing cut or copied), clamped to the region; the keys' spans
        leave the z prefix out."""
        lo, hi = self._clamp(start, end)
        if lo >= hi:
            import numpy as np

            return (b"", *(np.empty(0, dtype=np.int64),) * 4)
        buf, k_at, k_len, v_at, v_len = self._snap.scan_spans(cf, lo, hi)
        z = len(keys.DATA_PREFIX)
        return buf, k_at + z, k_len - z, v_at, v_len

    def get_cf(self, cf: str, key: bytes) -> bytes | None:
        dkey = keys.data_key(key)
        if not (self._lower <= dkey < self._upper):
            return None
        return self._snap.get_cf(cf, dkey)

    def multi_get_cf(self, cf: str, ks: list[bytes]) -> list[bytes | None]:
        """``get_cf`` of every key: one read of the engine for those inside
        the region, None for the others."""
        dkeys = [keys.data_key(k) for k in ks]
        inside = [i for i, d in enumerate(dkeys) if self._lower <= d < self._upper]
        out: list[bytes | None] = [None] * len(dkeys)
        for i, v in zip(inside, self._snap.multi_get_cf(cf, [dkeys[i] for i in inside])):
            out[i] = v
        return out

    def newest_versions_cf(self, cf: str, user_keys: list[bytes], ts: int,
                           lower: bytes | None = None, upper: bytes | None = None):
        """The trait's answer under ``cursor_cf``'s bounds, z prefix stripped."""
        lo, hi = self._bounds(lower, upper)
        found = self._snap.newest_versions_cf(
            cf, [keys.data_key(k) for k in user_keys], ts, lo, hi)
        return [None if f is None else (keys.origin_key(f[0]), f[1]) for f in found]

    def _bounds(self, lower: bytes | None, upper: bytes | None) -> tuple[bytes, bytes]:
        lo = keys.data_key(lower) if lower is not None else self._lower
        hi = keys.data_key(upper) if upper is not None else self._upper
        return max(lo, self._lower), min(hi, self._upper)

    def cursor_cf(self, cf: str, lower: bytes | None = None, upper: bytes | None = None) -> Cursor:
        return _PrefixCursor(self._snap.cursor_cf(cf, *self._bounds(lower, upper)))


class RaftKv(Engine):
    """Engine over one store's raft peers.  ``pump`` drives the cluster's
    message loop until a callback fires (test clusters pump synchronously;
    the server wires a background poller)."""

    def __init__(
        self,
        store: Store,
        pump: Callable[[], None] | None = None,
        resolved_ts=None,
        propose_timeout: float = 10.0,
    ):
        self.store = store
        # default: yield to the node's background raft loop
        self.pump = pump or (lambda: time.sleep(0.0005))
        # ResolvedTsEndpoint enabling follower stale reads (kv.rs stale-read
        # path gated by RegionReadProgress/resolved-ts)
        self.resolved_ts = resolved_ts
        self.propose_timeout = propose_timeout

    @property
    def data_token(self):
        """Identity of the data this engine serves — delegates to the ONE
        definition on the store (docs/write_path.md): RegionSnapshots stamp
        it, apply-side write-through notifies carry it, and the region
        column cache binds to it at construction."""
        return self.store.data_token

    def _peer_for_ctx(self, ctx: dict | None):
        ctx = ctx or {}
        region_id = ctx.get("region_id")
        if region_id is not None:
            peer = self.store.peers.get(region_id)
            if peer is None:
                raise NotLeaderError(region_id, None)
            return peer
        key = ctx.get("key", b"")
        peer = self.store.region_for_key(key)
        if peer is None:
            raise NotLeaderError(-1, None)
        return peer

    class DataNotReadyError(Exception):
        def __init__(self, region_id: int, read_ts: int, resolved: int):
            self.region_id = region_id
            self.read_ts = read_ts
            self.resolved = resolved
            super().__init__(
                f"region {region_id}: stale read at {read_ts} above resolved ts {resolved}"
            )

    def _stale_ready(self, peer, ctx: dict) -> tuple[int, int]:
        """ONE definition of stale-read admission (snapshot() and the copr
        scheduler's ``check_read_ready`` probe): returns the region's
        RegionReadProgress pair when this replica may serve ``read_ts``,
        else raises NotLeader (witness) / DataNotReady (watermark or apply
        lag).  Never touches the engine."""
        # follower stale read: safe at/below the region's resolved-ts
        # watermark on any DATA replica — witnesses store no data
        if peer.peer_id in peer.node.witnesses:
            raise NotLeaderError(peer.region.id, self.store.leader_store_of(peer.region.id))
        if self.resolved_ts is None:
            raise ValueError("stale reads need a resolved-ts endpoint")
        read_ts = ctx.get("read_ts")
        if read_ts is None:
            raise ValueError("stale reads need read_ts in the context")
        resolved, required_idx = self.resolved_ts.progress_of(peer.region.id)
        # RegionReadProgress pairing: the watermark is only meaningful on
        # a replica whose ENGINE contains at least the index it was
        # computed at (apply_index — node.applied may run ahead of the
        # apply pipeline) — a lagging follower must refuse rather than
        # serve a snapshot missing committed data
        if read_ts > resolved or peer.apply_index < required_idx:
            raise RaftKv.DataNotReadyError(peer.region.id, read_ts, resolved)
        return resolved, required_idx

    def check_read_ready(self, ctx: dict | None) -> tuple[int, int] | None:
        """Admission-time readiness probe: raises exactly what ``snapshot``
        would raise for a stale read — NotLeader on a witness, DataNotReady
        on a lagging watermark/apply — WITHOUT freezing the engine.  The
        copr read scheduler calls this before a stale request costs a queue
        slot, let alone a device dispatch (docs/stale_reads.md).  Returns
        the (resolved_ts, required_apply_index) pair, or None for reads
        that don't take the stale path."""
        ctx = ctx or {}
        if not ctx.get("stale_read"):
            return None
        return self._stale_ready(self._peer_for_ctx(ctx), ctx)

    def local_snapshot(self, region_id: int) -> RegionSnapshot:
        """A PROTOCOL-FREE snapshot of this store's local apply state for
        ``region_id`` — no lease, no ReadIndex, works on followers.  Not
        linearizable; exists for the integrity scrubber (docs/integrity.md),
        which verifies derived images against the LOCAL engine at a pinned
        apply index — exactly what this returns.  Never serve client reads
        off it."""
        peer = self.store.peers.get(region_id)
        if peer is None:
            raise NotLeaderError(region_id, None)
        applied, frozen = _applied_snapshot(peer, self.store.engine)
        return RegionSnapshot(frozen, peer.region.clone(),
                              apply_index=applied,
                              data_token=self.data_token)

    def snapshot(self, ctx: dict | None = None) -> RegionSnapshot:
        peer = self._peer_for_ctx(ctx)
        ctx = ctx or {}
        if ctx.get("stale_read"):
            resolved, required_idx = self._stale_ready(peer, ctx)
            # apply_index and the engine freeze as one step: the snapshot
            # holds exactly the data of the index it claims — the region
            # cache stamps images with this index (docs/write_path.md
            # apply_index contract)
            applied, frozen = _applied_snapshot(peer, self.store.engine)
            snap = RegionSnapshot(frozen, peer.region.clone(),
                                  apply_index=applied,
                                  data_token=self.data_token)
            snap.stale = True
            snap.read_progress = (resolved, required_idx)
            return snap
        if not peer.node.is_leader():
            if ctx.get("replica_read") and peer.peer_id not in peer.node.witnesses:
                # replica read (read.rs replica-read + ReplicaReadLockChecker
                # role): the FOLLOWER serves a linearizable snapshot by
                # asking the leader for a ReadIndex over the wire and waiting
                # until its own apply catches up to it — the raft core's
                # READ_INDEX forward/RESP machinery does the round trip
                return self._read_index_barrier(peer)
            raise NotLeaderError(peer.region.id, self.store.leader_store_of(peer.region.id))
        # lease fast path (LocalReader, read.rs:342): while the leader holds a
        # quorum-granted lease and the ENGINE contains everything committed
        # (apply_index, not node.applied — the pipeline may still be writing),
        # reads skip the ReadIndex round entirely
        if peer.node.lease_valid() and peer.apply_index >= peer.node.commit:
            applied, frozen = _applied_snapshot(peer, self.store.engine)
            return RegionSnapshot(frozen, peer.region.clone(),
                                  apply_index=applied,
                                  data_token=self.data_token)
        return self._read_index_barrier(peer)

    def _read_index_barrier(self, peer) -> RegionSnapshot:
        """ONE definition of the ReadIndex wait (leader slow path AND
        follower replica reads): block until the read point is applied
        locally, then snapshot."""
        note_blocking("raftkv.read_index_barrier")
        done = threading.Event()
        err: list = []

        def cb(e):
            if e is not None:
                err.append(e)
            done.set()

        peer.read_index(cb)
        self._pump_until(done, peer.region.id)
        if err:
            raise err[0]
        applied, frozen = _applied_snapshot(peer, self.store.engine)
        return RegionSnapshot(frozen, peer.region.clone(),
                              apply_index=applied,
                              data_token=self.data_token)

    def write(self, ctx: dict | None, batch: WriteBatch) -> None:
        # one full propose -> replicate -> apply -> ack round trip: a caller
        # holding any subsystem lock across this stalls every peer of that
        # lock for a raft round (sanitizer flags exactly that)
        note_blocking("raftkv.write")
        peer = self._peer_for_ctx(ctx)
        ops = []
        for op, cf, key, val in batch.ops:
            ops.append((op, cf, key, val))
        cmd = {
            "epoch": (peer.region.epoch.conf_ver, peer.region.epoch.version),
            "ops": ops,
        }
        done = threading.Event()
        result: list = []
        # propose→apply span handle (docs/tracing.md): begun at propose on
        # the caller's thread, FINISHED inside the write callback — which
        # fires on the apply pipeline's thread, so the span's duration is
        # the true replicate+apply time, not the caller's ack-wait.  The
        # tracer lock is a leaf: finishing under apply locks is safe.
        from ..util import trace

        sp = trace.begin("raft.propose_apply", region=peer.region.id,
                         ops=len(ops))

        def cb(r):
            sp.finish()
            result.append(r)
            done.set()

        try:
            peer.propose_cmd(cmd, cb)
            self._pump_until(done, peer.region.id)
        finally:
            if not done.is_set():
                # timeout/propose failure: the callback will never fire —
                # close the handle so the trace record cannot leak open
                sp.tag(error="propose_incomplete").finish()
        r = result[0]
        if isinstance(r, Exception):
            raise r

    def _pump_until(self, done, region_id: int) -> None:
        """Wall-clock deadline, not a round count: completion may come from
        the apply pipeline's worker threads, which need real time regardless
        of how fast the caller's pump spins."""
        deadline = time.monotonic() + self.propose_timeout
        while time.monotonic() < deadline:
            if done.is_set():
                return
            self.pump()
        raise TimeoutError(f"raft command on region {region_id} did not complete (no quorum?)")
