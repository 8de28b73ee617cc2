"""Node lifecycle: bootstrap a store against PD and keep it beating.

Re-expression of ``src/server/node.rs`` (:61 Node, :153 bootstrap: alloc store
id from PD, bootstrap the first region) and the raftstore PD worker
(``store/worker/pd.rs:101``): periodic store heartbeats (capacity/usage) and
per-region heartbeats from leaders, plus PD-driven region split when a region
grows past the configured size.
"""

from __future__ import annotations

import threading
import time

from ..pd.client import PdClient
from ..raft.region import Peer as RegionPeer, Region, RegionEpoch
from ..raft.store import Store, Transport
from ..util import keys
from ..util.metrics import REGISTRY

REGION_COUNT = REGISTRY.gauge(
    "tikv_raftstore_region_count", "Regions hosted by this store")
LEADER_COUNT = REGISTRY.gauge(
    "tikv_raftstore_leader_count", "Regions this store leads")
STORE_USED_BYTES = REGISTRY.gauge(
    "tikv_store_size_bytes", "Engine resident bytes, by type")

FIRST_REGION_ID = 1


class Node:
    def __init__(
        self,
        pd: PdClient,
        transport: Transport,
        store_id: int | None = None,
        split_threshold_keys: int | None = None,
        engine=None,
        split_qps_threshold: float | None = None,
        consistency_check_interval: float | None = None,
        raft_log=None,
    ):
        self.pd = pd
        self.store_id = store_id or pd.alloc_id()
        self.store = Store(self.store_id, transport, engine=engine, raft_log=raft_log)
        # server nodes run the apply pipeline (apply.rs ApplyBatchSystem):
        # committed data entries apply off the raft thread
        self.store.enable_apply_pipeline()
        self.split_threshold_keys = split_threshold_keys
        # load-based auto split (store/worker/split_controller.rs): write
        # ops per region per heartbeat; sustained load above the threshold
        # for two consecutive beats splits the region at its middle key
        self.split_qps_threshold = split_qps_threshold
        self._write_ops: dict[int, int] = {}
        self._hot_beats: dict[int, int] = {}
        self.consistency_check_interval = consistency_check_interval
        self._last_consistency = 0.0
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # faults escaping the raft loop (e.g. injected failpoints) land here
        # instead of silently killing the daemon thread; apply re-delivery is
        # handled by the store (Peer.handle_ready rewinds on failure)
        self.thread_errors: list[Exception] = []
        # callables invoked once per store heartbeat (memory-trace polling,
        # CDC idle reaping, ...); exceptions land in thread_errors
        self.heartbeat_hooks: list = []
        pd.put_store(self.store_id)
        self.store.split_observers.append(self._on_split)
        # always counted (one dict increment per applied command): the
        # region-heartbeat load that feeds PD's hot-region leader balance
        # needs real numbers whether or not load SPLITTING is enabled
        self.store.apply_observers.append(self._count_writes)

    def _count_writes(self, store, region, cmd) -> None:
        ops = cmd.get("ops")
        if ops:
            self._write_ops[region.id] = self._write_ops.get(region.id, 0) + len(ops)

    # -- bootstrap ----------------------------------------------------------

    def try_bootstrap_cluster(self, all_store_ids: list[int]) -> Region | None:
        """First node up bootstraps region 1 across the given stores."""
        if self.pd.get_region_by_id(FIRST_REGION_ID) is not None:
            return None
        peers = [RegionPeer(self.pd.alloc_id(), sid) for sid in all_store_ids]
        region = Region(FIRST_REGION_ID, b"", b"", RegionEpoch(), peers)
        self.pd.bootstrap_region(region)
        return region

    def create_region_peers(self) -> None:
        """Create local peers for every PD region placed on this store."""
        region = self.pd.get_region_by_id(FIRST_REGION_ID)
        if region is not None and region.peer_on_store(self.store_id) is not None:
            if region.id not in self.store.peers:
                self.store.create_peer(region)

    # -- background loops ---------------------------------------------------

    def start(self, tick_interval: float = 0.05, heartbeat_interval: float = 0.5,
              pollers: int = 2, use_batch_system: bool = True) -> None:
        if use_batch_system:
            # batch-system mode (batch.rs Poller pool): per-region mailboxes,
            # N pollers, a tick broadcaster — no O(all-regions) loop body
            from ..raft.fsm_system import BatchSystem, Router as FsmRouter
            from ..raft.store import StoreFsmDelegate

            router = FsmRouter()
            self.store.attach_fsm_router(router)
            self._batch_system = BatchSystem(
                router, lambda: StoreFsmDelegate(self.store),
                pollers=pollers, name=f"raftstore-{self.store_id}",
            )
            self._batch_system.errors = self.thread_errors  # share the sink
            self._batch_system.spawn()

            def raft_loop():  # tick broadcaster only
                while not self._stop.is_set():
                    router.broadcast(lambda a: ("tick",))
                    if self.store._compact_requested.is_set():
                        self.store._compact_requested.clear()
                        router.broadcast(lambda a: ("compact",))
                    self._stop.wait(tick_interval)
        else:
            def raft_loop():
                last_tick = 0.0
                while not self._stop.is_set():
                    try:
                        moved = self.store.process_messages()
                        moved |= self.store.handle_readies()
                        now = time.monotonic()
                        if now - last_tick >= tick_interval:
                            self.store.tick()
                            last_tick = now
                    except Exception as exc:  # keep the store beating on faults
                        if len(self.thread_errors) < 128:
                            self.thread_errors.append(exc)
                        moved = False
                    if not moved:
                        time.sleep(0.001)

        def pd_loop():
            while not self._stop.is_set():
                try:
                    stats = {"regions": len(self.store.peers)}
                    mem_bytes = getattr(self.store.engine, "mem_bytes", None)
                    if mem_bytes is not None:
                        # size-weighted balance input (store_heartbeat
                        # capacity/used stats, pd.rs:101)
                        stats["used_bytes"] = mem_bytes()
                    REGION_COUNT.set(len(self.store.peers))
                    if "used_bytes" in stats:
                        STORE_USED_BYTES.set(stats["used_bytes"], type="memtable")
                    wal_bytes = getattr(self.store.engine, "wal_bytes", None)
                    if wal_bytes is not None:
                        STORE_USED_BYTES.set(wal_bytes(), type="wal")
                    repl = self.pd.store_heartbeat(self.store_id, stats)
                    if isinstance(repl, dict):
                        # DrAutoSync state rides the heartbeat response
                        # (replication_mode.rs); majority mode clears it
                        self.store.set_replication_mode(repl)
                    led = set()
                    for peer in list(self.store.peers.values()):
                        if peer.node.is_leader():
                            led.add(peer.region.id)
                            op = self.pd.region_heartbeat(
                                peer.region.clone(), self.store_id,
                                load=self._write_ops.get(peer.region.id, 0))
                            if op:
                                self._execute_operator(peer, op)
                            self._maybe_split(peer)
                            self._maybe_load_split(peer, heartbeat_interval)
                    # counts accrued while FOLLOWING must not look like load
                    # the moment this store wins leadership
                    LEADER_COUNT.set(len(led))
                    for rid in list(self._write_ops):
                        if rid not in led:
                            self._write_ops.pop(rid, None)
                            self._hot_beats.pop(rid, None)
                    self._maybe_consistency_check()
                    self.store.request_log_compaction()
                    for hook in self.heartbeat_hooks:
                        hook()
                except Exception as exc:  # PD briefly unreachable: keep beating
                    if len(self.thread_errors) < 128:
                        self.thread_errors.append(exc)
                self._stop.wait(heartbeat_interval)

        for fn in (raft_loop, pd_loop):
            t = threading.Thread(target=fn, daemon=True)
            t.start()
            self._threads.append(t)

    def stop(self) -> list:
        """The loop threads given up on (still alive after their join)."""
        self._stop.set()
        bs = getattr(self, "_batch_system", None)
        if bs is not None:
            bs.shutdown()
        for t in self._threads:
            t.join(timeout=2)
        self.store.stop_apply_pipeline()
        return [t for t in self._threads if t.is_alive()]

    def pump(self) -> None:
        """Synchronous message pump for RaftKv when loops aren't running.
        Not valid in batch-system mode (pollers own per-region state)."""
        if self.store.fsm_router is not None:
            return  # pollers are driving; a sync sweep here would race them
        self.store.process_messages()
        self.store.handle_readies()

    # -- split checking (split_check worker + AutoSplitController) ----------

    def _maybe_split(self, peer) -> None:
        if self.split_threshold_keys is None:
            return
        ks = self._scan_region_keys(peer, self.split_threshold_keys + 1)
        if len(ks) <= self.split_threshold_keys:
            return
        self._propose_middle_split(peer, ks)

    def _scan_region_keys(self, peer, limit: int) -> list:
        eng = self.store.engine
        start = keys.data_key(peer.region.start_key)
        end = keys.data_end_key(peer.region.end_key)
        return [k for k, _ in eng.scan_cf("write", start, end, limit=limit)]

    def _propose_middle_split(self, peer, ks: list) -> None:
        """THE split-point rule, shared by size- and load-based splitting:
        strip the MVCC ts suffix ONLY — region boundaries live in the opaque
        engine key space (the memcomparable-encoded form for txn data),
        never decoded: a raw-decoded boundary would not be order-consistent
        with the stored keys (same rule as the reference, where split-check
        emits origin_key(engine key) verbatim)."""
        if len(ks) < 2:
            return
        split_at = keys.origin_key(ks[len(ks) // 2])
        from ..storage.txn_types import split_ts

        try:
            split_at, _ = split_ts(split_at)
        except ValueError:
            pass  # no ts suffix (raw-mode data)
        if not peer.region.contains(split_at) or split_at == peer.region.start_key:
            return
        new_region_id = self.pd.alloc_id()
        new_pids = [self.pd.alloc_id() for _ in peer.region.peers]
        peer.propose_split(split_at, new_region_id, new_pids, lambda r: None)

    def _on_split(self, store, old: Region, new: Region) -> None:
        self.pd.report_split(old.clone(), new.clone())

    # -- PD operator execution (heartbeat-response scheduling) ---------------

    def _execute_operator(self, peer, op: dict) -> None:
        """Run ONE scheduling order from the PD heartbeat response (the
        raftstore pd worker executing pdpb::RegionHeartbeatResponse)."""
        kind = op.get("type")
        if kind == "transfer_leader":
            if not peer.transfer_leader_to(op["peer_id"]):
                # target not caught up yet (the MsgTimeoutNow gate): put the
                # operator back so a later heartbeat retries it
                add_op = getattr(self.pd, "add_operator", None)
                if add_op is not None:
                    add_op(peer.region.id, op)
        elif kind == "add_peer":
            peer.propose_cmd(
                {
                    "epoch": (peer.region.epoch.conf_ver, peer.region.epoch.version),
                    "ops": [],
                    "admin": ("conf_change", "add", self.pd.alloc_id(), op["store_id"]),
                },
                lambda r: None,
            )
        elif kind == "remove_peer":
            peer.propose_cmd(
                {
                    "epoch": (peer.region.epoch.conf_ver, peer.region.epoch.version),
                    "ops": [],
                    "admin": ("conf_change", "remove", op["peer_id"], 0),
                },
                lambda r: None,
            )

    def _maybe_load_split(self, peer, interval: float) -> None:
        """AutoSplitController: a region whose sustained write rate exceeds
        the threshold for two consecutive heartbeats splits at its middle
        key (split_controller.rs, simplified to write QPS)."""
        if self.split_qps_threshold is None:
            return
        rid = peer.region.id
        ops = self._write_ops.pop(rid, 0)
        if ops / max(interval, 1e-6) >= self.split_qps_threshold:
            self._hot_beats[rid] = self._hot_beats.get(rid, 0) + 1
        else:
            self._hot_beats.pop(rid, None)
            return
        if self._hot_beats[rid] < 2:
            return
        self._hot_beats.pop(rid, None)
        self._propose_middle_split(peer, self._scan_region_keys(peer, 2048))

    def _maybe_consistency_check(self) -> None:
        """Periodic compute_hash proposals on led regions
        (CONSISTENCY_CHECK tick)."""
        if self.consistency_check_interval is None:
            return
        now = time.monotonic()
        if now - self._last_consistency < self.consistency_check_interval:
            return
        self._last_consistency = now
        for peer in list(self.store.peers.values()):
            if peer.node.is_leader():
                peer.schedule_consistency_check()
