"""ServerCluster: N real stores wired over real TCP sockets.

Re-expression of ``components/test_raftstore``'s ``ServerCluster``
(src/server.rs:601): unlike the in-memory ``raft.cluster.Cluster`` (the
NodeCluster analog, which pumps messages deterministically through a
ChannelTransport), every node here runs its own background raft loop and all
peer traffic — raft batches AND chunked snapshots — rides the framed-TCP
transport through ``RaftClient``/``KvService.raft_*``.  Scenario tests
(failover, partition, snapshot catch-up, split/merge) therefore exercise the
actual networked stack.

Fault injection keeps the ``Filter`` API: filters attach to a node's
RemoteTransport (outbound), mirroring transport_simulate.rs.
"""

from __future__ import annotations

import itertools
import threading
import time

from ..pd.client import MockPd
from ..raft.raftkv import RaftKv
from ..raft.region import Peer as RegionPeer, Region, RegionEpoch
from ..raft.store import StorePeer
from ..storage.engine import CF_DEFAULT, WriteBatch
from ..util import keys as keymod, retry
from ..util.inbound import InboundReads
from .node import Node
from .raft_client import RemoteTransport
from .server import Server
from .service import KvService

FIRST_REGION_ID = 1

# one policy for every leader-routed client loop in this harness (the
# reference client's backoff discipline): NotLeader/Epoch/Timeout re-route
# with exponential backoff + jitter; AssertionError/KeyError — the routing
# races that the old loops swallowed wholesale — ride the bounded "suspect"
# class and LOG on final failure instead of masking bugs silently
CLIENT_RETRY = retry.RetryPolicy(base_s=0.05, max_s=0.5, jitter=0.3)


class StoreNode:
    """One store: engine + Store + raft loops + TCP server (a TiKVServer).

    ``full_service`` additionally assembles the serving stack — RaftKv,
    Storage, a coprocessor endpoint, the resolved-ts sidecar (check_leader
    fan-out over the cluster's sockets), the read-degradation ladder
    (``read_plane``), and a WaiterManager whose detector forwards wait-for
    edges to the cluster's detector leader — so scenario tests can drive
    transactional RPCs AND follower/forwarded reads across real stores."""

    def __init__(self, cluster: "ServerCluster", store_id: int, engine=None,
                 full_service: bool = False):
        self.cluster = cluster
        self.full_service = full_service
        security = cluster.security
        self.transport = RemoteTransport(cluster.resolve, security=security)
        self.node = Node(cluster.pd, self.transport, store_id=store_id, engine=engine)
        self.store = self.node.store
        self.read_plane = None
        self.resolved_ts = None
        if full_service:
            from ..copr.endpoint import Endpoint
            from ..sidecar.resolved_ts import ResolvedTsEndpoint
            from .lock_manager import DetectorHandle, WaiterManager
            from .read_plane import ReadPlane
            from ..storage.storage import Storage

            self.read_plane = ReadPlane(
                store=self.store, resolver=cluster.resolve, security=security,
            )
            copr_kwargs = {"enable_device": False, **cluster.copr_kwargs}
            self.resolved_ts = ResolvedTsEndpoint(
                cluster.pd, store_id=store_id,
                # the fan-out rides the read plane's peer-client pool
                check_leader_send=lambda sid, payload: self.read_plane.call(
                    sid, "raft_check_leader", payload, timeout=2.0),
            )
            self.resolved_ts.attach_store(self.store)
            self.read_plane.resolved_ts = self.resolved_ts
            self.raftkv = RaftKv(self.store, resolved_ts=self.resolved_ts)
            self.lock_manager = WaiterManager(
                detector=DetectorHandle(self.store, cluster.resolve, security=security)
            )
            copr = Endpoint(self.raftkv, **copr_kwargs)
            if cluster.overload_config is not None:
                # overload control plane (docs/robustness.md "Overload"):
                # the standalone StoreServer wiring, mirrored so scenario
                # tests drive per-tenant admission over real sockets.  The
                # config object is SHARED across nodes on purpose — one
                # runtime toggle flips the whole cluster.
                from ..copr.overload import OverloadControl

                copr.overload = OverloadControl(
                    cluster.overload_config,
                    region_cache=copr.region_cache)
            self.service = KvService(
                Storage(engine=self.raftkv), raft_router=self.store,
                copr=copr,
                lock_manager=self.lock_manager, pd=cluster.pd,
                resolved_ts=self.resolved_ts, read_plane=self.read_plane,
            )
            # the standalone store's wiring: the server counts the reads on
            # their way to the scheduler, which stops lingering at zero
            inbound = InboundReads()
            copr.scheduler.watch_inbound(inbound)
        else:
            self.lock_manager = None
            self.service = KvService(storage=None, raft_router=self.store)
            inbound = None
        self.server = Server(self.service, security=security, inbound=inbound)
        self.running = False

    def start(self) -> None:
        self.server.start()
        self.cluster.addrs[self.store.store_id] = self.server.addr
        self.node.start(tick_interval=0.02, heartbeat_interval=0.2)
        if self.full_service and self.cluster.sched_continuous:
            # continuous coalescing lanes, the standalone default shape
            self.service.copr.scheduler.start()
        self.running = True

    def stop(self) -> None:
        self.running = False
        self.cluster.addrs.pop(self.store.store_id, None)
        if self.full_service:
            self.service.copr.scheduler.stop()
        self.node.stop()
        self.server.stop()
        self.transport.close()
        if self.read_plane is not None:
            self.read_plane.close()
        if self.lock_manager is not None:
            self.lock_manager.close()


class ServerCluster:
    def __init__(
        self,
        n_stores: int,
        pd: MockPd | None = None,
        engines: dict | None = None,
        security=None,
        full_service: bool = False,
        copr_kwargs: dict | None = None,
        overload_config=None,
        sched_continuous: bool = False,
    ):
        self.security = security
        # full_service endpoint assembly knobs: extra Endpoint kwargs (e.g.
        # enable_device / sched_config), an OverloadConfig for the per-node
        # OverloadControl, and whether to run the continuous scheduler
        # lanes — the standalone StoreServer shape for scenario tests
        self.copr_kwargs = copr_kwargs or {}
        self.overload_config = overload_config
        self.sched_continuous = sched_continuous
        self.pd = pd or MockPd()
        self.addrs: dict[int, tuple[str, int]] = {}
        self.nodes: dict[int, StoreNode] = {}
        self._ids = itertools.count(5000)
        self._engines = engines or {}
        # region -> leader store route cache, refreshed from NotLeader hints
        # (the client-go region-cache role): must_put/must_get consult it
        # before falling back to the wait_leader scan
        self._route: dict[int, int] = {}
        for sid in range(1, n_stores + 1):
            self.nodes[sid] = StoreNode(self, sid, engine=self._engines.get(sid),
                                        full_service=full_service)

    # -- addressing (resolve.rs: store id -> socket addr through PD) --------

    def resolve(self, store_id: int) -> tuple[str, int] | None:
        return self.addrs.get(store_id)

    def alloc_id(self) -> int:
        return self.pd.alloc_id()

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> None:
        for node in self.nodes.values():
            if not node.running:
                node.start()

    def bootstrap(self, store_ids: list[int] | None = None) -> Region:
        sids = store_ids or list(self.nodes)
        peers = [RegionPeer(self.alloc_id(), sid) for sid in sids]
        region = Region(FIRST_REGION_ID, b"", b"", RegionEpoch(), peers)
        self.pd.bootstrap_region(region.clone())
        for sid in sids:
            self.nodes[sid].store.create_peer(region)
        return region

    def run(self) -> None:
        """start + bootstrap + elect a first leader (Cluster::run)."""
        self.start()
        self.bootstrap()
        first = self.nodes[min(self.nodes)]
        first.store.peers[FIRST_REGION_ID].node.campaign()
        self.wait_leader(FIRST_REGION_ID)

    def shutdown(self) -> None:
        for node in self.nodes.values():
            if node.running:
                node.stop()

    def stop_node(self, store_id: int) -> None:
        self.nodes[store_id].stop()

    def restart_node(self, store_id: int) -> None:
        """Reboot a store over the SAME engine (state survives like a real
        restart over a durable engine; fsm/store.rs init recovers peers)."""
        old = self.nodes[store_id]
        assert not old.running, f"store {store_id} still running"
        node = StoreNode(self, store_id, engine=old.store.engine,
                         full_service=old.full_service)
        node.store.recover()
        self.nodes[store_id] = node
        node.start()

    # -- observation --------------------------------------------------------

    def leader_peer(self, region_id: int) -> StorePeer | None:
        leaders = []
        for node in self.nodes.values():
            if not node.running:
                continue
            p = node.store.peers.get(region_id)
            if p is not None and p.node.is_leader():
                leaders.append(p)
        if not leaders:
            return None
        return max(leaders, key=lambda p: p.node.term)

    def wait_leader(self, region_id: int, timeout: float = 10.0) -> StorePeer:
        return retry.wait_until(
            lambda: self.leader_peer(region_id), timeout,
            desc=f"leader for region {region_id}",
        )

    def wait_applied_on(self, store_id: int, region_id: int, index: int, timeout: float = 10.0) -> None:
        def applied():
            p = self.nodes[store_id].store.peers.get(region_id)
            return p is not None and p.node.applied >= index

        retry.wait_until(
            applied, timeout,
            desc=f"store {store_id} region {region_id} applied index {index}",
        )

    def get_on_store(self, store_id: int, key: bytes, cf: str = CF_DEFAULT) -> bytes | None:
        return self.nodes[store_id].store.engine.get_cf(cf, keymod.data_key(key))

    def wait_get_on_store(self, store_id: int, key: bytes, value: bytes, timeout: float = 10.0) -> None:
        retry.wait_until(
            lambda: self.get_on_store(store_id, key) == value, timeout,
            desc=f"store {store_id} sees {key!r}={value!r}",
        )

    # -- KV (leader-routed, with NotLeader retry like a real client) --------

    def region_for_key(self, key: bytes) -> int:
        for node in self.nodes.values():
            if not node.running:
                continue
            p = node.store.region_for_key(key)
            if p is not None:
                return p.region.id
        raise KeyError(key)

    def _routed_leader(self, region_id: int, timeout: float = 2.0) -> StorePeer:
        """Leader lookup through the route cache: a cached NotLeader hint
        answers without the all-store wait_leader poll; a stale entry drops
        and falls back."""
        sid = self._route.get(region_id)
        if sid is not None:
            node = self.nodes.get(sid)
            if node is not None and node.running:
                p = node.store.peers.get(region_id)
                if p is not None and p.node.is_leader():
                    return p
            self._route.pop(region_id, None)
        p = self.wait_leader(region_id, timeout=timeout)
        self._route[region_id] = p.store.store_id
        return p

    def _note_not_leader(self, region_id: int, exc: Exception) -> None:
        """NotLeader hints refresh the route cache instead of forcing the
        next attempt back through wait_leader's full poll."""
        from ..raft.region import NotLeaderError

        if isinstance(exc, NotLeaderError) and exc.leader_store:
            self._route[region_id] = exc.leader_store
        else:
            self._route.pop(region_id, None)

    def must_put(self, key: bytes, value: bytes, cf: str = CF_DEFAULT, timeout: float = 10.0) -> None:
        """Leader-routed put with the shared retry policy: NotLeader/Epoch/
        Timeout re-route freely; AssertionError/KeyError (routing races, but
        also how a REAL bug would surface) ride the bounded suspect class."""
        def attempt():
            region_id = self.region_for_key(key)
            leader = self._routed_leader(region_id)
            kv = RaftKv(leader.store)
            wb = WriteBatch()
            wb.put_cf(cf, key, value)
            try:
                kv.write({"region_id": region_id}, wb)
            except Exception as e:  # noqa: BLE001 — hint + re-raise to retry
                self._note_not_leader(region_id, e)
                raise

        retry.call(attempt, policy=CLIENT_RETRY, timeout=timeout,
                   site="server_cluster.must_put")

    def must_get(self, key: bytes, cf: str = CF_DEFAULT, timeout: float = 10.0,
                 stale_fallback: bool = False,
                 max_staleness: int | None = None) -> bytes | None:
        """Leader-routed snapshot read.  ``stale_fallback=True`` opts into
        the degraded mode (docs/stale_reads.md): when no leader is
        reachable within the budget, serve from any replica at the freshest
        RegionReadProgress watermark — bounded by ``max_staleness``
        timestamp units behind the current TSO (unbounded when None)."""
        def attempt():
            region_id = self.region_for_key(key)
            leader = self._routed_leader(region_id)
            kv = RaftKv(leader.store)
            try:
                snap = kv.snapshot({"region_id": region_id})
            except Exception as e:  # noqa: BLE001
                self._note_not_leader(region_id, e)
                raise
            return snap.get_cf(cf, key)

        try:
            return retry.call(attempt, policy=CLIENT_RETRY, timeout=timeout,
                              site="server_cluster.must_get")
        except Exception:
            if not stale_fallback:
                raise
            return self.stale_get(key, cf=cf, max_staleness=max_staleness)

    def stale_get(self, key: bytes, cf: str = CF_DEFAULT,
                  read_ts: int | None = None,
                  max_staleness: int | None = None) -> bytes | None:
        """Follower stale read: serve off ANY replica whose
        RegionReadProgress admits ``read_ts`` (default: the freshest
        watermark any live replica publishes).  ``max_staleness`` bounds
        how far behind the current TSO that watermark may be."""
        region_id = self.region_for_key(key)
        nodes = [n for n in self.nodes.values()
                 if n.running and n.resolved_ts is not None]
        if not nodes:
            raise RuntimeError("stale reads need full_service store nodes")
        if read_ts is None:
            read_ts = max(n.resolved_ts.progress_of(region_id)[0] for n in nodes)
        if max_staleness is not None:
            now = self.pd.get_tso()
            if now - read_ts > max_staleness:
                raise RaftKv.DataNotReadyError(region_id, now - max_staleness,
                                                read_ts)
        last: Exception | None = None
        for node in nodes:
            kv = RaftKv(node.store, resolved_ts=node.resolved_ts)
            try:
                snap = kv.snapshot({"region_id": region_id,
                                    "stale_read": True, "read_ts": read_ts})
                return snap.get_cf(cf, key)
            except Exception as e:  # noqa: BLE001 — next replica may serve
                last = e
        raise last if last is not None else KeyError(key)

    def coprocessor_rows(self, store_id: int, dag, ranges, start_ts: int,
                         chunk: bool = False, context: dict | None = None,
                         timeout: float = 30.0) -> list[list]:
        """Socket coprocessor call against one store with per-request
        TypeChunk opt-in (docs/wire_path.md "Columnar chunk responses"):
        ``chunk=True`` asks for column-slab responses (``encode_type`` +
        ``data_parts`` on the wire) and decodes them against the sent plan;
        the datum path stays the default.  Returns decoded rows either way
        — value-identical across encodings by the differential contract."""
        from dataclasses import replace

        from ..copr import dag as dag_mod
        from ..copr.dag_wire import dag_to_wire
        from .server import Client

        if chunk and dag.encode_type != dag_mod.ENC_TYPE_CHUNK:
            dag = replace(dag, encode_type=dag_mod.ENC_TYPE_CHUNK)
        addr = self.addrs[store_id]
        client = Client(*addr)
        try:
            r = client.call("coprocessor", {
                "dag": dag_to_wire(dag),
                "ranges": [list(rng) for rng in ranges],
                "start_ts": start_ts,
                "context": dict(context or {}),
            }, timeout=timeout)
        finally:
            client.close()
        if isinstance(r, dict) and r.get("error"):
            raise RuntimeError(f"coprocessor failed: {r['error']}")
        return dag_mod.decode_wire_response(r, dag).iter_rows()

    def set_device_owners(self, owners: dict[int, int]) -> None:
        """Push a device-owner placement map (region -> store) into every
        full-service node's read plane — the deterministic test-harness
        stand-in for the standalone deployment's PD heartbeat advertisement
        (docs/wire_path.md)."""
        for node in self.nodes.values():
            if node.read_plane is not None:
                node.read_plane.set_device_owners(owners)

    def advance_resolved_ts(self) -> dict[int, dict[int, int]]:
        """One watermark advance round on every full-service store (the
        standalone deployment's background loop, driven explicitly so tests
        stay deterministic)."""
        out: dict[int, dict[int, int]] = {}
        for node in self.nodes.values():
            if node.running and node.resolved_ts is not None:
                out[node.store.store_id] = node.resolved_ts.advance_all()
        return out

    # -- admin --------------------------------------------------------------

    def _run_admin(self, leader: StorePeer, cmd: dict, timeout: float = 10.0) -> None:
        done = threading.Event()
        res: list = []

        def cb(r):
            res.append(r)
            done.set()

        leader.propose_cmd(cmd, cb)
        if not done.wait(timeout):
            raise TimeoutError(f"admin command on region {leader.region.id} timed out")
        if isinstance(res[0], Exception):
            raise res[0]

    def ingest_sst(self, region_id: int, payload: bytes, timeout: float = 30.0) -> None:
        """Propose a raft ingest_sst admin command: the staged entries ride
        the log entry, so every replica (and any catching-up one) applies
        them (fsm/apply.rs exec_ingest_sst shape).  Retries leadership
        churn the way a real import client does (must_put discipline)."""
        def attempt():
            leader = self.wait_leader(region_id)
            cmd = {
                "epoch": (leader.region.epoch.conf_ver, leader.region.epoch.version),
                "admin": ("ingest_sst", payload),
            }
            try:
                self._run_admin(leader, cmd, timeout=2.0)
            except KeyError as e:
                # payload outside the region range: re-route the policy's
                # default suspect classification to permanent — retrying a
                # malformed import can never land it
                e.retry_class = "permanent"
                raise

        retry.call(attempt, policy=CLIENT_RETRY, timeout=timeout,
                   site="server_cluster.ingest_sst")

    def split_region(self, region_id: int, split_key: bytes) -> int:
        leader = self.wait_leader(region_id)
        new_region_id = self.alloc_id()
        new_pids = [self.alloc_id() for _ in leader.region.peers]
        done = threading.Event()
        res: list = []

        def cb(r):
            res.append(r)
            done.set()

        leader.propose_split(split_key, new_region_id, new_pids, cb)
        if not done.wait(10.0):
            raise TimeoutError("split timed out")
        if isinstance(res[0], Exception):
            raise res[0]
        self.wait_leader(new_region_id)
        return new_region_id

    def add_peer(self, region_id: int, store_id: int) -> int:
        leader = self.wait_leader(region_id)
        new_pid = self.alloc_id()
        cmd = {
            "epoch": (leader.region.epoch.conf_ver, leader.region.epoch.version),
            "ops": [],
            "admin": ("conf_change", "add", new_pid, store_id),
        }
        self._run_admin(leader, cmd)
        return new_pid

    def remove_peer(self, region_id: int, peer_id: int) -> None:
        leader = self.wait_leader(region_id)
        cmd = {
            "epoch": (leader.region.epoch.conf_ver, leader.region.epoch.version),
            "ops": [],
            "admin": ("conf_change", "remove", peer_id, 0),
        }
        self._run_admin(leader, cmd)

    def transfer_leader(self, region_id: int, to_store: int, timeout: float = 10.0) -> None:
        """Prefer the proper leader-side transfer (TIMEOUT_NOW once the
        target's log is caught up); fall back to target-side campaigns only
        at a slow cadence — a 0.1s campaign loop bumps terms faster than a
        loaded cluster can replicate, livelocking the very catch-up the
        election needs."""
        peer = self.nodes[to_store].store.peers[region_id]
        pacing = {"ordered_at": 0.0,   # last ACCEPTED leader-side order
                  "forced_at": 0.0}    # last target-side forced campaign

        def step() -> bool:
            if peer.node.is_leader():
                return True
            now = time.monotonic()
            cur = self.leader_peer(region_id)
            ordered = False
            if (cur is not None and cur.store.store_id != to_store
                    and now - pacing["ordered_at"] > 1.0):
                # leader-side order at most 1/s: TIMEOUT_NOW re-sent every
                # loop tick would force-campaign (and term-bump) the target
                # per delayed delivery, churning the very election it runs
                ordered = cur.transfer_leader_to(peer.peer_id)
                if ordered:
                    pacing["ordered_at"] = now
            if not ordered and now - max(pacing.values()) > 1.0:
                # the polite path is refused (learner target, or match never
                # equals last_index under a concurrent writer) or there is
                # no leader: fall back to the forced campaign — at a slow
                # cadence so replication can still outrun the term bumps
                peer.node.campaign()
                pacing["forced_at"] = now
            return False

        retry.wait_until(step, timeout, interval=0.05,
                         desc=f"store {to_store} takes region {region_id}")
