"""Standalone store process: the ``run_tikv`` assembly entrypoint.

Re-expression of ``components/server/src/server.rs:105`` (run_tikv) +
``cmd/tikv-server/src/main.rs``: one OS process = one store.  Connects to PD
over TCP, opens (or recovers) the durable native engine, assembles
transport -> raftstore -> RaftKv -> Storage -> coprocessor -> KvService,
registers its address with PD, bootstraps region 1 if the cluster is virgin,
and serves until signalled.

Run:  python -m tikv_tpu.server.standalone \
          --store-id 1 --pd 127.0.0.1:2379 --dir /data/store1 --expect-stores 3
"""

from __future__ import annotations

import argparse
import faulthandler
import os
import sys
import threading
import time

from ..copr.endpoint import Endpoint
from ..pd.service import RemotePd
from ..raft.raftkv import RaftKv
from ..raft.region import Peer as RegionPeer, Region, RegionEpoch
from ..storage.storage import Storage
from ..util.inbound import InboundReads
from .debug import Debugger
from .node import FIRST_REGION_ID, Node
from .raft_client import RemoteTransport
from .server import Server
from .service import KvService


def init_device_backend() -> list:
    """Bring the JAX backend up and say what it found.  A store asked to
    serve on the device does not start without one: whatever backend
    initialisation raises reaches the caller.  ``JAX_PLATFORMS=cpu`` in the
    caller's environment is the explicit way to run on the CPU."""
    import jax

    devices = jax.devices()
    d = devices[0]
    print(f"[standalone] device backend: platform={d.platform} "
          f"kind={d.device_kind} count={len(devices)}", file=sys.stderr,
          flush=True)
    return devices


def _default_mesh(devices: list):
    """A (regions × groups) mesh over every visible device when more than one
    is present — the serving path scaled out over the host's chips.  A single
    device serves single-device."""
    n = len(devices)
    if n <= 1:
        return None
    from ..parallel.mesh import make_mesh

    return make_mesh(devices, groups=2 if n % 2 == 0 else 1)


def open_engine(path: str | None, keys_mgr=None):
    if path is None:
        from ..storage.btree_engine import BTreeEngine

        return BTreeEngine()
    from ..native.engine import NativeEngine, native_available

    if not native_available():
        raise RuntimeError("native engine unavailable; cannot open a durable store")
    return NativeEngine(path=path, keys_mgr=keys_mgr)


def open_raft_log(data_dir: str | None, enable: bool = True, keys_mgr=None):
    """The raft_log_engine selection (components/server/src/server.rs:153-157):
    durable stores get the purpose-built segmented log by default; in-memory
    test stores keep the log in CF_RAFT."""
    if data_dir is None or not enable:
        return None
    import os

    from ..native.raftlog import NativeRaftLog, raftlog_available

    if not raftlog_available():
        return None
    return NativeRaftLog(os.path.join(data_dir, "raftlog"), keys_mgr=keys_mgr)


class StoreServer:
    """The assembled store (TiKVServer, components/server/src/server.rs:168)."""

    def __init__(
        self,
        store_id: int,
        pd: RemotePd,
        data_dir: str | None = None,
        host: str = "127.0.0.1",
        port: int = 0,
        enable_device: bool = False,
        security=None,
        raft_engine: bool = True,
        encryption_master_key: str | None = None,
        sched_continuous: bool = True,
        shard_cache: bool = True,
        group_commit: bool = True,
        write_through: bool = True,
        encode_columns: bool = True,
        integrity_scrub_interval: float = 10.0,
        shadow_sample: int | None = None,
        overload: bool = False,
        overload_rps: float = 0.0,
        overload_read_bps: float = 0.0,
        overload_max_priority: str = "high",
        cost_router: bool = True,
    ):
        # first, before anything is opened: no backend, no store
        self.devices = init_device_backend() if enable_device else []
        self.pd = pd
        self.security = security
        self._peer_clients: dict[int, object] = {}
        from ..pd.feature_gate import FeatureGate

        # gate follows PD's cluster version (rolling-upgrade safety); synced
        # from the heartbeat loop below
        self.feature_gate = FeatureGate()
        # encryption at rest (manager/mod.rs:398): ONE DataKeyManager per
        # store seals the key dictionary under the master key; the raw data
        # keys feed both native engines' file IO and the importer's staged
        # files.  Every persistent byte the store writes is then encrypted.
        self.keys_mgr = None
        if encryption_master_key is not None:
            if data_dir is None:
                raise ValueError("encryption at rest requires a durable --dir")
            from ..storage.encryption import DataKeyManager, MasterKey

            os.makedirs(data_dir, exist_ok=True)
            self.keys_mgr = DataKeyManager.open(
                MasterKey.from_file(encryption_master_key),
                os.path.join(data_dir, "keys.dict"),
            )
        self.engine = open_engine(data_dir, keys_mgr=self.keys_mgr)
        if hasattr(self.engine, "start_auto_compaction"):
            # background version GC (rocksdb's compaction threads)
            self.engine.start_auto_compaction(interval_s=30.0)
        self.raft_log = open_raft_log(data_dir, enable=raft_engine,
                                      keys_mgr=self.keys_mgr)
        self.transport = RemoteTransport(self._resolve, security=security)
        self.node = Node(pd, self.transport, store_id=store_id, engine=self.engine,
                         raft_log=self.raft_log)
        if self.raft_log is not None and hasattr(self.engine, "set_sync"):
            # the raft log is the durable source of truth: apply writes run
            # buffered, flushed before log purge (reference sync-log split)
            self.engine.set_sync(False)
            self.node.store.kv_buffered = True
        self.store = self.node.store
        recovered = self.store.recover()
        from ..sidecar.resolved_ts import ResolvedTsEndpoint
        from .diagnostics import Diagnostics
        from .gc_worker import GcWorker
        from .lock_manager import DetectorHandle, WaiterManager

        self.resolved_ts = ResolvedTsEndpoint(
            pd, store_id=store_id, check_leader_send=self._check_leader_send,
            feature_gate=self.feature_gate,
        )
        self.resolved_ts.attach_store(self.store)
        self.raftkv = RaftKv(self.store, resolved_ts=self.resolved_ts)
        # the read-degradation ladder (docs/stale_reads.md): reads for
        # regions this store does not lead forward one hop to the leader,
        # degrade to follower stale serving when it is unreachable, or
        # refuse with leader + safe_ts hints
        from .read_plane import ReadPlane

        self.read_plane = ReadPlane(
            store=self.store, resolved_ts=self.resolved_ts,
            resolver=self._resolve, security=security,
        )
        # group commit (docs/write_path.md): queued compatible prewrites /
        # commits coalesce into one raft proposal; --no-group-commit reverts
        # to one proposal per command
        self.storage = Storage(engine=self.raftkv,
                               group_commit_max=16 if group_commit else 1)
        mesh = _default_mesh(self.devices) if enable_device else None
        # cost-based path routing (docs/cost_router.md): --no-cost-router
        # forces the kill switch regardless of TIKV_TPU_COST_ROUTER
        from ..copr.costmodel import CostRouter, GeometryTuner

        self.copr = Endpoint(
            self.raftkv, enable_device=enable_device,
            mesh=mesh,
            feature_gate=self.feature_gate,
            shard_cache=shard_cache,
            write_through=write_through,
            encode_columns=encode_columns,
            shadow_sample=shadow_sample,
            cost_router=(CostRouter() if cost_router
                         else CostRouter(enabled=False)),
        )
        # overload control plane (docs/robustness.md "Overload"): always
        # CONSTRUCTED — so POST /config overload.enabled=true turns it on
        # at runtime — but disabled unless the operator opted in.  Quota
        # defaults come from the CLI/config; per-tenant overrides land via
        # OverloadControl.set_quota.
        from ..copr.overload import (
            OverloadConfig as _OvConfig, OverloadControl, TenantQuota,
        )

        self.overload = OverloadControl(
            _OvConfig(
                enabled=overload,
                default_quota=TenantQuota(
                    requests_per_s=overload_rps,
                    read_bytes_per_s=overload_read_bps,
                ),
                max_priority=overload_max_priority,
            ),
            region_cache=self.copr.region_cache,
        )
        self.copr.overload = self.overload
        # integrity plane (docs/integrity.md): the SDC scrubber verifies
        # warm images against the engine on a cadence; <=0 disables.
        # Shadow-read sampling is always on at its configured rate.
        if self.copr.scrubber is not None and integrity_scrub_interval > 0:
            self.copr.scrubber.start(integrity_scrub_interval)
        if mesh is not None and getattr(mesh, "size", 1) > 1:
            rc = self.copr.region_cache
            mode = ("sharded warm cache"
                    if rc is not None and getattr(rc, "sharded", False)
                    else "single-device warm cache")
            print(f"[standalone] serving mesh {dict(mesh.shape)} ({mode})",
                  file=sys.stderr)
        if sched_continuous:
            # continuous cross-region batching — ON BY DEFAULT since the
            # wire-path PR: unary coprocessor requests from concurrent
            # connections coalesce in the read scheduler's priority lanes
            # (service.coprocessor routes through it); same-plan-signature
            # requests across regions ride one vmapped device program and
            # identical requests share a slot (docs/wire_path.md)
            self.copr.scheduler.start()
        # one count of the reads between the socket and the scheduler's
        # lanes, kept by the server and read by the scheduler: a partial
        # batch leaves as soon as nobody else can join it
        self.inbound = InboundReads()
        self.copr.scheduler.watch_inbound(self.inbound)
        self.gc_worker = GcWorker(self.raftkv)
        # wait-for edges route to the cluster detector leader (region 1's
        # leader store); cross-store lock cycles break by error, not timeout
        self.lock_manager = WaiterManager(
            detector=DetectorHandle(self.store, self._resolve, security=security)
        )
        # store-wide memory attribution (tikv_util memory.rs MemoryTrace +
        # the server's memory-usage high-water): engine memtables, raft log
        # segments and CDC sink buffers report in; crossing the high-water
        # flushes the memtable — shedding instead of growing
        from ..sidecar.cdc import CdcService
        from ..util.memory import StoreMemoryTrace

        self.memory_trace = StoreMemoryTrace(f"store-{store_id}")
        if hasattr(self.engine, "mem_bytes"):
            self.memory_trace.child("engine_memtables", provider=self.engine.mem_bytes)
        if hasattr(self.engine, "wal_bytes"):
            self.memory_trace.child("engine_wal", provider=self.engine.wal_bytes)
        if self.raft_log is not None:
            self.memory_trace.child(
                "raft_log", provider=lambda: self.raft_log.stats()["active_size"]
            )
        self.cdc = CdcService(self.store, memory_trace=self.memory_trace)
        if hasattr(self.engine, "flush"):
            self.memory_trace.set_high_water(
                int(os.environ.get("TIKV_TPU_MEMORY_HIGH_WATER", str(4 << 30))),
                lambda total: self.engine.flush(),
            )
        # provider-backed trace nodes grow without add() calls: the heartbeat
        # re-evaluates the high-water condition, and reaps CDC subscriptions
        # whose client vanished (their buffers pin the shared quota)
        self.node.heartbeat_hooks.append(self.memory_trace.poll)

        def _sync_cluster_version():
            try:
                self.feature_gate.set_version(self.pd.get_cluster_version())
            except Exception:  # noqa: BLE001 — PD briefly unreachable
                pass

        _sync_cluster_version()
        self.node.heartbeat_hooks.append(_sync_cluster_version)
        # device-owner placement (docs/wire_path.md): advertise this store's
        # warm region images to PD each heartbeat and refresh the read
        # plane's owner route map from the response — the forwarding tier's
        # view of where every region's device image lives
        self.node.heartbeat_hooks.append(self._advertise_device_placement)
        self.node.heartbeat_hooks.append(lambda: self.cdc.reap_idle())
        from ..util.metrics import REGISTRY

        _mem_gauge = REGISTRY.gauge(
            "tikv_memory_usage_bytes", "Store memory-trace total")
        self.node.heartbeat_hooks.append(
            lambda: _mem_gauge.set(self.memory_trace.sum()))
        # engine internals for the operator dashboards (metrics/grafana/
        # tikv_tpu_engine.json): WAL size, memtable size, run counts per CF,
        # and the native perf counters (flushes, merges, block reads, bloom
        # skips) published as monotonic gauges each heartbeat
        self.node.heartbeat_hooks.append(self._publish_engine_metrics)
        # raw-KV TTL reclamation (ttl_checker.rs): a slow-cadence sweep of
        # expired raw entries through the replicated delete path, on its OWN
        # worker thread (the GcWorker AutoGc shape) — a large expired
        # backlog's raft round-trips must never stall the PD heartbeat loop
        from .ttl import TtlChecker

        self.ttl_checker = TtlChecker(self.storage)
        self._ttl_stop = threading.Event()
        # whether start() asked for the interpreter probe (stop lets go)
        self._probing = False

        def _ttl_loop(interval=float(os.environ.get("TIKV_TPU_TTL_SWEEP_SECS", "60"))):
            while not self._ttl_stop.wait(interval):
                for peer in list(self.store.peers.values()):
                    if self._ttl_stop.is_set():
                        return
                    if peer.node.is_leader():
                        try:
                            self.ttl_checker.sweep({"region_id": peer.region.id})
                        except Exception:  # noqa: BLE001 — next sweep retries
                            pass

        self._ttl_thread = threading.Thread(target=_ttl_loop, daemon=True,
                                            name="ttl-checker")
        # resolved-ts advance loop (endpoint.rs:247 advance-ts-interval):
        # periodic watermark advance with check_leader fan-out — what keeps
        # follower stale reads moving in the multi-process deployment
        self._rts_stop = threading.Event()

        def _rts_loop(interval=float(os.environ.get(
                "TIKV_TPU_RESOLVED_TS_INTERVAL", "1.0"))):
            while not self._rts_stop.wait(interval):
                try:
                    self.resolved_ts.advance_all()
                except Exception:  # noqa: BLE001 — next tick retries
                    pass

        self._rts_thread = threading.Thread(target=_rts_loop, daemon=True,
                                            name="resolved-ts-advance")
        # operator HTTP surface (status_server/mod.rs): /metrics, /status,
        # /debug/pprof/*, /debug/memory (the attribution tree above)
        from .status_server import StatusServer

        from ..util import trace
        from ..util.config import (
            ConfigController, CoprocessorConfig, OverloadSection, TikvConfig,
            TraceConfig,
        )

        self.config_controller = ConfigController(
            TikvConfig(
                coprocessor=CoprocessorConfig(enable_device=enable_device),
                # reflect the live tracer (env-seeded) so /config reads true
                trace=TraceConfig(sample_rate=trace.sample_rate(),
                                  slow_threshold_s=trace.slow_threshold()),
                overload=OverloadSection(
                    enabled=overload, requests_per_s=overload_rps,
                    read_bytes_per_s=overload_read_bps,
                    max_priority=overload_max_priority),
            )
        )
        # online overload knobs (docs/robustness.md "Overload"): POST
        # /config {"overload.enabled": true, "overload.requests_per_s": N}
        # — quota rates retune live, admission flips on/off at runtime
        self.config_controller.register(
            "overload", self.overload.reconfigure)
        # online coprocessor knobs: POST /config {"coprocessor.enable_device":
        # x, "coprocessor.block_rows": n, "coprocessor.max_wait_s": s} —
        # device toggle, block geometry (drops evaluators + warm images so
        # the next serve rebuilds at the new size), and the scheduler's
        # per-lane linger windows (docs/cost_router.md)

        def _copr_changed(changed: dict) -> None:
            if "enable_device" in changed:
                self.copr.set_enable_device(changed["enable_device"])
            if "block_rows" in changed:
                self.copr.set_block_rows(changed["block_rows"])
            waits = {k: v for k, v in changed.items()
                     if k in ("max_wait_s", "high_max_wait_s",
                              "low_max_wait_s")}
            if waits:
                self.copr.scheduler.reconfigure(waits)

        self.config_controller.register("coprocessor", _copr_changed)
        # geometry auto-tuner (docs/cost_router.md): hill-climbs block_rows
        # and the normal-lane linger from measured throughput — ONE change
        # in flight, applied through the SAME validated POST /config path
        # operators use, auto-reverted on a throughput floor regression
        tuner = GeometryTuner(enabled=self.copr.cost_router.enabled
                              and enable_device)
        tuner.register(
            "coprocessor.block_rows",
            lambda: self.config_controller.config.coprocessor.block_rows,
            lambda v: self.config_controller.update(
                {"coprocessor.block_rows": int(v)}),
            1 << 8, 1 << 20, integer=True)
        tuner.register(
            "coprocessor.max_wait_s",
            lambda: self.config_controller.config.coprocessor.max_wait_s,
            lambda v: self.config_controller.update(
                {"coprocessor.max_wait_s": float(v)}),
            0.0005, 0.05)
        self.copr.geometry_tuner = tuner
        self._tuner_stop = threading.Event()

        def _tuner_loop(interval=float(os.environ.get(
                "TIKV_TPU_TUNER_INTERVAL", "30"))):
            while not self._tuner_stop.wait(interval):
                try:
                    self.copr.geometry_tuner.tick()
                except Exception:  # noqa: BLE001 — next tick retries
                    pass

        self._tuner_thread = threading.Thread(target=_tuner_loop, daemon=True,
                                              name="geometry-tuner")
        # online tracing knobs (docs/tracing.md): POST /config
        # {"trace.sample_rate": r} — the ctl.py `trace set-sample-rate` path

        def _trace_changed(changed: dict) -> None:
            if "sample_rate" in changed:
                trace.set_sample_rate(changed["sample_rate"])
            if "slow_threshold_s" in changed:
                trace.set_slow_threshold(changed["slow_threshold_s"])

        self.config_controller.register("trace", _trace_changed)
        self.status_server = StatusServer(
            controller=self.config_controller,
            security=security, memory_trace=self.memory_trace,
            # stuck-follower debugging: per-region (resolved_ts,
            # required_apply_index) + the store safe_ts floor over HTTP
            read_progress=lambda: self.service.debug_read_progress({}),
            # derived-plane integrity: fingerprints, quarantine ledger,
            # scrubber + shadow-read state (docs/integrity.md)
            integrity=lambda: self.service.debug_integrity({}),
            # overload control plane: per-tenant buckets, controller scale,
            # HBM partition occupancy (docs/robustness.md "Overload")
            overload=lambda: self.service.debug_overload({}),
            # cost-router decisions + geometry tuner state
            # (docs/cost_router.md)
            cost_router=lambda: self.service.debug_cost_router({}),
        )
        self.service = KvService(
            self.storage,
            self.copr,
            debugger=Debugger(self.engine, raft_log=self.raft_log),
            pd=pd,
            raft_router=self.store,
            gc_worker=self.gc_worker,
            lock_manager=self.lock_manager,
            resolved_ts=self.resolved_ts,
            diagnostics=Diagnostics(),
            cdc=self.cdc,
            keys_rotator=self.rotate_data_keys if self.keys_mgr is not None else None,
            read_plane=self.read_plane,
            overload=self.overload,
        )
        self.server = Server(self.service, host=host, port=port, security=security,
                             inbound=self.inbound)
        self.recovered_peers = recovered

    def _advertise_device_placement(self) -> None:
        rc = self.copr.region_cache
        regions: list[int] = []
        if rc is not None and self.copr.device_enabled():
            regions = rc.warm_region_ids()
        try:
            owners = self.pd.advertise_device_regions(
                self.store.store_id, regions)
        except Exception:  # noqa: BLE001 — PD briefly unreachable
            return
        if isinstance(owners, dict):
            self.read_plane.set_device_owners(owners)

    def _publish_engine_metrics(self) -> None:
        from ..util.metrics import REGISTRY

        eng = self.engine
        if hasattr(eng, "wal_bytes"):
            REGISTRY.gauge(
                "tikv_engine_wal_bytes", "Live WAL segment bytes"
            ).set(eng.wal_bytes())
        if hasattr(eng, "mem_bytes"):
            REGISTRY.gauge(
                "tikv_engine_memtable_bytes", "Memtable resident bytes"
            ).set(eng.mem_bytes())
        if hasattr(eng, "run_count"):
            g = REGISTRY.gauge("tikv_engine_run_count", "Sorted runs per CF")
            for cf in ("default", "write", "lock", "raft"):
                try:
                    g.set(eng.run_count(cf), cf=cf)
                except (ValueError, OSError):
                    pass
        if hasattr(eng, "perf_context"):
            g = REGISTRY.gauge(
                "tikv_engine_perf_events",
                "Native engine perf counters (monotonic; rate() in panels)",
            )
            for k, v in eng.perf_context().items():
                g.set(v, event=k)

    def _check_leader_send(self, store_id: int, payload: dict):
        """One check_leader RPC to a peer store (short timeout: a dead peer
        simply contributes no vote this round)."""
        addr = self._resolve(store_id)
        if addr is None:
            return None
        cl = self._peer_clients.get(store_id)
        try:
            if cl is None:
                from .server import Client

                cl = Client(addr[0], addr[1], security=self.security)
                self._peer_clients[store_id] = cl
            return cl.call("raft_check_leader", payload, timeout=2.0)
        except (OSError, ConnectionError, TimeoutError, RuntimeError):
            self._peer_clients.pop(store_id, None)
            try:
                if cl is not None:
                    cl.close()
            except OSError:
                pass
            return None

    def rotate_data_keys(self) -> dict:
        """Mint ONE new data key and refresh every native engine's registry:
        files written from now on use it, existing files keep their sidecar
        key (debug_rotate_data_key RPC surface)."""
        new_id = self.keys_mgr.rotate()
        self.engine.refresh_encryption()
        if self.raft_log is not None:
            self.raft_log.refresh_encryption()
        return {"key_id": new_id}

    def _resolve(self, store_id: int):
        try:
            return self.pd.get_store_addr(store_id)
        except Exception:  # noqa: BLE001 — PD briefly unreachable
            return None

    def start(self) -> None:
        from ..util import trace

        # one interpreter serves every thread of the store: its collector's
        # pauses are timed from here on (docs/tracing.md, stage host.gc)
        trace.install_gc_hook()
        # and its waits for the OS and for the interpreter's lock, with a
        # slow-log line for every stall of the process (docs/tracing.md,
        # "The interpreter probe")
        trace.install_interpreter_probe(self.copr.slow_log)
        self._probing = True
        # the store runs native engines in this interpreter: a crash inside
        # one leaves every thread's Python frames on stderr, whoever started
        # the process (docs/tracing.md)
        if not faulthandler.is_enabled():
            faulthandler.enable()
        self.server.start()
        self.status_server.start()
        self._ttl_thread.start()
        self._rts_thread.start()
        self._tuner_thread.start()
        self.pd.put_store(self.store.store_id, addr=self.server.addr)
        self.node.start()

    def bootstrap_or_join(self, expect_stores: int, timeout: float = 30.0) -> None:
        """Cluster formation (node.rs:153 try_bootstrap): wait until
        ``expect_stores`` stores registered; the lowest id bootstraps region
        1 spanning all of them; everyone creates local peers placed here.
        A recovered store skips formation — its peers came off disk."""
        if self.recovered_peers:
            return
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            region = self.pd.get_region_by_id(FIRST_REGION_ID)
            if region is not None:
                me = region.peer_on_store(self.store.store_id)
                if me is not None and region.id not in self.store.peers:
                    self.store.create_peer(region)
                    if self.store.store_id == min(p.store_id for p in region.peers):
                        self.store.peers[region.id].node.campaign()
                return
            stores = sorted(self.pd.alive_stores())
            if len(stores) >= expect_stores:
                if self.store.store_id == stores[0]:
                    peers = [RegionPeer(self.pd.alloc_id(), sid) for sid in stores[:expect_stores]]
                    region = Region(FIRST_REGION_ID, b"", b"", RegionEpoch(), peers)
                    self.pd.bootstrap_region(region)
                    continue  # next loop iteration takes the join path
            time.sleep(0.1)
        raise TimeoutError("cluster never formed")

    def stop(self) -> None:
        """Stop the store's threads, then close its engines.  A thread that
        outlives its join is given up on and counted by name
        (``tikv_server_stop_abandoned_thread_total``); whatever it still
        asks of a native engine after the close below is refused with
        ``EngineClosed`` (native/guard.h), and what it had in flight at the
        close finishes first."""
        from ..util.metrics import REGISTRY

        abandoned = REGISTRY.counter(
            "tikv_server_stop_abandoned_thread_total",
            "Threads StoreServer.stop() gave up joining, by thread name",
        )
        if self._probing:
            from ..util import trace

            trace.release_interpreter_probe(self.copr.slow_log)
            self._probing = False
        if self.copr.scrubber is not None and not self.copr.scrubber.stop():
            abandoned.inc(thread="integrity-scrub")
        if not self.copr.scheduler.stop():
            abandoned.inc(thread="copr-sched")
        self._ttl_stop.set()
        self._rts_stop.set()
        self._tuner_stop.set()
        # the advance thread inserts into _peer_clients: join it BEFORE
        # closing/iterating the clients
        for t in (self._rts_thread, self._ttl_thread, self._tuner_thread):
            if t.is_alive():
                t.join(timeout=10.0)
                if t.is_alive():
                    abandoned.inc(thread=t.name)
        for cl in list(self._peer_clients.values()):
            try:
                cl.close()
            except OSError:
                pass
        self.read_plane.close()
        for t in self.node.stop():
            abandoned.inc(thread=t.name)
        self.server.stop()
        self.status_server.stop()
        self.transport.close()
        self.lock_manager.close()
        close = getattr(self.engine, "close", None)
        if close is not None:
            close()
        if self.raft_log is not None:
            self.raft_log.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="tikv_tpu store server")
    ap.add_argument("--store-id", type=int, required=True)
    ap.add_argument("--pd", required=True, help="host:port of the PD service")
    ap.add_argument("--dir", default=None, help="durable engine directory")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--expect-stores", type=int, default=1)
    ap.add_argument("--enable-device", action="store_true")
    ap.add_argument("--sched-continuous", action="store_true",
                    help="deprecated no-op: continuous coalescing is the "
                         "default (see --no-sched-continuous)")
    ap.add_argument("--no-sched-continuous", action="store_true",
                    help="serve unary coprocessor requests per-request "
                         "instead of coalescing them across connections in "
                         "the read scheduler's priority lanes")
    ap.add_argument("--no-shard-cache", action="store_true",
                    help="keep the region column cache single-device even "
                         "with a multi-chip mesh (sharded warm serving off)")
    ap.add_argument("--no-group-commit", action="store_true",
                    help="one raft proposal per txn command instead of "
                         "coalescing queued prewrites/commits (write_path.md)")
    ap.add_argument("--no-column-encoding", action="store_true",
                    help="keep region images device-resident DECODED "
                         "(docs/compressed_columns.md kill switch; budgets "
                         "then account decoded bytes)")
    ap.add_argument("--no-write-through", action="store_true",
                    help="disable raft-apply delta emission into the region "
                         "column cache (warm reads repair via scan_delta)")
    ap.add_argument("--integrity-scrub-interval", type=float, default=10.0,
                    help="seconds between SDC scrubber rounds over warm "
                         "region images (docs/integrity.md); <=0 disables")
    ap.add_argument("--overload", action="store_true",
                    help="enable the overload control plane: per-tenant "
                         "quota admission, priority clamping, adaptive "
                         "shedding (docs/robustness.md)")
    ap.add_argument("--overload-rps", type=float, default=0.0,
                    help="default-tenant requests/s quota (0 = unlimited)")
    ap.add_argument("--overload-read-bps", type=float, default=0.0,
                    help="default-tenant read-bytes/s quota (0 = unlimited)")
    ap.add_argument("--overload-max-priority", default="high",
                    choices=["high", "normal", "low"],
                    help="lane ceiling for client-declared priorities")
    ap.add_argument("--shadow-sample", type=int, default=None,
                    help="shadow-read 1-in-N sampling of warm device serves "
                         "(default 256 or TIKV_TPU_SHADOW_SAMPLE; 0 "
                         "disables, 1 verifies every warm serve)")
    ap.add_argument("--trace-sample", type=float, default=None,
                    help="distributed-tracing head sample rate in [0,1] "
                         "(default 0.01 or TIKV_TPU_TRACE_SAMPLE; 0 turns "
                         "the tracing plane off; docs/tracing.md)")
    ap.add_argument("--no-cost-router", action="store_true",
                    help="kill switch for cost-based path routing + the "
                         "geometry auto-tuner: serve with the static rule "
                         "ladder exactly (docs/cost_router.md; equivalent "
                         "to TIKV_TPU_COST_ROUTER=0)")
    ap.add_argument("--no-raft-engine", action="store_true",
                    help="keep the raft log in CF_RAFT instead of the segmented log engine")
    ap.add_argument("--ca-path", default="")
    ap.add_argument("--cert-path", default="")
    ap.add_argument("--key-path", default="")
    ap.add_argument("--redact-info-log", default="off", choices=["off", "on", "marker"])
    ap.add_argument("--encryption-master-key", default=None,
                    help="path to a 32-byte master key file: encrypt every "
                         "engine/raft-log file at rest (data keys sealed "
                         "under it in <dir>/keys.dict)")
    args = ap.parse_args(argv)

    from ..util import logger as slog
    from .security import SecurityConfig

    if args.trace_sample is not None:
        from ..util import trace as _trace

        _trace.set_sample_rate(args.trace_sample)
    slog.set_redact_info_log(args.redact_info_log)
    security = SecurityConfig(
        ca_path=args.ca_path, cert_path=args.cert_path, key_path=args.key_path
    )
    security.validate()
    if not security.enabled:
        security = None

    if args.enable_device:
        from ..util.compile_cache import place_compile_cache

        place_compile_cache()
    host, port = args.pd.rsplit(":", 1)
    pd = RemotePd(host, int(port), security=security)
    srv = StoreServer(
        args.store_id, pd, data_dir=args.dir,
        host=args.host, port=args.port, enable_device=args.enable_device,
        security=security, raft_engine=not args.no_raft_engine,
        encryption_master_key=args.encryption_master_key,
        sched_continuous=not args.no_sched_continuous,
        shard_cache=not args.no_shard_cache,
        group_commit=not args.no_group_commit,
        write_through=not args.no_write_through,
        encode_columns=not args.no_column_encoding,
        integrity_scrub_interval=args.integrity_scrub_interval,
        shadow_sample=args.shadow_sample,
        overload=args.overload,
        overload_rps=args.overload_rps,
        overload_read_bps=args.overload_read_bps,
        overload_max_priority=args.overload_max_priority,
        cost_router=not args.no_cost_router,
    )
    srv.start()
    srv.bootstrap_or_join(args.expect_stores)
    print(f"READY store={args.store_id} addr={srv.server.addr[0]}:{srv.server.addr[1]}", flush=True)
    try:
        while True:
            time.sleep(1)
    except KeyboardInterrupt:
        srv.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
