"""The KV service: the store's full external API surface.

Re-expression of the gRPC ``Tikv`` service (``src/server/service/kv.rs``; the
handler inventory is SURVEY.md Appendix A): transactional KV, raw KV, and
coprocessor, plus cluster-internal helpers.  Handlers take/return plain
wire-codable dicts so the same functions serve in-process calls and the TCP
server's ``batch_commands`` multiplexing.

Errors are returned as ``{"error": {...}}`` region errors / key errors the
way the reference maps storage errors into kvproto errors.
"""

from __future__ import annotations

import threading

from ..copr.endpoint import CoprRequest, Endpoint, REQ_TYPE_CHECKSUM, REQ_TYPE_DAG
from ..raft.region import EpochError, NotLeaderError
from ..storage.mvcc.reader import KeyIsLockedError, WriteConflictError
from ..storage.mvcc.txn import AlreadyExistsError, TxnError
from ..storage.storage import Storage
from ..storage.txn import commands as cmds
from ..storage.txn_types import Key, Mutation, MutationType
from ..util import trace


def _mutation_from_wire(m: dict) -> Mutation:
    op = MutationType(m["op"])
    return Mutation(op, Key.from_raw(m["key"]), m.get("value"))


def _rewrite_from_wire(req: dict) -> tuple[bytes, bytes] | None:
    if req.get("rewrite_old") is None:
        return None
    return (req["rewrite_old"], req["rewrite_new"])


def _err(e: Exception) -> dict:
    if isinstance(e, KeyIsLockedError):
        return {
            "locked": {
                "key": e.key,
                "primary": e.lock.primary,
                "lock_ts": e.lock.ts,
                "ttl": e.lock.ttl,
            }
        }
    if isinstance(e, WriteConflictError):
        return {
            "conflict": {
                "key": e.key,
                "start_ts": e.start_ts,
                "conflict_start_ts": e.conflict_start_ts,
                "conflict_commit_ts": e.conflict_commit_ts,
            }
        }
    if isinstance(e, AlreadyExistsError):
        return {"already_exists": {"key": e.key}}
    if isinstance(e, NotLeaderError):
        return {"not_leader": {"region_id": e.region_id, "leader_store": e.leader_store}}
    if isinstance(e, EpochError):
        return {"epoch_not_match": {}}
    if type(e).__name__ == "DataNotReadyError":
        # stale read above the replica's watermark (raftkv stale path): a
        # TYPED refusal — the carried ``resolved`` ts drives the client's
        # watermark-aware backoff (util.retry data_not_ready class) and the
        # read plane's refusal hints ride the same dict
        return {"data_not_ready": {
            "region_id": getattr(e, "region_id", None),
            "read_ts": getattr(e, "read_ts", None),
            "resolved": getattr(e, "resolved", None),
        }}
    retry_after = getattr(e, "retry_after_s", None)
    if retry_after is not None or type(e).__name__ in ("SchedTooBusy", "ServerBusyError"):
        # ServerIsBusy shape: the retry-after hint survives the wire so the
        # client-side retry policy can honor it (util.retry)
        busy = {}
        if retry_after is not None:
            busy["retry_after_ms"] = int(retry_after * 1000)
        return {"server_is_busy": busy}
    if type(e).__name__ == "DeadlineExceeded":
        return {"deadline_exceeded": {}}
    return {"other": str(e)}


class KvService:
    """All handlers of one store (kv.rs handler inventory)."""

    def __init__(
        self, storage: Storage, copr: Endpoint | None = None, copr_v2=None,
        resource_tags=None, debugger=None, cdc=None, pd=None, importer=None,
        raft_router=None, gc_worker=None, lock_manager=None, resolved_ts=None,
        diagnostics=None, keys_rotator=None, read_plane=None, overload=None,
    ):
        self.storage = storage
        self.copr = copr
        # overload control plane (docs/robustness.md "Overload"): per-tenant
        # quota admission on the read entries — over-quota work defers a
        # bounded wait then sheds as ServerIsBusy with a refill-deficit
        # retry_after hint.  None (the default) gates nothing.
        self.overload = overload if overload is not None \
            else getattr(copr, "overload", None)
        # the read-degradation ladder (server/read_plane.py): wraps the read
        # handlers so NotLeader/DataNotReady region errors forward one hop,
        # degrade to follower stale serving, or refuse with hints.  None
        # (embedded assemblies) keeps the old bounce-the-error behavior.
        self.read_plane = read_plane
        self.copr_v2 = copr_v2
        self.resource_tags = resource_tags
        self.debugger = debugger
        self.cdc = cdc
        self.pd = pd
        self.importer = importer
        self.gc_worker = gc_worker
        self.lock_manager = lock_manager
        self.resolved_ts = resolved_ts
        self.diagnostics = diagnostics
        self.keys_rotator = keys_rotator
        # peer raft ingress: the local Store messages are routed into
        # (service/kv.rs raft:612 / batch_raft:649 / snapshot:692).
        # The assembler is built eagerly: lazy init would race between
        # connection threads and orphan a concurrent transfer's first chunk.
        self.raft_router = raft_router
        from ..raft.net import SnapshotAssembler

        self._snap_assembler = SnapshotAssembler()
        # Per-instance: the 2-slot long-poll bound must not be shared across
        # stores in one process (a poller on one store would degrade
        # cdc_events long-polls on unrelated stores to immediate returns).
        self._cdc_longpoll_slots = threading.Semaphore(2)
        # wire-DAG parse memo: clients resend the same plan on every request
        # of a workload, and dag_from_wire + executor descriptor construction
        # was a fixed per-request tax on the wire path.  Keyed by the plan's
        # canonical wire bytes; DagRequests are treated as immutable by every
        # serving path (the streaming handler copies before re-framing).
        self._dag_memo: dict[bytes, object] = {}
        self._dag_memo_mu = threading.Lock()
        # device-eligibility verdicts for owner routing, keyed by the memoized
        # DagRequest object (id + identity check guards against reuse)
        self._dag_eligible_memo: dict[int, tuple] = {}

    _HANDLER_PREFIXES = (
        "kv_", "raw_", "coprocessor", "mvcc_", "debug_", "cdc_", "import_", "raft_",
        "backup", "diagnostics_",
    )
    # RPCs whose reference names carry no family prefix (kv.rs:358-1061)
    _EXTRA_HANDLERS = frozenset(
        {
            "register_lock_observer", "check_lock_observer", "remove_lock_observer",
            "physical_scan_lock", "unsafe_destroy_range", "get_store_safe_ts",
            "get_lock_wait_info", "deadlock_detect",
        }
    )

    # -- peer raft ingress (kv.rs raft/batch_raft/snapshot handlers) --------

    def _router(self):
        if self.raft_router is None:
            raise RuntimeError("peer raft service not enabled on this node")
        return self.raft_router

    def raft_message(self, req: dict) -> dict:
        """Single RaftMessage ingress (kv.rs:612)."""
        from ..raft import net as raft_net

        self._router().enqueue_message(raft_net.rmsg_from_wire(req["msg"]))
        return {}

    def raft_batch(self, req: dict) -> dict:
        """BatchRaftMessage ingress (kv.rs:649): the peer stream's one frame
        shape — every buffered message of a flush interval together."""
        from ..raft import net as raft_net

        router = self._router()
        for t in req["msgs"]:
            router.enqueue_message(raft_net.rmsg_from_wire(t))
        return {}

    def raft_snapshot_chunk(self, req: dict) -> dict:
        """Chunked snapshot stream ingress (kv.rs snapshot:692, snap.rs:260):
        chunks joined per transfer id; the completed snapshot message enters
        the store like any other raft message."""
        from ..raft import net as raft_net

        router = self._router()
        rmsg = self._snap_assembler.add_chunk(req)
        if rmsg is not None:
            router.enqueue_message(rmsg)
        return {}

    def raft_check_leader(self, req: dict) -> dict:
        """resolved-ts CheckLeader (advance.rs:211 service side): acknowledge
        matching (term, leader) claims and adopt disseminated watermarks."""
        if self.resolved_ts is None:
            return {"accepted": []}
        return self.resolved_ts.handle_check_leader(req)

    def debug_rotate_data_key(self, req: dict) -> dict:
        """Encryption-at-rest data-key rotation on a RUNNING store
        (manager/mod.rs rotation surface): new engine/raft-log files encrypt
        under the fresh key; nothing on disk is rewritten."""
        if self.keys_rotator is None:
            return {"error": {"other": "encryption at rest not enabled"}}
        try:
            return self.keys_rotator()
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def debug_consistency(self, req: dict) -> dict:
        """Consistency-check results (tikv-ctl consistency-check view):
        recorded region hashes and any detected divergences."""
        router = self._router()
        return {
            "hashes": {
                rid: {"index": idx, "hash": h}
                for rid, (idx, h) in list(router.consistency_hashes.items())
            },
            "inconsistent": dict(router.inconsistent_regions),
        }

    def debug_consistency_check(self, req: dict) -> dict:
        """Trigger a consistency-check round NOW (``ctl.py
        consistency-check --trigger``): propose compute_hash on every led
        region (or just ``region_id``).  The round completes asynchronously
        through raft apply; poll ``debug_consistency`` for results."""
        router = self._router()
        rid = req.get("region_id")
        scheduled = []
        for region_id, peer in list(router.peers.items()):
            if rid is not None and region_id != rid:
                continue
            if peer.node.is_leader():
                peer.schedule_consistency_check()
                scheduled.append(region_id)
        return {"scheduled": sorted(scheduled)}

    def debug_integrity(self, req: dict) -> dict:
        """Integrity-plane state (docs/integrity.md; ``ctl.py integrity``
        and the status server's ``/debug/integrity``): per-region image
        fingerprints + apply points, the quarantine ledger, scrubber
        cadence/progress, and shadow-read sample/mismatch counts."""
        if self.copr is None:
            return {"error": {"other": "coprocessor endpoint not wired"}}
        return self.copr.integrity_snapshot()

    # -- ImportSST service (sst_service.rs: download + ingest) --------------

    def _importer(self):
        if self.importer is None:
            raise RuntimeError("import service not enabled")
        return self.importer

    def import_download(self, req: dict) -> dict:
        try:
            return self._importer().download(req["name"], _rewrite_from_wire(req))
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def import_ingest(self, req: dict) -> dict:
        """Ingest a (downloaded) backup file as committed writes at
        restore_ts — through the raft propose path when the engine is a
        RaftKv, exactly like the reference's IngestSst command."""
        try:
            return self._importer().restore(
                self.storage.engine, req["name"], req["restore_ts"],
                req.get("context"), _rewrite_from_wire(req),
            )
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    # -- ChangeData service (cdcpb over the multiplexed transport) ----------

    def _cdc(self):
        if self.cdc is None:
            raise RuntimeError("cdc service not enabled")
        return self.cdc

    def cdc_register(self, req: dict) -> dict:
        return self._cdc().register(req["region_id"], req.get("checkpoint_ts", 0))

    def cdc_events(self, req: dict) -> dict:
        # timeout_ms: long-poll — block until events arrive or the deadline.
        # The wait parks a shared worker thread, so concurrent long-pollers
        # are bounded; excess pollers degrade to an immediate (empty) return
        # instead of starving every other RPC on the store
        timeout = min(int(req.get("timeout_ms", 0)), 10_000) / 1000.0
        if timeout > 0:
            if not self._cdc_longpoll_slots.acquire(blocking=False):
                timeout = 0.0
        try:
            return self._cdc().events(
                req["sub_id"], req.get("after_seq", 0), req.get("limit", 1024), timeout
            )
        finally:
            if timeout > 0:
                self._cdc_longpoll_slots.release()

    def cdc_deregister(self, req: dict) -> dict:
        return self._cdc().deregister(req["sub_id"])

    # -- Debug service (debug.rs over gRPC; read-only surface -- the
    # destructive commands like unsafe-recover are offline-only by design) --

    def _debug(self):
        if self.debugger is None:
            raise RuntimeError("debug service not enabled")
        return self.debugger

    def debug_region_info(self, req: dict) -> dict:
        info = self._debug().region_info(req["region_id"])
        return {"info": info} if info is not None else {"error": {"other": "region not found"}}

    def debug_region_properties(self, req: dict) -> dict:
        props = self._debug().region_properties(req["region_id"])
        return {"props": props} if props is not None else {"error": {"other": "region not found"}}

    def debug_bad_regions(self, req: dict) -> dict:
        return {"bad": self._debug().bad_regions()}

    def debug_all_regions(self, req: dict) -> dict:
        return {"regions": self._debug().all_regions()}

    def dispatch(self, method: str, req: dict):
        """Invoke a handler with resource-group attribution (the tagged-future
        wrapper from resource_metering/cpu/future_ext.rs).  Only methods with
        handler prefixes are reachable from the wire — attributes like
        ``storage`` can never be called remotely."""
        if not method.startswith(self._HANDLER_PREFIXES) and method not in self._EXTRA_HANDLERS:
            return {"error": {"other": f"unknown method {method}"}}
        handler = getattr(self, method, None)
        if handler is None:
            return {"error": {"other": f"unknown method {method}"}}
        tag = (req.get("context") or {}).get("resource_group", b"default")
        if self.resource_tags is not None:
            with self.resource_tags.attach(tag):
                return handler(req)
        return handler(req)

    def raw_coprocessor(self, req: dict) -> dict:
        """Coprocessor V2 plugin dispatch (kv.rs:330 raw_coprocessor)."""
        if self.copr_v2 is None:
            return {"error": {"other": "coprocessor v2 not enabled"}}
        return self.copr_v2.handle_request(req)

    # -- transactional KV ---------------------------------------------------

    def _serve_read(self, method: str, req: dict, local) -> dict:
        """Read-degradation ladder entry (docs/stale_reads.md): serve
        locally; a NotLeader/DataNotReady region error hands the response
        to the read plane, which forwards ONE hop to the leader (loop-
        guarded by the ``forwarded`` ctx flag), degrades to a follower
        stale read when the request permits, or returns the typed refusal
        carrying the leader hint + this store's ``safe_ts``.  With no read
        plane wired the behavior is exactly the pre-ladder one."""
        resp = local(req)
        if self.read_plane is None or not isinstance(resp, dict):
            return resp
        err = resp.get("error")
        if not isinstance(err, dict) or not ({"not_leader", "data_not_ready"} & err.keys()):
            return resp
        return self.read_plane.degrade(self, method, req, resp, local)

    def _admit_overload(self, req: dict, where: str) -> dict | None:
        """Per-tenant quota gate on a read entry: None = admitted (possibly
        after a bounded defer), else the typed ServerIsBusy error dict with
        ``retry_after_ms`` riding the wire (docs/robustness.md).

        This is the WIRE BOUNDARY: a client-supplied admission marker is
        stripped before admitting — `_overload_admitted` is an in-process
        nesting contract (service -> scheduler), never a client claim — and
        a missing context is materialized onto the request so the stamp
        reaches the nested layers (otherwise the scheduler would charge a
        second token against a fresh dict)."""
        ov = self.overload
        if ov is None:
            return None
        ctx = req.get("context")
        if not isinstance(ctx, dict):
            ctx = req["context"] = {}
        ctx.pop("_overload_admitted", None)
        try:
            ov.admit(ctx, where=where)
        except Exception as e:  # noqa: BLE001 — ServerBusyError, typed
            return {"error": _err(e)}
        return None

    def _note_read_bytes(self, req: dict, nbytes: int) -> None:
        """Post-serve read-byte charge against the tenant's byte bucket
        (response size is unknown at admission; the debt gates the
        tenant's NEXT admission)."""
        if self.overload is not None and nbytes:
            self.overload.note_bytes(req.get("context"), nbytes)

    def kv_get(self, req: dict) -> dict:
        busy = self._admit_overload(req, "kv")
        if busy is not None:
            return busy
        resp = self._serve_read("kv_get", req, self._kv_get_local)
        if isinstance(resp, dict) and resp.get("value"):
            self._note_read_bytes(req, len(resp["value"]))
        return resp

    def _kv_get_local(self, req: dict) -> dict:
        try:
            v = self.storage.get(
                req["key"], req["version"], req.get("context"),
                bypass_locks=frozenset(req.get("bypass_locks", ())),
            )
            return {"value": v, "not_found": v is None}
        except Exception as e:  # noqa: BLE001 — mapped to wire errors
            return {"error": _err(e)}

    def kv_batch_get(self, req: dict) -> dict:
        busy = self._admit_overload(req, "kv")
        if busy is not None:
            return busy
        resp = self._serve_read("kv_batch_get", req, self._kv_batch_get_local)
        if isinstance(resp, dict) and resp.get("pairs"):
            self._note_read_bytes(req, sum(
                len(p[1]) for p in resp["pairs"] if p and p[1]))
        return resp

    def _kv_batch_get_local(self, req: dict) -> dict:
        try:
            pairs = self.storage.batch_get(req["keys"], req["version"], req.get("context"))
            return {"pairs": [list(p) for p in pairs]}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_scan(self, req: dict) -> dict:
        busy = self._admit_overload(req, "kv")
        if busy is not None:
            return busy
        resp = self._serve_read("kv_scan", req, self._kv_scan_local)
        if isinstance(resp, dict) and resp.get("pairs"):
            self._note_read_bytes(req, sum(
                len(p[0]) + len(p[1]) for p in resp["pairs"] if p and p[1]))
        return resp

    def _kv_scan_local(self, req: dict) -> dict:
        try:
            pairs = self.storage.scan(
                req.get("start_key", b""),
                req.get("end_key"),
                req.get("limit"),
                req["version"],
                req.get("context"),
                reverse=req.get("reverse", False),
                key_only=req.get("key_only", False),
            )
            return {"pairs": [list(p) for p in pairs]}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_prewrite(self, req: dict) -> dict:
        cmd = cmds.Prewrite(
            [_mutation_from_wire(m) for m in req["mutations"]],
            req["primary_lock"],
            req["start_version"],
            lock_ttl=req.get("lock_ttl", 3000),
            use_async_commit=req.get("use_async_commit", False),
            secondaries=req.get("secondaries", []),
            is_pessimistic=req.get("is_pessimistic", False),
            pessimistic_flags=req.get("is_pessimistic_lock", []),
            for_update_ts=req.get("for_update_ts", 0),
        )
        try:
            r = self.storage.sched_txn_command(cmd, req.get("context"))
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}
        if "errors" in r:
            return {"errors": [_err(e) for e in r["errors"]]}
        return {"min_commit_ts": r.get("min_commit_ts", 0)}

    def kv_commit(self, req: dict) -> dict:
        cmd = cmds.Commit(
            [Key.from_raw(k) for k in req["keys"]],
            req["start_version"],
            req["commit_version"],
        )
        try:
            self.storage.sched_txn_command(cmd, req.get("context"))
            self._wake_lock_waiters(req["start_version"])
            return {"commit_version": req["commit_version"]}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_batch_rollback(self, req: dict) -> dict:
        cmd = cmds.Rollback([Key.from_raw(k) for k in req["keys"]], req["start_version"])
        try:
            self.storage.sched_txn_command(cmd, req.get("context"))
            self._wake_lock_waiters(req["start_version"])
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_cleanup(self, req: dict) -> dict:
        cmd = cmds.Cleanup(
            Key.from_raw(req["key"]), req["start_version"], req.get("current_ts", 0)
        )
        try:
            self.storage.sched_txn_command(cmd, req.get("context"))
            self._wake_lock_waiters(req["start_version"])
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_pessimistic_lock(self, req: dict) -> dict:
        """Acquire pessimistic locks; on conflict, WAIT through the lock
        manager (waiter_manager.rs) for up to wait_timeout_ms and retry —
        the reference's WaitForLock flow, with deadlock detection."""
        from .lock_manager import DeadlockError

        def attempt():
            cmd = cmds.AcquirePessimisticLock(
                [(Key.from_raw(k), False) for k in req["keys"]],
                req["primary_lock"],
                req["start_version"],
                req["for_update_ts"],
                lock_ttl=req.get("lock_ttl", 3000),
                return_values=req.get("return_values", False),
            )
            return self.storage.sched_txn_command(cmd, req.get("context"))

        try:
            return {"values": attempt().get("values")}
        except KeyIsLockedError as e:
            wait_ms = req.get("wait_timeout_ms", 0)
            if self.lock_manager is None or not wait_ms:
                return {"error": _err(e)}
            try:
                woken = self.lock_manager.wait_for(
                    req["start_version"], e.lock.ts, e.key, timeout=wait_ms / 1000.0
                )
            except DeadlockError as de:
                return {
                    "error": {
                        "deadlock": {
                            "waiting_txn": de.waiting_txn,
                            "blocked_on_txn": de.blocked_on_txn,
                            "cycle": de.cycle,
                        }
                    }
                }
            if not woken:
                return {"error": _err(e)}  # wait timed out: surface the lock
            try:
                return {"values": attempt().get("values")}
            except Exception as e2:  # noqa: BLE001
                return {"error": _err(e2)}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def deadlock_detect(self, req: dict) -> dict:
        """Detector-leader ingress (the reference's separate Deadlock gRPC
        service, deadlock.rs:343-391): remote stores forward wait-for edges
        here; only the store holding region 1's leadership answers with
        authority."""
        from .lock_manager import DeadlockError, DetectorHandle, FIRST_REGION_ID

        if self.lock_manager is None:
            return {"error": {"other": "lock manager not enabled"}}
        det = self.lock_manager.detector
        if isinstance(det, DetectorHandle):
            router = self.raft_router
            if router is not None and \
                    router.leader_store_of(FIRST_REGION_ID) != router.store_id:
                return {"not_leader": True}
            det = det.local
        tp = req.get("tp")
        try:
            if tp == "detect":
                det.detect(req["waiter_ts"], req["lock_ts"])
            elif tp == "clean_up_wait_for":
                det.clean_up_wait_for(req["waiter_ts"], req["lock_ts"])
            elif tp == "clean_up":
                det.clean_up(req["txn_ts"])
            else:
                return {"error": {"other": f"unknown detect tp {tp!r}"}}
        except DeadlockError as de:
            return {
                "deadlock": {
                    "waiting_txn": de.waiting_txn,
                    "blocked_on_txn": de.blocked_on_txn,
                    "cycle": de.cycle,
                }
            }
        return {"ok": True}

    def _wake_lock_waiters(self, released_ts: int) -> None:
        """Commit/rollback/resolve released this txn's locks: wake waiters
        (scheduler.rs on_release_locks -> lock_mgr.wake_up)."""
        if self.lock_manager is not None:
            self.lock_manager.wake_up_all(released_ts)

    def kv_pessimistic_rollback(self, req: dict) -> dict:
        cmd = cmds.PessimisticRollback(
            [Key.from_raw(k) for k in req["keys"]],
            req["start_version"],
            req["for_update_ts"],
        )
        try:
            self.storage.sched_txn_command(cmd, req.get("context"))
            self._wake_lock_waiters(req["start_version"])
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_txn_heart_beat(self, req: dict) -> dict:
        cmd = cmds.TxnHeartBeat(
            Key.from_raw(req["primary_lock"]), req["start_version"], req["advise_lock_ttl"]
        )
        try:
            r = self.storage.sched_txn_command(cmd, req.get("context"))
            return {"lock_ttl": r["lock_ttl"]}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_check_txn_status(self, req: dict) -> dict:
        cmd = cmds.CheckTxnStatus(
            Key.from_raw(req["primary_key"]),
            req["lock_ts"],
            req.get("caller_start_ts", 0),
            req.get("current_ts", 0),
            rollback_if_not_exist=req.get("rollback_if_not_exist", False),
            force_sync_commit=req.get("force_sync_commit", False),
        )
        try:
            r = self.storage.sched_txn_command(cmd, req.get("context"))
            st = r["status"]
            return {
                "kind": st.kind.value,
                "commit_version": st.commit_ts,
                "lock_ttl": st.lock_ttl,
                "min_commit_ts": st.min_commit_ts,
                "use_async_commit": st.use_async_commit,
            }
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_check_secondary_locks(self, req: dict) -> dict:
        cmd = cmds.CheckSecondaryLocks(
            [Key.from_raw(k) for k in req["keys"]], req["start_version"]
        )
        try:
            r = self.storage.sched_txn_command(cmd, req.get("context"))
            return {
                "locks": [{"ts": l.ts, "primary": l.primary} for l in r["locks"]],
                "commit_ts": r["commit_ts"],
            }
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_scan_lock(self, req: dict) -> dict:
        try:
            locks = self.storage.scan_lock(
                req.get("start_key"), req.get("end_key"), req["max_version"], req.get("limit")
            )
            return {
                "locks": [
                    {"key": k.to_raw(), "primary": l.primary, "lock_version": l.ts, "ttl": l.ttl}
                    for k, l in locks
                ]
            }
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def _raft_store(self):
        st = getattr(self.storage.engine, "store", None)
        if st is None:
            raise RuntimeError("not serving over a raft store")
        return st

    def kv_split_region(self, req: dict) -> dict:
        """Manual region split (kv.rs:710 split_region): allocate ids from
        PD and propose the split admin command on the region leader."""
        if self.pd is None:
            return {"error": {"other": "split_region needs a PD client"}}
        try:
            store = self._raft_store()
            region_id = (req.get("context") or {}).get("region_id")
            peer = store.peers.get(region_id)
            if peer is None or not peer.node.is_leader():
                return {"error": {"not_leader": {"region_id": region_id}}}
            # region boundaries live in ENGINE key space: txn-mode user keys
            # must be memcomparable-encoded first (kv.rs split_region does
            # Key::from_raw for non-raw mode) or the boundary would not sort
            # consistently with the stored keys
            split_key = req["split_key"]
            if not req.get("is_raw_kv", False):
                split_key = Key.from_raw(split_key).encoded
            if not peer.region.contains(split_key) or split_key == peer.region.start_key:
                return {"error": {"other": "split key out of region range"}}
            new_region_id = self.pd.alloc_id()
            new_pids = [self.pd.alloc_id() for _ in peer.region.peers]
            done = threading.Event()
            res: list = []
            peer.propose_split(
                split_key, new_region_id, new_pids,
                lambda r: (res.append(r), done.set()),
            )
            if not done.wait(5):
                return {"error": {"other": "split timed out"}}
            if isinstance(res[0], Exception):
                return {"error": _err(res[0])}
            return {"new_region_id": new_region_id}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_read_index(self, req: dict) -> dict:
        """Linearizable read barrier (kv.rs:796 read_index): returns once a
        quorum confirms leadership; callers may then read locally."""
        try:
            store = self._raft_store()
            region_id = (req.get("context") or {}).get("region_id") or req.get("region_id")
            peer = store.peers.get(region_id)
            if peer is None or not peer.node.is_leader():
                return {"error": {"not_leader": {"region_id": region_id}}}
            done = threading.Event()
            err: list = []
            peer.read_index(lambda e: (err.append(e) if e is not None else None, done.set()))
            if not done.wait(5):
                return {"error": {"other": "read_index timed out"}}
            if err:
                return {"error": _err(err[0])}
            return {"read_index": peer.node.commit}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_check_leader(self, req: dict) -> dict:
        """Leadership confirmation for resolved-ts advance (kv.rs:1005
        check_leader): of the requested regions, which does this store lead?"""
        try:
            store = self._raft_store()
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}
        leading = []
        for rid in req.get("regions", []):
            peer = store.peers.get(rid)
            if peer is not None and peer.node.is_leader():
                leading.append(rid)
        return {"regions": leading}

    def kv_flashback_to_version(self, req: dict) -> dict:
        """FlashbackToVersion (kvproto kvrpcpb.FlashbackToVersionRequest)."""
        cmd = cmds.FlashbackToVersion(
            version=req["version"],
            start_ts=req["start_ts"],
            commit_ts=req["commit_ts"],
            start_key=Key.from_raw(req["start_key"]) if req.get("start_key") else None,
            end_key=Key.from_raw(req["end_key"]) if req.get("end_key") else None,
        )
        try:
            return self.storage.sched_txn_command(cmd, req.get("context"))
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_resolve_lock(self, req: dict) -> dict:
        cmd = cmds.ResolveLock(
            req["start_version"],
            req.get("commit_version", 0),
            [Key.from_raw(k) for k in req["keys"]] if req.get("keys") else None,
        )
        try:
            r = self.storage.sched_txn_command(cmd, req.get("context"))
            self._wake_lock_waiters(req["start_version"])
            return {"resolved": r["resolved"]}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def kv_delete_range(self, req: dict) -> dict:
        from ..storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE, WriteBatch
        from ..storage.txn_types import Key as K

        wb = WriteBatch()
        start = K.from_raw(req["start_key"]).encoded
        end = K.from_raw(req["end_key"]).encoded
        for cf in (CF_DEFAULT, CF_LOCK, CF_WRITE):
            wb.delete_range_cf(cf, start, end)
        try:
            self.storage.engine.write(req.get("context"), wb)
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    # -- raw KV -------------------------------------------------------------

    def raw_get(self, req: dict) -> dict:
        v = self.storage.raw_get(req["key"], req.get("context"))
        return {"value": v, "not_found": v is None}

    def raw_batch_get(self, req: dict) -> dict:
        return {"pairs": [list(p) for p in self.storage.raw_batch_get(req["keys"], req.get("context"))]}

    def raw_put(self, req: dict) -> dict:
        self.storage.raw_put(req["key"], req["value"], req.get("context"), ttl=req.get("ttl", 0))
        return {}

    def raw_batch_put(self, req: dict) -> dict:
        self.storage.raw_batch_put(
            [tuple(p) for p in req["pairs"]], req.get("context"), ttl=req.get("ttl", 0)
        )
        return {}

    def raw_delete(self, req: dict) -> dict:
        self.storage.raw_delete(req["key"], req.get("context"))
        return {}

    def raw_batch_delete(self, req: dict) -> dict:
        self.storage.raw_batch_delete(req["keys"], req.get("context"))
        return {}

    def raw_delete_range(self, req: dict) -> dict:
        self.storage.raw_delete_range(req["start_key"], req["end_key"], req.get("context"))
        return {}

    def raw_scan(self, req: dict) -> dict:
        pairs = self.storage.raw_scan(
            req.get("start_key", b""),
            req.get("end_key"),
            req.get("limit"),
            req.get("context"),
            reverse=req.get("reverse", False),
            key_only=req.get("key_only", False),
        )
        return {"kvs": [list(p) for p in pairs]}

    def raw_batch_scan(self, req: dict) -> dict:
        """Multiple ranges, each capped at each_limit (kv.rs raw_batch_scan)."""
        out = []
        for rng in req["ranges"]:
            start, end = rng[0], rng[1]
            pairs = self.storage.raw_scan(
                start,
                end if end else None,
                req.get("each_limit"),
                req.get("context"),
                reverse=req.get("reverse", False),
                key_only=req.get("key_only", False),
            )
            out.extend(list(p) for p in pairs)
        return {"kvs": out}

    def raw_get_key_ttl(self, req: dict) -> dict:
        ttl = self.storage.raw_get_key_ttl(req["key"], req.get("context"))
        return {"ttl": ttl, "not_found": ttl is None}

    def raw_compare_and_swap(self, req: dict) -> dict:
        ok, prev = self.storage.raw_compare_and_swap(
            req["key"], req.get("previous_value"), req["value"], req.get("context"),
            ttl=req.get("ttl", 0),
        )
        return {"succeed": ok, "previous_value": prev}

    # -- coprocessor --------------------------------------------------------

    # -- MVCC debug reads (kv.rs:229-240, debug.rs mvcc_by_key) --------------

    def _mvcc_info_for_key(self, snap, raw_key: bytes) -> dict:
        from ..storage.engine import CF_DEFAULT, CF_LOCK, CF_WRITE
        from ..storage.txn_types import Key as K, Lock, Write, split_ts

        key = K.from_raw(raw_key)
        info: dict = {"lock": None, "writes": [], "values": []}
        raw_lock = snap.get_cf(CF_LOCK, key.encoded)
        if raw_lock is not None:
            lock = Lock.from_bytes(raw_lock)
            info["lock"] = {
                "type": lock.lock_type.name,
                "start_ts": lock.ts,
                "primary": lock.primary,
                "ttl": lock.ttl,
                "short_value": lock.short_value,
            }
        hi = key.append_ts(2**64 - 1).encoded
        # bounded to this key's version run: ts 0 sorts last under the desc
        # ts encoding, so the exclusive end is just past it
        lo_excl = key.append_ts(0).encoded + b"\x00"
        for k, v in snap.scan_cf(CF_WRITE, hi, lo_excl):
            try:
                user, commit_ts = split_ts(k)
            except ValueError:
                continue  # unversioned neighbor (raw-KV key) interleaved in the run
            if user != key.encoded:
                break
            w = Write.from_bytes(v)
            info["writes"].append(
                {
                    "type": w.write_type.name,
                    "start_ts": w.start_ts,
                    "commit_ts": commit_ts,
                    "short_value": w.short_value,
                }
            )
        for k, v in snap.scan_cf(CF_DEFAULT, hi, lo_excl):
            try:
                user, start_ts = split_ts(k)
            except ValueError:
                continue  # unversioned neighbor (raw-KV key)
            if user != key.encoded:
                break
            info["values"].append({"start_ts": start_ts, "value": v})
        return info

    def mvcc_get_by_key(self, req: dict) -> dict:
        """Every MVCC trace of one key: lock, write versions, large values
        (kv.rs:229 mvcc_get_by_key)."""
        try:
            snap = self.storage.engine.snapshot(req.get("context"))
            return {"key": req["key"], "info": self._mvcc_info_for_key(snap, req["key"])}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def mvcc_get_by_start_ts(self, req: dict) -> dict:
        """Find the key a txn (start_ts) touched, then its MVCC info
        (kv.rs:235 mvcc_get_by_start_ts) — scans CF_WRITE + CF_LOCK for the
        first trace of the txn inside the requested region/range."""
        from ..storage.engine import CF_LOCK, CF_WRITE
        from ..storage.txn_types import Key as K, Lock, Write, split_ts

        start_ts = req["start_ts"]
        try:
            snap = self.storage.engine.snapshot(req.get("context"))
            found: bytes | None = None
            for k, v in snap.scan_cf(CF_WRITE, b"", None):
                user, _commit = split_ts(k)
                if Write.from_bytes(v).start_ts == start_ts:
                    found = K.from_encoded(user).to_raw()
                    break
            if found is None:
                for k, v in snap.scan_cf(CF_LOCK, b"", None):
                    if Lock.from_bytes(v).ts == start_ts:
                        found = K.from_encoded(k).to_raw()
                        break
            if found is None:
                return {"key": None, "info": None}
            return {"key": found, "info": self._mvcc_info_for_key(snap, found)}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    # -- GC support (kv.rs:349-525) ------------------------------------------

    def kv_gc(self, req: dict) -> dict:
        """Deliberate stub, like the reference (kv.rs:349 returns
        unimplemented): GC is driven by the PD safe point through the
        GcManager loop, never by a client RPC."""
        return {"error": {"other": "kv_gc is deprecated: GC is safe-point driven (gc_manager)"}}

    def _gc(self):
        if self.gc_worker is None:
            raise RuntimeError("gc worker not enabled on this node")
        return self.gc_worker

    def unsafe_destroy_range(self, req: dict) -> dict:
        try:
            self._gc().unsafe_destroy_range(req["start_key"], req["end_key"], req.get("context"))
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def physical_scan_lock(self, req: dict) -> dict:
        try:
            locks = self._gc().physical_scan_lock(
                req["max_ts"], req.get("start_key"), req.get("limit")
            )
            return {
                "locks": [
                    {"key": k, "lock_ts": lock.ts, "primary": lock.primary, "ttl": lock.ttl}
                    for k, lock in locks
                ]
            }
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def register_lock_observer(self, req: dict) -> dict:
        try:
            self._gc().register_lock_observer(req["max_ts"])
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def check_lock_observer(self, req: dict) -> dict:
        try:
            return self._gc().check_lock_observer()
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def remove_lock_observer(self, req: dict) -> dict:
        try:
            self._gc().remove_lock_observer()
            return {}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    # -- cluster status RPCs (kv.rs:1034,1061) -------------------------------

    def get_store_safe_ts(self, req: dict) -> dict:
        """Minimum resolved-ts across this store's regions: the floor below
        which any stale read on this store is safe (kv.rs:1034).  Uses the
        RegionReadProgress view (safe_ts) so FOLLOWER stores — whose local
        resolvers never advance — report the disseminated floor instead of
        a frozen 0."""
        if self.resolved_ts is None:
            return {"safe_ts": 0}
        return {"safe_ts": self.resolved_ts.safe_ts()}

    def debug_read_progress(self, req: dict) -> dict:
        """Per-region RegionReadProgress pairs + the store safe_ts: the
        stuck-follower debugging surface (ctl.py ``read-progress`` and the
        status server's ``/debug/read_progress``).  Optional ``region_id``
        narrows to one region."""
        if self.resolved_ts is None:
            return {"safe_ts": 0, "regions": {}}
        rid = req.get("region_id")
        snap = self.resolved_ts.progress_snapshot()
        if rid is not None:
            resolved, required = self.resolved_ts.progress_of(rid)
            snap = {rid: (resolved, required)}
        return {
            "safe_ts": self.resolved_ts.safe_ts(),
            "regions": {
                r: {"resolved_ts": pair[0], "required_apply_index": pair[1]}
                for r, pair in sorted(snap.items())
            },
        }

    def debug_device_owners(self, req: dict) -> dict:
        """This store's current view of device-owner placement (region ->
        store), as advertised through PD (docs/wire_path.md)."""
        rp = self.read_plane
        return {"owners": rp.device_owners() if rp is not None else {}}

    def debug_wire_stages(self, req: dict) -> dict:
        """Per-stage wire-path summary (tikv_wire_stage_seconds): count and
        accumulated seconds for decode/route/execute/encode — where the wire
        path spends its time (docs/wire_path.md)."""
        from .server import WIRE_STAGE

        stages = {}
        for labels in WIRE_STAGE.label_sets():
            stage = labels.get("stage")
            if stage is None:
                continue
            stages[stage] = {
                "count": WIRE_STAGE.count(stage=stage),
                "seconds": WIRE_STAGE.total(stage=stage),
            }
        return {"stages": stages}

    def debug_observatory(self, req: dict) -> dict:
        """Performance-observatory state (docs/observatory.md; ``ctl.py
        observatory`` and the status server's ``/debug/observatory``):
        per-plan-signature path cost profiles, the compile ledger, and the
        pinned-HBM watermarks.  ``sig`` narrows to one signature; ``top``
        returns the time-spent leaderboard instead of the full snapshot;
        ``floor`` returns the per-sig rows/s baselines obs_diff.py gates
        on."""
        from ..copr import observatory as obs

        if req.get("top"):
            return {"top": obs.OBSERVATORY.top(int(req.get("limit", 20)))}
        if req.get("floor"):
            return obs.OBSERVATORY.floor(
                min_count=int(req.get("min_count", 3)))
        return obs.OBSERVATORY.snapshot(sig=req.get("sig"))

    def debug_overload(self, req: dict) -> dict:
        """Overload-control state (docs/robustness.md "Overload"; ``ctl.py
        overload`` and the status server's ``/debug/overload``): per-tenant
        bucket levels + effective rates, shed/defer counts, the adaptive
        controller's scale and evidence, and HBM partition occupancy."""
        ov = self.overload
        if ov is None and self.copr is not None:
            ov = self.copr.overload
        if ov is None:
            return {"enabled": False, "wired": False}
        return ov.snapshot()

    def debug_cost_router(self, req: dict) -> dict:
        """Cost-router + geometry-tuner state (docs/cost_router.md;
        ``ctl.py cost-router`` and the status server's
        ``/debug/cost_router``): decision counts by reason, the recent
        decision ring, and the tuner's knobs / in-flight change /
        keep-revert history."""
        if self.copr is None:
            return {"enabled": False, "wired": False}
        return self.copr.cost_router_snapshot()

    def debug_traces(self, req: dict) -> dict:
        """Recent + slow traces from the process tracer (docs/tracing.md):
        the ``ctl.py trace`` surface.  ``trace_id`` narrows to one trace;
        ``limit`` bounds the rings returned."""
        tid = req.get("trace_id")
        if tid:
            t = trace.TRACER.get(tid)
            if t is None:
                return {"error": {"other": f"trace {tid!r} not found"}}
            return {"trace": t, "timeline": trace.timeline(t)}
        return trace.snapshot(limit=int(req.get("limit", 20)))

    def get_lock_wait_info(self, req: dict) -> dict:
        """Current pessimistic lock waits (kv.rs:1061): who waits on whom."""
        if self.lock_manager is None:
            return {"entries": []}
        waiters = self.lock_manager.wait_info()
        return {
            "entries": [
                {"key": w["key"], "txn": w["start_ts"], "wait_for_txn": w["lock_ts"]}
                for w in waiters
            ]
        }

    # -- Backup service (backup/src/service.rs, server.rs:955-984) -----------

    def backup(self, req: dict) -> dict:
        """Run a consistent backup of the requested ranges at backup_ts into
        the external storage named by a URL (local:///, s3://, gcs://...),
        one file per range."""
        from ..sidecar.backup import BackupEndpoint
        from ..sidecar.cloud import create_storage

        try:
            storage = create_storage(req["storage"])
            ep = BackupEndpoint(storage)
            snap = self.storage.engine.snapshot(req.get("context"))
            files = []
            for i, rng in enumerate(req["ranges"]):
                start, end = rng[0], rng[1]
                name = req.get("name_prefix", "backup") + f"-{i:04d}"
                files.append(
                    ep.backup_range(snap, name, req["backup_ts"], start or None, end or None)
                )
            return {"files": files}
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    # -- Diagnostics service (service/diagnostics/, server.rs:907) -----------

    def _diag(self):
        if self.diagnostics is None:
            from .diagnostics import Diagnostics

            self.diagnostics = Diagnostics()
        return self.diagnostics

    def diagnostics_search_log(self, req: dict) -> dict:
        return {
            "lines": self._diag().search_log(
                patterns=req.get("patterns"),
                levels=req.get("levels"),
                start_time=req.get("start_time"),
                end_time=req.get("end_time"),
                limit=req.get("limit", 1024),
            )
        }

    def diagnostics_server_info(self, req: dict) -> dict:
        return self._diag().server_info()

    def _parse_dag_wire(self, dag: dict):
        """Memoized wire-dict -> DagRequest parse (shared by the unary,
        batch, and streaming handlers).

        The key is the plan's canonical wire bytes, which INCLUDE
        ``encode_type`` (dag_wire emits it whenever non-default): a datum
        and a TypeChunk request with identical executor bytes parse to
        distinct DagRequest objects, so a cached parse can never pin the
        wrong response encoder onto the other encoding's requests
        (tests/test_chunk_wire.py)."""
        from . import wire
        from ..copr.dag_wire import dag_from_wire

        key = wire.dumps(dag)
        with self._dag_memo_mu:
            parsed = self._dag_memo.get(key)
        if parsed is None:
            parsed = dag_from_wire(dag)
            with self._dag_memo_mu:
                self._dag_memo[key] = parsed
                while len(self._dag_memo) > 128:
                    self._dag_memo.pop(next(iter(self._dag_memo)))
        return parsed

    def _parse_copr_request(self, req: dict) -> CoprRequest:
        """ONE definition of the coprocessor sub-request parse (unary and
        batch must accept identical payloads — including dag-less CHECKSUM)."""
        dag = req.get("dag")
        if isinstance(dag, dict):
            dag = self._parse_dag_wire(dag)
        tp = req.get("tp", REQ_TYPE_DAG)
        if dag is None and tp != REQ_TYPE_CHECKSUM:
            raise ValueError("dag required for this request type")
        context = req.get("context") or {}
        if "timeout_ms" in context and "deadline" not in context:
            # wire clients can't share our monotonic clock: their relative
            # budget becomes an absolute deadline HERE, at parse time, so
            # queue wait and execution all draw down the same budget
            # (util.retry.deadline_from_context; the scheduler lanes shed
            # expired work before dispatch)
            from ..util.retry import deadline_from_context

            context = dict(context)
            context["deadline"] = deadline_from_context(context)
        return CoprRequest(
            tp=tp,
            dag=dag,
            ranges=[tuple(r) for r in req["ranges"]],
            start_ts=req["start_ts"],
            context=context,
        )

    def coprocessor(self, req: dict) -> dict:
        """req: {tp, dag (DagRequest in-process, or wire dict; optional for
        CHECKSUM), ranges, start_ts}.

        When the endpoint's read scheduler runs in continuous mode, unary
        requests route through it: concurrent clients' device-eligible DAGs
        coalesce into cross-region micro-batches (scheduler.py), each thread
        blocking only until the batch that carries its request completes —
        the unified-read-pool serving shape with XLA dispatches as the
        shared resource.  With the scheduler stopped (the default), this is
        the plain per-request path.

        Routed through the read-degradation ladder: a DAG for a region this
        store does not lead forwards one hop, then degrades to a follower
        stale serve off the warm region column cache when the context
        permits (docs/stale_reads.md).

        Device-owner routing (docs/wire_path.md): a device-eligible DAG
        whose region image is warm on ANOTHER store's cache forwards one
        hop to that store instead of serving a cold local fallback —
        placement advertised through PD, loop-guarded, breaker-protected."""
        busy = self._admit_overload(req, "copr")
        if busy is not None:
            return busy
        fwd = self._try_owner_forward(req)
        if fwd is not None:
            return fwd
        return self._serve_read("coprocessor", req, self._coprocessor_local)

    def _try_owner_forward(self, req: dict) -> dict | None:
        """The owner-routing gate: forward only when (1) the request has not
        already hopped, (2) PD names another store as the region's warm
        device owner, (3) this store cannot serve the region warm itself,
        and (4) the plan is device-eligible — otherwise local serving is
        already the best this cluster can do."""
        rp = self.read_plane
        if rp is None or self.copr is None:
            return None
        ctx = req.get("context") or {}
        if ctx.get("forwarded"):
            return None
        region_id = ctx.get("region_id")
        if region_id is None:
            return None
        owner = rp.device_owner_of(region_id)
        if owner is None or owner == rp.store_id:
            return None
        rc = getattr(self.copr, "region_cache", None)
        if (self.copr.device_enabled() and rc is not None
                and rc.has_warm_region(region_id)):
            return None  # warm here: a hop can only add latency
        if not self._dag_device_eligible(req.get("dag")):
            return None
        return rp.forward_device_owner("coprocessor", req, owner)

    def _dag_device_eligible(self, dag) -> bool:
        """Cheap, memoized device-eligibility probe for owner routing —
        deliberately independent of THIS store's enable_device switch (a
        CPU-only store is exactly the one that benefits from forwarding)."""
        from ..copr import jax_eval
        from ..copr.dag import Aggregation

        if isinstance(dag, dict):
            try:
                dag = self._parse_dag_wire(dag)
            except Exception:  # noqa: BLE001 — malformed plans serve locally
                return False
        if dag is None:
            return False
        key = id(dag)
        with self._dag_memo_mu:
            hit = self._dag_eligible_memo.get(key)
        if hit is not None and hit[0] is dag:
            return hit[1]
        ok = (any(isinstance(e, Aggregation) for e in dag.executors)
              and jax_eval.supports(dag))
        with self._dag_memo_mu:
            self._dag_eligible_memo[key] = (dag, ok)
            while len(self._dag_eligible_memo) > 256:
                self._dag_eligible_memo.pop(
                    next(iter(self._dag_eligible_memo)))
        return ok

    @staticmethod
    def _requested_chunk(req: dict) -> bool:
        """Did THIS wire request opt into TypeChunk?  (The parsed dag may
        already be the downgraded datum twin, so read the raw request.)"""
        dag = req.get("dag") if isinstance(req, dict) else None
        if isinstance(dag, dict):
            return dag.get("encode_type", 0) == 1
        return getattr(dag, "encode_type", 0) == 1

    @staticmethod
    def _copr_resp_dict(r, requested_chunk: bool, declined: bool) -> dict:
        """One coprocessor sub-response as a wire dict.  TypeChunk
        responses ship ``data_parts`` — the unjoined column slabs, each
        ≥PASSTHROUGH_MIN riding the frame as its own memoryview part
        through the ``sendmsg`` gather write — plus ``encode_type`` so the
        client picks the decoder.  Outcomes land in
        ``tikv_wire_chunk_total`` (declines were counted, with their cause,
        at negotiation time)."""
        out: dict = {"from_device": r.from_device}
        if r.encode_type:
            out["encode_type"] = r.encode_type
            out["data_parts"] = (r.data_parts if r.data_parts is not None
                                 else [r.data])
            outcome = "chunk"
        else:
            out["data"] = r.data
            outcome = None if (not requested_chunk or declined) \
                else "datum_fallback"
        if requested_chunk and outcome is not None:
            from ..util.metrics import REGISTRY

            REGISTRY.counter(
                "tikv_wire_chunk_total",
                "TypeChunk response negotiation, by outcome (cause on "
                "declines)",
            ).inc(outcome=outcome, cause="")
        return out

    @staticmethod
    def _copr_resp_nbytes(r) -> int:
        """Response payload size WITHOUT forcing the lazy data_parts join
        (the zero-copy wire path's whole point)."""
        if r.data_parts is not None:
            return sum(p.nbytes if isinstance(p, memoryview) else len(p)
                       for p in r.data_parts)
        return len(r.data)

    def _coprocessor_local(self, req: dict) -> dict:
        assert self.copr is not None, "coprocessor endpoint not wired"
        try:
            with trace.stage("copr.parse"):
                creq = self._parse_copr_request(req)
            sched = getattr(self.copr, "scheduler", None)
            if sched is not None and sched.running:
                r = sched.execute(creq)
            else:
                r = self.copr.handle_request(creq)
            self._note_read_bytes(req, self._copr_resp_nbytes(r))
            return self._copr_resp_dict(
                r, self._requested_chunk(req),
                bool((creq.context or {}).get("chunk_declined")))
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

    def coprocessor_batch(self, req: dict) -> dict:
        """K coprocessor requests in one RPC (batch_coprocessor surface):
        device-eligible aggregations over the same region view fuse into ONE
        device program; everything else answers per-request.  Response order
        matches request order; a bad sub-request fails ONLY its own slot."""
        assert self.copr is not None, "coprocessor endpoint not wired"
        from ..util.retry import DeadlineExceeded

        subs = req.get("requests") or []
        # quota admission per sub-request at the WIRE BOUNDARY (no defer —
        # a synchronous batch must not sleep per rider): client-supplied
        # markers stripped, missing contexts materialized, over-quota slots
        # answer ServerIsBusy typed with the refill-deficit hint while
        # siblings serve normally
        out_by_idx: dict[int, dict] = {}
        if self.overload is not None:
            for i, sub in enumerate(subs):
                ctx = sub.get("context")
                if not isinstance(ctx, dict):
                    ctx = sub["context"] = {}
                ctx.pop("_overload_admitted", None)
                try:
                    self.overload.admit(ctx, where="batch", wait=False)
                except Exception as e:  # noqa: BLE001 — ServerBusyError
                    out_by_idx[i] = {"error": _err(e)}
            if out_by_idx:
                live = [(i, sub) for i, sub in enumerate(subs)
                        if i not in out_by_idx]
                try:
                    creqs = [self._parse_copr_request(s) for _i, s in live]
                    results, errors = self.copr.handle_batch_errors(creqs)
                except Exception:  # noqa: BLE001 — parse poisons nothing
                    merged = [out_by_idx.get(i) or self.coprocessor(sub)
                              for i, sub in enumerate(subs)]
                    return {"responses": merged}
                served = {}
                for (i, sub), r, e, creq in zip(live, results, errors, creqs):
                    if e is None and r is not None:
                        served[i] = self._copr_resp_dict(
                            r, self._requested_chunk(sub),
                            bool((creq.context or {}).get("chunk_declined")))
                    elif isinstance(e, DeadlineExceeded):
                        served[i] = {"error": _err(e)}
                    else:
                        served[i] = self.coprocessor(sub)
                return {"responses": [out_by_idx.get(i) or served[i]
                                      for i in range(len(subs))]}
        try:
            creqs = [self._parse_copr_request(sub) for sub in subs]
            results, errors = self.copr.handle_batch_errors(creqs)
        except Exception:  # noqa: BLE001 — a parse failure poisons nothing
            return {"responses": [self.coprocessor(sub) for sub in subs]}
        out = []
        for sub, r, e, creq in zip(subs, results, errors, creqs):
            if e is None and r is not None:
                # per-region payloads (chunk or datum) answer in THIS one
                # frame — the scheduler's vmapped cross-region batch rides
                # back to the wire client as a single multi-response frame
                # with per-region error isolation (docs/wire_path.md)
                out.append(self._copr_resp_dict(
                    r, self._requested_chunk(sub),
                    bool((creq.context or {}).get("chunk_declined"))))
            elif isinstance(e, DeadlineExceeded):
                # expired in queue: report it, never re-dispatch — the
                # client already gave up on this slot
                out.append({"error": _err(e)})
            else:
                # per-slot re-serve keeps the old isolation contract (and a
                # batch-path device error may still succeed per-request);
                # handle_request's entry gate sheds it cheaply if its
                # deadline lapsed meanwhile
                out.append(self.coprocessor(sub))
        return {"responses": out}

    def coprocessor_stream(self, req: dict):
        """Streamed DAG execution (endpoint.rs:508-584): returns a GENERATOR
        of per-frame dicts.  The server writes each frame to the wire as it
        is produced (same req_id, terminated by a stream_end frame), so
        server-side memory stays O(one frame) and a slow client back-
        pressures the executor through TCP instead of ballooning a buffer.
        Validation errors before the first frame return a plain error dict
        (the unary shape)."""
        assert self.copr is not None, "coprocessor endpoint not wired"
        busy = self._admit_overload(req, "stream")
        if busy is not None:
            return busy
        try:
            dag = req.get("dag")
            if isinstance(dag, dict):
                dag = self._parse_dag_wire(dag)
            if dag is None:
                return {"error": {"other": "dag required"}}
            creq = CoprRequest(
                tp=req.get("tp", REQ_TYPE_DAG),
                dag=dag,
                ranges=[tuple(r) for r in req["ranges"]],
                start_ts=req["start_ts"],
                context=req.get("context") or {},
            )
            rows_per_stream = req.get("rows_per_stream", 1024)
        except Exception as e:  # noqa: BLE001
            return {"error": _err(e)}

        requested_chunk = self._requested_chunk(req)

        def frames():
            for r in self.copr.handle_streaming_request(creq, rows_per_stream):
                yield self._copr_resp_dict(
                    r, requested_chunk,
                    bool((creq.context or {}).get("chunk_declined")))

        return frames()
