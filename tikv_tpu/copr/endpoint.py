"""Coprocessor endpoint: parse, route, execute.

Re-expression of ``src/coprocessor/endpoint.rs`` (:45 Endpoint, :144
parse_request_and_check_memory_locks, :392/:459/:486 unary path): takes a
coprocessor request (DAG over key ranges at a start_ts), obtains a snapshot
from the engine, and runs the plan — on the **device path** when the DAG is
eligible (gated at the plugin boundary), else the CPU batch
pipeline.  A response cache keyed by (region, data version) serves repeated
requests and backs the columnar block cache.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from ..analysis import bufsan as _bufsan
from ..storage.kv import Engine
from ..storage.mvcc import Statistics
from ..util import trace
from ..util.metrics import REGISTRY
from . import jax_eval
from . import plan_shape as _plan_shape
from .cache import ColumnBlockCache, CopCache
from .dag import (
    ENC_TYPE_CHUNK,
    BatchExecutorsRunner,
    DagRequest,
    SelectResponse,
    negotiate_encode_type,
)
from .executors import MvccScanSource
from .mvcc_batch import MvccBatchScanSource

REQ_TYPE_DAG = 103
REQ_TYPE_ANALYZE = 104
REQ_TYPE_CHECKSUM = 105

_MESH_UNCHECKED = object()  # sentinel: DAG not yet probed for mesh eligibility

# server.py's wire-stage buckets (tikv_wire_stage_seconds): the coprocessor
# response-encode observation below must create the series with the SAME
# bucket layout when the endpoint runs before the TCP server imports
_WIRE_STAGE_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1,
                      0.5, 1, 5)


_PLAN_SHAPES = REGISTRY.counter(
    "tikv_coprocessor_plan_shape_total",
    "Device-served tasks by how their plan's shape found its evaluator: "
    "reused (the shape was known, whatever the literals) or built")


class RungProgramError(RuntimeError):
    """A serving rung raised a type/shape error while tracing its program: a
    bug that every call repeats, so it reaches the caller instead of being
    absorbed as a device fault by the next rung down."""


@dataclass
class CoprRequest:
    """coppb.Request equivalent."""

    tp: int
    dag: DagRequest
    ranges: list[tuple[bytes, bytes]]
    start_ts: int
    context: dict = field(default_factory=dict)  # region_id, epoch...


class CoprResponse:
    """coppb.Response equivalent.

    ``data`` is the canonical payload bytes (every in-process consumer and
    byte-identity compare).  TypeChunk responses additionally carry
    ``data_parts`` — the unjoined buffer list from
    ``SelectResponse.encode_parts`` — and ``data`` joins LAZILY, so the
    wire path ships each large column slab as its own ``sendmsg`` iovec
    without ever paying the join (docs/wire_path.md)."""

    __slots__ = ("_data", "data_parts", "encode_type", "from_device",
                 "from_cache", "metrics")

    def __init__(self, data: bytes | None = None, from_device: bool = False,
                 from_cache: bool = False, metrics: dict | None = None,
                 data_parts: list | None = None, encode_type: int = 0):
        assert data is not None or data_parts is not None
        self._data = data
        self.data_parts = data_parts
        self.encode_type = encode_type
        self.from_device = from_device
        self.from_cache = from_cache
        self.metrics = metrics if metrics is not None else {}

    @property
    def data(self) -> bytes:
        if self._data is None:
            self._data = b"".join(bytes(p) for p in self.data_parts)
        return self._data


def resolve_encode_type(req: CoprRequest) -> None:
    """Entry-gate encoding negotiation: a TypeChunk request whose plan
    cannot chunk-encode downgrades IN PLACE to its datum twin — a datum
    response with a counted cause, never an error.  Idempotent (the twin's
    encode_type is datum), called at every serving entry (service parse,
    endpoint unary/batch, scheduler admission) so no path can reach an
    evaluator with an unsupported chunk plan."""
    dag = req.dag
    if dag is None or dag.encode_type != ENC_TYPE_CHUNK:
        return
    eff, cause = negotiate_encode_type(dag)
    if cause is None:
        return
    req.dag = eff
    ctx = req.context if req.context is not None else {}
    req.context = ctx
    if "chunk_declined" not in ctx:
        ctx["chunk_declined"] = cause
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_wire_chunk_total",
            "TypeChunk response negotiation, by outcome (cause on declines)",
        ).inc(outcome="decline", cause=cause)


def stale_read_ctx(req: CoprRequest) -> dict | None:
    """Effective stale-read context for admission and snapshotting: the DAG
    executes its MVCC read at ``req.start_ts``, so the watermark check must
    cover start_ts even when the client declared a lower ``read_ts`` —
    otherwise a lagging replica would admit a request whose scan then reads
    above the watermark (a typed DataNotReady here, not a tripped pairing
    invariant in the region cache)."""
    ctx = req.context or None
    if not ctx or not ctx.get("stale_read"):
        return ctx
    read_ts = ctx.get("read_ts")
    if read_ts is None or int(read_ts) < req.start_ts:
        ctx = dict(ctx, read_ts=req.start_ts)
    return ctx


class Endpoint:
    def __init__(
        self,
        engine: Engine,
        enable_device: bool = True,
        block_cache: CopCache | None = None,
        concurrency_manager=None,
        slow_log=None,
        mesh=None,
        feature_gate=None,
        enable_region_cache: bool = True,
        region_cache=None,
        sched_config=None,
        block_rows: int | None = None,
        shard_cache: bool = True,
        write_through: bool = True,
        encode_columns: bool = True,
        breaker=None,
        breaker_config=None,
        shadow_sample: int | None = None,
        overload=None,
        overload_config=None,
        cost_router=None,
    ):
        from .breaker import DeviceCircuitBreaker
        from .tracker import SlowLog

        self.engine = engine
        self.enable_device = enable_device
        # device block geometry: evaluators pad every block to this row
        # count, so small-region deployments (many regions per store) should
        # size it near the region row count — a 4k-row region padded to the
        # 64k default wastes 16x the compute on every backend.  None keeps
        # the jax_eval default.
        self.block_rows = block_rows
        # device-resident per-region column cache with delta apply (region
        # requests carrying region_epoch + apply_index in the context skip
        # scan+decode entirely on repeat reads); None = disabled.  With a
        # multi-device mesh the cache runs SHARDED: images placed on owner
        # devices so warm serving uses every chip (docs/mesh_serving.md).
        # shard_cache=False is the kill switch: no sharded placement AND no
        # sharded warm routing (unary or scheduler) — PR-2 behavior exactly
        self.shard_cache = shard_cache
        if region_cache is not None:
            self.region_cache = region_cache
        elif enable_region_cache:
            from .region_cache import RegionColumnCache

            # write_through=False is the kill switch for the raft-apply
            # delta intake (docs/write_path.md): warm reads under writes
            # then always repair through scan_delta
            self.region_cache = RegionColumnCache(
                block_rows=block_rows,
                mesh=mesh if shard_cache else None,
                write_through=write_through,
                # compressed residency (docs/compressed_columns.md): images
                # encode at fill and the budget counts ENCODED bytes —
                # encode_columns=False is the kill switch
                encode_columns=encode_columns,
                # bind the cache to THIS engine's write-through stream now —
                # a raft engine exposes its store engine's identity; a plain
                # local engine binds None (direct notify callers, tests)
                data_token=getattr(engine, "data_token", None),
            )
        else:
            self.region_cache = None
        # version-gated rollout (feature_gate.rs:14): the gate is the hard
        # floor under the enable_device/mesh/batch-fusion switches — a
        # mixed-version cluster keeps device serving off until every store
        # can speak it.  None = ungated (tests, embedded use).
        self.feature_gate = feature_gate
        self.cop_cache = block_cache or CopCache()
        self.cm = concurrency_manager
        self.slow_log = slow_log or SlowLog()
        self._evaluators: dict = {}
        # evaluators built, one a plan shape the memo did not hold: it stops
        # growing once the shapes in use are known, whatever literals come
        self.plan_shapes_built = 0
        # multi-device serving: a (regions × groups) jax.sharding.Mesh shards
        # eligible aggregation DAGs' row blocks across devices (scale-out
        # analog of region sharding); single-device when None or 1 device
        self.mesh = mesh
        self._mesh_runners: dict = {}
        # device-path failures observed (CPU fallback taken): a permanently
        # broken device shows up here instead of only as from_device=False
        self.device_fallbacks = 0
        self.last_device_error: str | None = None
        # device-path circuit breaker (docs/robustness.md): repeated faults
        # on a serving path (unary/zone/fused/xregion/mesh) trip THAT path
        # to its fallback for a cooldown, with half-open probes — one flaky
        # path stops re-paying its failure latency on every request.  The
        # scheduler and the zone evaluator consult the same instance.
        self.breaker = breaker or DeviceCircuitBreaker(breaker_config)
        # unified read scheduler (scheduler.py): cross-region continuous
        # batching over the region column cache.  handle_batch always routes
        # through it; start() turns on the continuous unary lanes.
        from .scheduler import CoprReadScheduler

        self.scheduler = CoprReadScheduler(self, sched_config)
        # integrity plane (docs/integrity.md): deterministic shadow-read
        # sampling of warm device serves (default 1/256, TIKV_TPU_SHADOW_SAMPLE
        # env; 0 = off, 1 = verify every warm serve) + the SDC scrubber —
        # constructed unstarted; standalone servers start the cadence
        from .integrity import IntegrityScrubber, ShadowSampler

        self.shadow = ShadowSampler(shadow_sample)
        self.scrubber = (
            IntegrityScrubber(self.region_cache, engine)
            if self.region_cache is not None else None
        )
        # overload control plane (docs/robustness.md "Overload"): per-tenant
        # quota admission + lane clamping in the scheduler, HBM partitions
        # in the region cache, CPU fallback on the memory-pressure ladder's
        # last rung.  None = no admission policy (historical behavior).
        if overload is not None:
            self.overload = overload
        elif overload_config is not None:
            from .overload import OverloadControl

            self.overload = OverloadControl(
                overload_config, region_cache=self.region_cache)
        else:
            self.overload = None
        # cost-based path router (docs/cost_router.md): picks the cheapest
        # measured path per plan signature, bounded explore, strict static
        # fallback.  None (the library default) means the static ladder
        # stands untouched; the standalone server wires a default-on router
        # (kill switch: TIKV_TPU_COST_ROUTER=0 / --no-cost-router — the
        # router still answers, with reason="kill_switch" and the static
        # head, byte- and path-identical to the pre-router ladder).
        self.cost_router = cost_router
        if cost_router is not None and cost_router.delta_sink is None:
            # chosen-vs-best deltas feed the overload AdaptiveController so
            # admission tightening and path choice share evidence (PR 15)
            cost_router.delta_sink = self._note_route_delta
        # geometry auto-tuner attach point: the standalone server parks its
        # GeometryTuner here so /debug/cost_router shows tuner state next
        # to the decisions it reacted to
        self.geometry_tuner = None

    def _encode_response(self, resp: SelectResponse):
        """SelectResponse -> (frame parts, encode_type): the one response
        serialization point of the device/CPU unary paths, timed into the
        wire-stage histogram (stage=copr_encode) so response assembly stays
        attributable next to decode/route/execute/encode
        (docs/wire_path.md)."""
        from ..util.metrics import REGISTRY

        with trace.timed_stage("copr.encode") as st:
            parts = resp.encode_parts()
        REGISTRY.histogram(
            "tikv_wire_stage_seconds",
            "Wire-path time per served frame, by stage",
            buckets=_WIRE_STAGE_BUCKETS,
        ).observe(st.seconds, stage="copr_encode")
        return parts, resp.encode_type

    def handle_request(self, req: CoprRequest) -> CoprResponse:
        """Instrumented entry: every path (device, CPU fallback, analyze,
        checksum) lands in tikv_coprocessor_request_* exactly once."""
        import time as _time

        from ..util.metrics import REGISTRY
        from ..util.retry import DeadlineExceeded, deadline_from_context

        resolve_encode_type(req)

        # shed expired work at the LAST entry gate: every fallback route
        # (scheduler direct serve, per-slot batch re-serve, scheduler-off
        # unary service) funnels through here, so an expired request can
        # never reach a snapshot or a device dispatch
        dl = deadline_from_context(req.context)
        if dl is not None and _time.monotonic() >= dl:
            REGISTRY.counter(
                "tikv_coprocessor_deadline_expired_total",
                "Requests shed because their deadline expired, by detection point",
            ).inc(at="endpoint")
            raise DeadlineExceeded("deadline expired before serving")

        t0 = _time.perf_counter()
        with trace.span("copr.handle", tp=req.tp,
                        region=(req.context or {}).get("region_id")) as sp:
            resp = self._handle_request_inner(req)
            md = resp.metrics or {}
            if sp:
                # the tracker's phase breakdown rides the request's span so
                # the slow log and the trace tell one story (docs/tracing.md)
                sp.tag(from_device=resp.from_device,
                       from_cache=resp.from_cache,
                       **{k: md[k] for k in
                          ("schedule_wait_ms", "snapshot_ms", "handle_ms",
                           "total_ms", "scanned_keys", "region_cache")
                          if k in md})
        REGISTRY.counter(
            "tikv_coprocessor_request_total", "Coprocessor requests, by type/path"
        ).inc(tp=str(req.tp), path="device" if resp.from_device else "cpu")
        REGISTRY.histogram(
            "tikv_coprocessor_request_duration_seconds", "Coprocessor latency"
        ).observe(md.get("total_s", _time.perf_counter() - t0), tp=str(req.tp))
        if resp.from_cache:
            REGISTRY.counter(
                "tikv_coprocessor_cache_hit_total",
                "Requests answered from the HBM-pinned block cache",
            ).inc()
        return resp

    def _handle_request_inner(self, req: CoprRequest) -> CoprResponse:
        from .tracker import Tracker

        from ..util.failpoint import fail_point

        fail_point("coprocessor_parse_request")
        tracker = Tracker(f"copr tp={req.tp} region={req.context.get('region_id') if req.context else None}")
        if req.tp == REQ_TYPE_ANALYZE:
            return self._tracked(tracker, self._handle_analyze, req)
        if req.tp == REQ_TYPE_CHECKSUM:
            return self._tracked(tracker, self._handle_checksum, req)
        if req.tp != REQ_TYPE_DAG:
            raise ValueError(f"unsupported coprocessor request type {req.tp}")
        if self.cm is not None:
            from ..storage.txn_types import Key

            for start, end in req.ranges:
                self.cm.read_range_check(Key.from_raw(start), Key.from_raw(end), req.start_ts)
        tracker.on_schedule()
        # chaos/regression hook INSIDE the tracked window (the parse
        # failpoint above fires before the tracker starts): a seeded
        # sleep here inflates measured serve latency — what the
        # observatory floor gate's regression test injects
        fail_point("coprocessor_serve")
        with trace.stage("copr.snapshot"):
            snap = self.engine.snapshot(stale_read_ctx(req))
        tracker.on_snapshot_finished()
        # follower stale serving (docs/stale_reads.md): the snapshot itself
        # says whether it came off the stale path — counted per serving
        # path below so operators see read traffic scale with replicas
        stale_snap = bool(getattr(snap, "stale", False))
        use_device = False
        if self.device_enabled():
            decline = jax_eval.decline_cause(req.dag)
            use_device = decline is None
            if decline is not None:
                from .dag import Join, Limit, Projection, TopN

                if any(isinstance(e, (Limit, TopN, Join, Projection))
                       for e in req.dag.executors[1:]):
                    # Limit/TopN plans never fall to the CPU silently: the
                    # early-exit tiling work (docs/zone_maps.md) made them
                    # device-eligible, so a decline is a named, counted
                    # event; Join/Projection plans likewise (the join rung
                    # below may still serve them — docs/device_join.md)
                    from . import encoding as _encoding

                    _encoding.count_decline("device_plan", decline)
        if use_device and self.overload is not None \
                and not self.overload.allow_device(req.context):
            # memory-pressure degradation ladder, last rung (overload.py):
            # this tenant's HBM partition would not fit even after eviction
            # and pin demotion — serve its work on the CPU pipeline until
            # the cooldown lifts, leaving other tenants' warm sets alone
            from .tracker import count_path_fallback

            count_path_fallback("unary", "tenant_pressure")
            use_device = False
        if use_device and not self.breaker.allow("unary"):
            # tripped: repeated unary device faults — serve straight off the
            # CPU pipeline until a half-open probe restores the path
            from .tracker import count_path_fallback

            count_path_fallback("unary", "breaker_open")
            use_device = False
        # cost-based routing (docs/cost_router.md) AFTER the admission
        # gates: overload and breaker verdicts are overrides, not cost
        # preferences — the router only picks among paths admission allows
        route = None
        if use_device:
            route = self._route_for(req)
            if route is not None and route.path == "cpu":
                # measured: the host wins this plan shape (Tailwind-style
                # routing around the accelerator), or a budgeted cold
                # probe keeping the CPU profile fresh
                from .tracker import count_path_fallback

                count_path_fallback("unary", "cost_route")
                use_device = False
        if use_device:
            cache = None
            ev = None
            try:
                cache, rc_outcome = self._region_cache_for(req, snap, tracker)
                if cache is None:
                    cache = self._block_cache_for(req)
                # cold path with a mesh: MeshServingRunner shards the MVCC
                # scan's super-blocks; warm path with a mesh: the cache is
                # ALREADY sharded (RegionColumnCache places images on owner
                # devices), so cached serving routes through the sharded
                # cross-region launcher below — the PR-2 "mesh bypass due to
                # filled cache" is gone
                ev = None
                params = ()
                if cache is None:
                    ev = self._mesh_evaluator_for(req.dag)
                if ev is None:
                    ev, params = self._bind(req.dag)
                src = None
                if cache is None or not cache.filled:
                    src = MvccBatchScanSource(snap, req.start_ts, req.ranges)
                resp = None
                want_mesh = route is None or route.path == "mesh"
                if src is None and want_mesh and self._mesh_would_serve(req.dag):
                    resp = self._run_sharded_cached(ev, cache, params)
                if resp is None:
                    # routed zone/unary steer the evaluator's rung choice;
                    # set/cleared around run — a concurrent mis-read only
                    # picks a different byte-identical warm rung
                    ev.route_hint = (route.path if route is not None
                                     and route.path in ("zone", "unary")
                                     else None)
                    try:
                        resp = ev.run(src, cache=cache, params=params)
                    finally:
                        ev.route_hint = None
                parts, enc_tp = self._encode_response(resp)
                data = None
                from_device = True
                # shadow-read verification (docs/integrity.md): a sampled
                # warm image-backed serve re-executes on the CPU oracle and
                # byte-compares — a mismatch quarantines the image and the
                # CPU bytes serve, so a sampled request never returns
                # corrupted derived state.  The oracle runs the SAME
                # negotiated encoding (req.dag carries it), so chunk
                # responses byte-compare chunk bytes.
                if (rc_outcome in ("hit", "delta", "wt_delta")
                        and self.shadow.pick("unary")):
                    fixed = self.shadow_compare(
                        req, snap, b"".join(bytes(p) for p in parts), "unary")
                    if fixed is not None:
                        data, parts = fixed, None
                        from_device = False
                scanned = src.stats.write.processed_keys if src is not None else 0
                m = tracker.on_finish(scanned_keys=scanned, from_device=from_device)
                rows = (cache.total_rows
                        if cache is not None and cache.filled and src is None
                        else scanned)
                self._record_obs(req, tracker,
                                 getattr(resp, "_obs_path", "unary"),
                                 getattr(resp, "_obs_encoding", "plain"),
                                 rows, ev=ev, resp=resp)
                self.slow_log.observe(tracker)
                from_cache = (from_device
                              and cache is not None and cache.filled and src is None
                              and rc_outcome not in ("miss", "too_big"))
                self.breaker.record_success("unary")
                if stale_snap:
                    self.count_follower_read("device" if from_device else "cpu")
                return CoprResponse(
                    data, from_device=from_device,
                    from_cache=from_cache,
                    metrics=m.to_dict(),
                    data_parts=parts, encode_type=enc_tp,
                )
            except Exception as exc:
                from ..storage.mvcc.reader import KeyIsLockedError
                from .integrity import IntegrityMismatch

                if isinstance(exc, (IntegrityMismatch, RungProgramError)):
                    raise  # fatal integrity / program bug: surface, never mask
                if isinstance(exc, KeyIsLockedError):
                    # the reader met a lock: the answer is ``locked`` (the
                    # client backs off and retries at the same start_ts), as
                    # the CPU scan would give it — not a device fault
                    raise
                # device/runtime failure (compiler, runtime, OOM): the CPU
                # pipeline is the correctness oracle and always available —
                # re-run there off the same immutable snapshot rather than
                # surfacing an accelerator error to the client
                if cache is not None and not cache.filled:
                    # a partially-filled block cache would double-append on
                    # the next request and serve wrong data forever; the
                    # failed run may have pinned arrays — clear WITH the
                    # observatory's pin accounting
                    cache.clear_blocks()
                self.device_fallbacks += 1
                self.last_device_error = repr(exc)
                self.breaker.record_failure("unary")
                cur = trace.current()
                if cur is not None:
                    cur.tag(device_fallback=repr(exc))
                from ..util.metrics import REGISTRY

                from . import observatory as _obs
                from .tracker import count_path_fallback

                count_path_fallback("unary", "device_error")
                _obs.OBSERVATORY.record_decline(
                    getattr(ev, "obs_sig", None), "unary", "device_error")
                REGISTRY.counter(
                    "tikv_coprocessor_device_fallback_total",
                    "Device-path failures that re-ran on the CPU pipeline",
                ).inc()
        resp = self._try_device_join(req, snap, tracker, stale_snap)
        if resp is not None:
            return resp
        resp = self._try_dict_rewrite(req, snap, tracker, stale_snap)
        if resp is not None:
            return resp
        stats = Statistics()
        src = MvccScanSource(snap, req.start_ts, req.ranges, statistics=stats)
        with trace.span("copr.cpu"):
            resp = BatchExecutorsRunner(req.dag, src).handle_request()
        m = tracker.on_finish(scanned_keys=stats.write.processed_keys, from_device=False)
        self._record_obs(req, tracker, "cpu", "plain",
                         stats.write.processed_keys)
        self.slow_log.observe(tracker)
        if stale_snap:
            self.count_follower_read("cpu")
        parts, enc_tp = self._encode_response(resp)
        return CoprResponse(None, from_device=False, metrics=m.to_dict(),
                            data_parts=parts, encode_type=enc_tp)

    def _build_cache_for(self, req: CoprRequest, snap, join):
        """Resolve a Join's build-side region image.  The build context
        (region id / epoch / apply index) rides the Join descriptor — the
        probe snapshot cannot vouch for a DIFFERENT region's identity, so
        a missing context is a named decline, never a guess."""
        ctx = join.build_context
        if ctx is None:
            return None, "no_build_context"
        context = dict(ctx)
        if req.context and "tenant" in req.context:
            # one request, one tenant: the build image bills the same
            # HBM partition as the probe's
            context.setdefault("tenant", req.context["tenant"])
        cache, outcome, _delta = self.region_cache.serve(
            snap, context, join.build[0].columns_info, join.build_ranges,
            req.start_ts)
        return cache, outcome

    def _try_device_join(self, req: CoprRequest, snap, tracker, stale_snap):
        """Device join rung (docs/device_join.md): a ``[TableScan, Join,
        ...]`` plan whose probe AND build region images are warm serves as
        ONE dispatch over both images — rank-space joins over shared
        sorted dictionaries, radix-hash joins over int key lanes — with
        payload columns late-materialized only for surviving row pairs.
        Every shape the kernels cannot cover (outer joins, filtered probe
        sides, unsorted dictionaries, exotic key types) is a per-cause
        counted decline to the CPU oracle, never a silent fallback."""
        from . import encoding as _encoding
        from . import observatory as _obs
        from .dag import Join

        dag = req.dag
        if (self.region_cache is None or not self.device_enabled()
                or dag is None
                or not any(isinstance(e, Join) for e in dag.executors)):
            return None

        def declined(cause: str):
            _encoding.count_join("device", "declined")
            _encoding.count_decline("join", cause)
            try:
                sig, _desc = _obs.dag_sig(dag)
            except Exception:  # noqa: BLE001 — profiling must not fail serving
                sig = None
            _obs.OBSERVATORY.record_decline(sig, "join", cause)
            return None

        from . import jax_join as _jax_join

        try:
            _probe_scan, join, _rest = _jax_join.analyze_plan(dag)
        except _jax_join.JoinDecline as d:
            return declined(d.cause)
        if self.overload is not None \
                and not self.overload.allow_device(req.context):
            from .tracker import count_path_fallback

            count_path_fallback("unary", "tenant_pressure")
            return None
        if not self.breaker.allow("unary"):
            from .tracker import count_path_fallback

            count_path_fallback("unary", "breaker_open")
            return None
        # cost routing among the join ladder (docs/cost_router.md):
        # candidate_paths declares rank/hash/cpu for join plans, so the
        # router prices the measured rank vs hash vs CPU profiles
        route = self._route_for(req)
        prefer = (route.path if route is not None
                  and route.path in ("rank", "hash", "cpu") else None)
        if prefer == "cpu":
            from .tracker import count_path_fallback

            count_path_fallback("unary", "cost_route")
            _encoding.count_join("cpu", "routed")
            self.breaker.release_probe("unary")
            return None
        try:
            probe_cache, rc_outcome = self._region_cache_for(req, snap, tracker)
            if (probe_cache is None or not probe_cache.filled
                    or not probe_cache.blocks):
                self.breaker.release_probe("unary")
                return declined("probe_cold")
            build_cache, b_outcome = self._build_cache_for(req, snap, join)
            if b_outcome == "no_build_context":
                self.breaker.release_probe("unary")
                return declined("no_build_context")
            if (build_cache is None or not build_cache.filled
                    or not build_cache.blocks):
                self.breaker.release_probe("unary")
                return declined("build_cold")
            try:
                resp, path, stats = _jax_join.serve(
                    dag, probe_cache, build_cache, prefer=prefer)
            except _jax_join.JoinDecline as d:
                self.breaker.release_probe("unary")
                return declined(d.cause)
            parts, enc_tp = self._encode_response(resp)
            data = None
            from_device = True
            warm = ("hit", "delta", "wt_delta")
            if ((rc_outcome in warm or b_outcome in warm)
                    and self.shadow.pick("unary")):
                fixed = self.shadow_compare(
                    req, snap, b"".join(bytes(p) for p in parts), "unary")
                if fixed is not None:
                    data, parts = fixed, None
                    from_device = False
            _encoding.count_join(path, "served")
            m = tracker.on_finish(scanned_keys=0, from_device=from_device)
            resp._obs_join = (stats["build_rows"], stats["probe_rows"],
                              stats["out_rows"])
            self._record_obs(req, tracker, path, "encoded",
                             stats["probe_rows"] + stats["build_rows"],
                             resp=resp)
            self.slow_log.observe(tracker)
            self.breaker.record_success("unary")
            if stale_snap:
                self.count_follower_read("device" if from_device else "cpu")
            cold = ("miss", "too_big")
            return CoprResponse(
                data, from_device=from_device,
                from_cache=(from_device and rc_outcome not in cold
                            and b_outcome not in cold),
                metrics=m.to_dict(), data_parts=parts, encode_type=enc_tp)
        except Exception as exc:  # noqa: BLE001 — CPU pipeline always serves
            from .integrity import IntegrityMismatch

            if isinstance(exc, IntegrityMismatch):
                raise  # TIKV_TPU_INTEGRITY_FATAL: surface, never mask
            self.device_fallbacks += 1
            self.last_device_error = repr(exc)
            self.breaker.record_failure("unary")
            from .tracker import count_path_fallback

            count_path_fallback("unary", "device_error")
            _encoding.count_join("device", "error")
            return None

    def _try_dict_rewrite(self, req: CoprRequest, snap, tracker, stale_snap):
        """Dictionary code-space serving rung (docs/compressed_columns.md):
        a DAG whose ONLY device blocker is bytes predicates over
        dictionary-resident columns rewrites those predicates into the warm
        image's code space (equality/IN through the bytes→code map, ranges
        through searchsorted ranks on a SORTED dictionary) and serves on
        the device — no string ever materializes.  Declines — cold region,
        unstable/unsorted dictionary, a plan shape the rewrite can't cover —
        are counted per-cause and fall to the CPU pipeline; served bytes
        ride the same shadow-read sampling as every warm device serve."""
        from . import encoding as _encoding

        if (self.region_cache is None or not self.device_enabled()
                or not _encoding.dict_rewrite_probe(req.dag)):
            return None
        if req.dag.encode_type == ENC_TYPE_CHUNK:
            # the rewrite rung is DATUM-ONLY: the rewritten plan's schema
            # declares a dict column LONGLONG while the served column still
            # carries bytes, and the schema-driven chunk encoder would emit
            # raw dictionary codes a client decoding against its own plan
            # cannot read (the oracle would then false-quarantine a healthy
            # image on the shadow mismatch).  The CPU pipeline below serves
            # the chunk bytes correctly.
            _encoding.count_decline("rewrite", "chunk_encoding")
            return None
        if not self.breaker.allow("unary"):
            from .tracker import count_path_fallback

            count_path_fallback("unary", "breaker_open")
            return None
        try:
            cache, rc_outcome = self._region_cache_for(req, snap, tracker)
            if cache is None or not cache.filled or not cache.blocks:
                _encoding.count_rewrite("cold")
                _encoding.count_decline("rewrite", "cold_region")
                self.breaker.release_probe("unary")
                return None
            new_dag, info = _encoding.rewrite_dag_for_dict(req.dag, cache.blocks)
            if new_dag is None or not jax_eval.supports(new_dag):
                _encoding.count_rewrite("declined")
                _encoding.count_decline(
                    "rewrite",
                    info if isinstance(info, str) else "unsupported_plan")
                self.breaker.release_probe("unary")
                return None
            ev, params = self._bind(new_dag)
            resp = ev.run(None, cache=cache, params=params)
            parts, enc_tp = self._encode_response(resp)
            data = None
            from_device = True
            if (rc_outcome in ("hit", "delta", "wt_delta")
                    and self.shadow.pick("unary")):
                fixed = self.shadow_compare(
                    req, snap, b"".join(bytes(p) for p in parts), "unary")
                if fixed is not None:
                    data, parts = fixed, None
                    from_device = False
            _encoding.count_rewrite("served")
            m = tracker.on_finish(scanned_keys=0, from_device=from_device)
            # the rewrite rung serves over resident code lanes — encoded by
            # construction; the sig recorded is the ORIGINAL plan's (what
            # the client sent), not the rewritten one
            self._record_obs(req, tracker, "unary", "encoded",
                             cache.total_rows, resp=resp)
            self.slow_log.observe(tracker)
            self.breaker.record_success("unary")
            if stale_snap:
                self.count_follower_read("device" if from_device else "cpu")
            return CoprResponse(
                data, from_device=from_device,
                # first-touch builds are NOT cache hits — same rule as the
                # main unary path's from_cache accounting
                from_cache=from_device and rc_outcome not in ("miss", "too_big"),
                metrics=m.to_dict(), data_parts=parts, encode_type=enc_tp)
        except Exception as exc:  # noqa: BLE001 — CPU pipeline always serves
            from .integrity import IntegrityMismatch

            if isinstance(exc, IntegrityMismatch):
                raise  # TIKV_TPU_INTEGRITY_FATAL: surface, never mask
            self.device_fallbacks += 1
            self.last_device_error = repr(exc)
            self.breaker.record_failure("unary")
            from .tracker import count_path_fallback

            count_path_fallback("unary", "device_error")
            _encoding.count_rewrite("error")
            return None

    def _record_obs(self, req: CoprRequest, tracker, path: str,
                    encoding: str, rows: int, ev=None, resp=None) -> None:
        """Report one served request into the performance observatory
        (docs/observatory.md) and stamp the serving path + plan sig onto
        the tracker so the slow log pivots into ``ctl.py observatory sig``.
        Must run BEFORE ``slow_log.observe``."""
        from . import observatory as _obs

        if not _obs.OBSERVATORY.enabled:
            # kill switch: skip even the dag_sig walk — a disabled
            # observatory must cost the hot path nothing
            return
        with trace.stage("copr.obs"):
            sig = getattr(ev, "obs_sig", "") if ev is not None else ""
            desc = getattr(ev, "obs_desc", "") if ev is not None else ""
            if not sig:
                try:
                    sig, desc = _obs.dag_sig(req.dag)
                except Exception:  # noqa: BLE001 — profiling must not fail serving
                    return
            tracker.metrics.serve_path = path
            tracker.metrics.plan_sig = sig
            m = tracker.metrics
            # zone-map pruning effectiveness rides the profile (docs/zone_maps.md)
            prune = getattr(resp, "_obs_prune", None) or (0, 0)
            # device-join magnitudes ride it too (docs/device_join.md)
            jn = getattr(resp, "_obs_join", None) or (0, 0, 0)
            _obs.OBSERVATORY.record_serve(
                sig, path, m.total_s, rows=rows, encoding=encoding,
                queue_wait_s=m.schedule_wait_s, trace_id=tracker.trace_id,
                desc=desc, blocks_examined=prune[0], blocks_pruned=prune[1],
                join_build_rows=jn[0], join_probe_rows=jn[1],
                join_out_rows=jn[2])

    def _cpu_bytes(self, req: CoprRequest, snap) -> bytes:
        """The CPU-oracle answer to ``req`` off ``snap`` — the byte-identity
        ground truth every device path is held to."""
        stats = Statistics()
        src = MvccScanSource(snap, req.start_ts, req.ranges, statistics=stats)
        return BatchExecutorsRunner(req.dag, src).handle_request().encode()

    def shadow_compare(self, req: CoprRequest, snap, device_data: bytes,
                       path: str) -> bytes | None:
        """Shadow-read verification core (docs/integrity.md): re-execute a
        sampled warm serve on the CPU oracle off the SAME snapshot and byte
        compare.  Returns None on a match (or an inconclusive oracle error);
        on mismatch the backing image is quarantined, the mismatch counts
        under stage=shadow_read, and the CPU bytes return for the caller to
        serve — zero wrong bytes reach the sampled client."""
        with trace.stage("copr.shadow", path=path):
            return self._shadow_compare(req, snap, device_data, path)

    def _shadow_compare(self, req: CoprRequest, snap, device_data: bytes,
                        path: str) -> bytes | None:
        from .integrity import IntegrityMismatch, count_mismatch, integrity_fatal

        # the device answer is an exposure: it is held across the oracle
        # re-execution, and a concurrent fold mutating its backing buffer
        # would turn a true mismatch into a phantom (or mask one)
        _bufsan.export("shadow_read", device_data, site="endpoint.shadow_compare")
        try:
            try:
                cpu = self._cpu_bytes(req, snap)
            except Exception:  # noqa: BLE001 — locks/races: inconclusive, not bad
                self.shadow.note(path, "error")
                return None
        finally:
            _bufsan.release(device_data, site="endpoint.shadow_compare")
        if cpu == device_data:
            self.shadow.note(path, "ok")
            return None
        self.shadow.note(path, "mismatch")
        count_mismatch("shadow_read")
        region_id = (req.context or {}).get("region_id")
        if region_id is None:
            region = getattr(snap, "region", None)
            region_id = getattr(region, "id", None)
        if self.region_cache is not None and region_id is not None:
            self.region_cache.quarantine_region(
                region_id, ranges=req.ranges, stage="shadow_read",
                detail={"path": path},
            )
        if integrity_fatal():
            raise IntegrityMismatch(
                f"shadow read mismatch on region {region_id} path={path}"
            )
        return cpu

    def overload_snapshot(self) -> dict:
        """The /debug/overload + ``ctl.py overload`` view: per-tenant
        bucket levels and effective rates, shed/defer counts, adaptive
        controller state, and HBM partition occupancy."""
        if self.overload is None:
            return {"enabled": False, "wired": False}
        return self.overload.snapshot()

    def integrity_snapshot(self) -> dict:
        """The /debug/integrity + ``ctl.py integrity`` view: per-image
        fingerprints, the quarantine ledger, scrubber cadence/progress, and
        shadow-read sample/mismatch counts."""
        rc = self.region_cache
        out = {
            "enabled": rc is not None,
            "shadow": self.shadow.snapshot(),
            "scrubber": self.scrubber.snapshot() if self.scrubber is not None else None,
        }
        if rc is not None:
            out["fingerprints"] = rc.image_fingerprints()
            out["quarantine"] = list(rc.quarantine_ledger)
        return out

    @staticmethod
    def count_follower_read(path: str) -> None:
        """Follower/stale-served DAG requests, by serving path — the series
        that shows coprocessor read traffic scaling with replica count
        instead of leader count (docs/stale_reads.md)."""
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_follower_read_total",
            "DAG requests served off a stale-read (follower-eligible) "
            "snapshot, by serving path",
        ).inc(path=path)

    def _tracked(self, tracker, handler, req: CoprRequest) -> CoprResponse:
        resp = handler(req, tracker)
        resp.metrics = tracker.on_finish(scanned_keys=tracker.metrics.scanned_keys).to_dict()
        self.slow_log.observe(tracker)
        return resp

    def handle_streaming_request(self, req: CoprRequest, rows_per_stream: int = 1024):
        """Yield CoprResponse frames (endpoint.rs streaming path — always the
        CPU pipeline; the device path answers whole queries)."""
        if req.tp != REQ_TYPE_DAG:
            raise ValueError("streaming supports DAG requests only")
        resolve_encode_type(req)
        snap = self.engine.snapshot(stale_read_ctx(req))
        src = MvccScanSource(snap, req.start_ts, req.ranges, statistics=Statistics())
        # frames flush at whole response chunks — align the chunk size so
        # streams actually split at the requested granularity (on a copy:
        # the caller's DagRequest framing must not change).  The copy keeps
        # the negotiated encoding: large TypeChunk results stream as
        # column-slab frames on the same flush cadence.
        dag = DagRequest(
            executors=req.dag.executors,
            output_offsets=req.dag.output_offsets,
            chunk_rows=min(req.dag.chunk_rows, rows_per_stream),
            encode_type=req.dag.encode_type,
        )
        runner = BatchExecutorsRunner(dag, src)
        for resp in runner.handle_streaming_request(rows_per_stream):
            parts, enc_tp = self._encode_response(resp)
            yield CoprResponse(None, from_device=False, data_parts=parts,
                               encode_type=enc_tp)

    def _handle_analyze(self, req: CoprRequest, tracker=None) -> CoprResponse:
        from . import analyze as az
        from .dag import build_executors
        from .tracker import Tracker

        tracker = tracker or Tracker()
        tracker.on_schedule()
        snap = self.engine.snapshot(stale_read_ctx(req))
        tracker.on_snapshot_finished()
        src = MvccBatchScanSource(snap, req.start_ts, req.ranges)
        executor = build_executors(req.dag, src)
        n_cols = len(executor.schema())
        params = req.context.get("analyze", {}) if req.context else {}
        result = az.analyze_columns(
            executor,
            n_cols,
            sample_size=params.get("sample_size", 10000),
            max_buckets=params.get("max_buckets", 256),
        )
        tracker.metrics.scanned_keys = result.sampled_rows
        out = bytearray()
        from ..util import codec as c

        out += c.encode_var_u64(result.sampled_rows)
        out += c.encode_var_u64(n_cols)
        for ci in range(n_cols):
            h = result.histograms[ci]
            out += c.encode_var_u64(h.ndv)
            out += c.encode_var_u64(len(h.buckets))
            for b in h.buckets:
                out += c.encode_compact_bytes(b.lower)
                out += c.encode_compact_bytes(b.upper)
                out += c.encode_var_u64(b.count)
                out += c.encode_var_u64(b.repeats)
            out += c.encode_var_u64(result.fm_sketches[ci].ndv())
            out += c.encode_var_u64(result.cm_sketches[ci].count)
        return CoprResponse(bytes(out))

    def _handle_checksum(self, req: CoprRequest, tracker=None) -> CoprResponse:
        """MVCC-consistent checksum: the logical rows visible at start_ts
        (checksum.rs scans through the snapshot store), so large values in
        CF_DEFAULT are covered and replicas with different physical version
        histories but identical logical data agree.

        Warm path (docs/integrity.md): a resident region image of exactly
        these ranges carries the XOR-folded per-row crc64 — byte-identical
        to this scan's answer by construction — so ADMIN CHECKSUM over warm
        data costs zero engine reads; anything else falls back to the
        CPU-oracle scan."""
        from . import analyze as az
        from ..storage.mvcc import ForwardScanner
        from ..storage.txn_types import Key
        from ..util.metrics import REGISTRY
        from .tracker import Tracker

        tracker = tracker or Tracker()
        tracker.on_schedule()
        snap = self.engine.snapshot(stale_read_ctx(req))
        tracker.on_snapshot_finished()
        warm = None
        if self.region_cache is not None:
            warm = self.region_cache.checksum_serve(
                snap, self._snap_context(req, snap), req.ranges, req.start_ts
            )
        if warm is not None:
            checksum, total_kvs, total_bytes = warm
            r = {"checksum": checksum, "total_kvs": total_kvs,
                 "total_bytes": total_bytes}
        else:
            kvs = []
            for start, end in req.ranges:
                kvs.extend(
                    ForwardScanner(snap, req.start_ts, Key.from_raw(start), Key.from_raw(end))
                )
            r = az.checksum_range(kvs)
            tracker.metrics.scanned_keys = r["total_kvs"]
        REGISTRY.counter(
            "tikv_coprocessor_checksum_total",
            "Coprocessor Checksum (tp=105) requests, by serving path",
        ).inc(path="warm" if warm is not None else "cold")
        from ..util import codec as c

        out = (
            c.encode_u64(r["checksum"])
            + c.encode_var_u64(r["total_kvs"])
            + c.encode_var_u64(r["total_bytes"])
        )
        return CoprResponse(out, from_cache=warm is not None)

    def handle_batch(self, reqs: list[CoprRequest]) -> list["CoprResponse"]:
        """K coprocessor requests answered together (the batch_coprocessor /
        batch_commands serving shape, kv.rs:891), routed through the unified
        read scheduler (scheduler.py): device-eligible aggregation DAGs fuse
        into as few XLA dispatches as their plan signatures allow — same
        plan across regions stacks into ONE cross-region program over the
        cached region images; different plans over the same region view fuse
        the old way (jax_eval.run_batch_cached).  Anything ineligible falls
        back to per-request handling; responses are byte-identical either
        way."""
        for r in reqs:
            resolve_encode_type(r)
        if len(reqs) >= 2 and self.device_enabled() and self._gate_ok("batch"):
            from ..util.failpoint import fail_point

            fail_point("coprocessor_parse_request")
            return self.scheduler.run_batch(reqs)
        return [self.handle_request(r) for r in reqs]

    def handle_batch_errors(
        self, reqs: list[CoprRequest]
    ) -> tuple[list["CoprResponse | None"], list[BaseException | None]]:
        """``handle_batch`` with per-slot error isolation: returns parallel
        (results, errors) lists instead of raising on the first bad slot, so
        the service layer keeps every computed response when one rider's
        deadline expires in the queue (re-serving the whole batch would
        double the device work the shed was meant to save)."""
        for r in reqs:
            resolve_encode_type(r)
        if len(reqs) >= 2 and self.device_enabled() and self._gate_ok("batch"):
            from ..util.failpoint import fail_point

            fail_point("coprocessor_parse_request")
            return self.scheduler.run_batch(reqs, return_errors=True)
        results: list[CoprResponse | None] = [None] * len(reqs)
        errors: list[BaseException | None] = [None] * len(reqs)
        for i, r in enumerate(reqs):
            try:
                results[i] = self.handle_request(r)
            except Exception as e:  # noqa: BLE001 — per-slot isolation
                errors[i] = e
        return results, errors

    def _bind(self, dag: DagRequest, split=None, tasks: int = 1):
        """``(evaluator, parameters)`` for a request's plan: the plan is
        taken apart into its shape and its literals (copr/plan_shape.py),
        and the shape finds its evaluator, which holds the jitted programs
        (one a shape and geometry, whatever the literals) and no literal;
        the request's own travel beside it into every entry point.  Stage
        ``copr.bind`` is what that costs a read; ``split`` is the pair where
        the caller made it already (the read scheduler keys its slots by it),
        ``tasks`` the device-served tasks this resolution stands for."""
        with trace.stage("copr.bind") as st:
            shape, params = split if split is not None else _plan_shape.split(dag)
            ev = self._evaluators.get(shape)
            outcome = "reused"
            if ev is None:
                st.tag(built=1)
                outcome = "built"
                kw = {} if self.block_rows is None else {"block_rows": self.block_rows}
                ev = jax_eval.JaxDagEvaluator(
                    _plan_shape.shape_dag(dag), breaker=self.breaker, **kw)
                self._evaluators[shape] = ev
                self.plan_shapes_built += 1
                while len(self._evaluators) > 64:
                    self._evaluators.pop(next(iter(self._evaluators)))
            _PLAN_SHAPES.inc(tasks, outcome=outcome)
        return ev, params

    def device_enabled(self) -> bool:
        return self.enable_device and self._gate_ok("device")

    def set_enable_device(self, on: bool) -> None:
        """Online toggle (POST /config coprocessor.enable_device)."""
        self.enable_device = bool(on)

    def set_block_rows(self, n: int) -> None:
        """Online geometry change (POST /config coprocessor.block_rows /
        the auto-tuner).  Evaluators pad every block to block_rows and warm
        images were built at the old geometry, so both are dropped: the
        next serve rebuilds at the new size.  Bounds are enforced by
        TikvConfig.validate before this is ever called."""
        n = int(n)
        if n == self.block_rows:
            return
        self.block_rows = n
        self._evaluators.clear()
        self._mesh_runners.clear()
        if self.region_cache is not None:
            self.region_cache.block_rows = n
            for rid in list(self.region_cache.warm_region_ids()):
                self.region_cache.invalidate_region(rid, reason="geometry")

    def _route_for(self, req: CoprRequest):
        """Consult the cost router for this request's execution path
        (docs/cost_router.md).  None means routing is unavailable (sig
        walk failed) — the static ladder stands."""
        router = self.cost_router
        if router is None:
            return None
        from . import encoding as _encoding
        from . import observatory as _obs

        with trace.stage("copr.route") as st:
            try:
                sig, desc = _obs.dag_sig(req.dag)
            except Exception:  # noqa: BLE001 — routing must not fail serving
                return None
            cands = _encoding.candidate_paths(
                req.dag, device_ok=True,
                mesh_ok=self._mesh_would_serve(req.dag))
            route = router.route(sig, cands, desc=desc)
            st.tag(path=route.path)
        return route

    def _note_route_delta(self, delta_ms: float, best_ms: float | None) -> None:
        if self.overload is not None:
            self.overload.note_route_delta(delta_ms, best_ms)

    def cost_router_snapshot(self) -> dict:
        """The ``/debug/cost_router`` + ``ctl.py cost-router`` view: router
        decision counts/ring and the geometry tuner's knobs, in-flight
        change, and keep/revert history."""
        if self.cost_router is None:
            return {"enabled": False, "wired": False}
        out = {"router": self.cost_router.snapshot()}
        if self.geometry_tuner is not None:
            out["tuner"] = self.geometry_tuner.snapshot()
        return out

    def _gate_ok(self, what: str) -> bool:
        if self.feature_gate is None:
            return True
        from ..pd.feature_gate import BATCH_FUSION, DEVICE_COPROCESSOR, MESH_SERVING

        feat = {"device": DEVICE_COPROCESSOR, "mesh": MESH_SERVING,
                "batch": BATCH_FUSION}[what]
        return self.feature_gate.can_enable(feat)

    def _run_sharded_cached(self, ev, cache, params=()):
        """Warm cached serving THROUGH the mesh: run the plan over the
        image's device-local shards via the sharded cross-region launcher
        (one region = one slot; a block-spread huge region uses every chip).
        Returns the SelectResponse, or None on a documented decline — an
        aggregate with no mesh merge rule, unstable group dictionaries —
        which serves per-request on the single-device warm path.  Real
        device failures count against the MESH breaker path and decline to
        the single-device warm path (which can still serve the bytes) —
        tripping every unary request to CPU for one bad collective would
        throw away a working single-device fallback."""
        from ..parallel.mesh import mesh_mergeable
        from ..util.metrics import REGISTRY
        from . import jax_eval as _je
        from .tracker import count_path_fallback

        if not self.shard_cache:
            return None
        if ev.plan.agg is None or not mesh_mergeable(ev.device_aggs):
            count_path_fallback("mesh", "no_merge_rule")
            return None
        if not self.breaker.allow("mesh"):
            count_path_fallback("mesh", "breaker_open")
            return None
        # A single-owner image still routes here on purpose: SPMD means the
        # other devices scan only zero-pad slabs (same wall time as the
        # owner) plus a tiny-carry collective — while the single-device
        # warm path would REBUILD a full default-device pin, paying the
        # whole-image transfer the owner placement exists to avoid.
        try:
            pending = _je.launch_xregion_sharded(ev, [cache], self.mesh, params)
            resp = pending.finalize()[0]
        except ValueError:
            # documented decline (no merge rule surfaced late, empty blocks)
            self.breaker.release_probe("mesh")
            count_path_fallback("mesh", "ineligible")
            return None
        except TypeError as exc:
            # a type/shape error at trace time is a bug in the program, not
            # a device fault: every call would throw it, so no fallback
            self.breaker.release_probe("mesh")
            raise RungProgramError(f"mesh rung: {exc}") from exc
        except Exception as exc:  # noqa: BLE001 — single-device path serves
            self.breaker.record_failure("mesh")
            self.device_fallbacks += 1
            self.last_device_error = repr(exc)
            count_path_fallback("mesh", "device_error")
            return None
        self.breaker.record_success("mesh")
        resp._obs_path = "mesh"  # observatory path marker
        REGISTRY.counter(
            "tikv_coprocessor_mesh_cache_hit_total",
            "Warm cached requests served mesh-sharded (replaces the PR-2 "
            "mesh_bypass{reason=cache})",
        ).inc()
        return resp

    def _mesh_would_serve(self, dag: DagRequest) -> bool:
        """True only when the mesh path would actually take this DAG (mesh
        present with real devices, gate open, AND the plan is mesh-runnable)
        — the sharded warm route must not probe plans the mesh would have
        declined anyway."""
        if (self.mesh is None or getattr(self.mesh, "size", 1) <= 1
                or getattr(self.mesh, "devices", None) is None):
            return False
        from .dag import Aggregation

        # cheap pre-filter: the mesh runner only takes aggregation DAGs, so
        # cached scan/selection traffic (the common warm path) never pays
        # the runner-construction probe below
        if not any(isinstance(e, Aggregation) for e in dag.executors):
            return False
        try:
            return self._mesh_evaluator_for(dag) is not None
        except Exception:  # noqa: BLE001 — a broken mesh backend is "no"
            return False

    def _mesh_evaluator_for(self, dag: DagRequest):
        """A MeshServingRunner when the mesh has >1 device and the DAG is an
        eligible aggregation; None routes to the single-device evaluator."""
        if self.mesh is None or self.mesh.size <= 1 or not self._gate_ok("mesh"):
            return None
        from ..parallel.mesh import MeshServingRunner
        from ..server import wire
        from .dag_wire import dag_to_wire

        key = wire.dumps(dag_to_wire(dag))
        runner = self._mesh_runners.get(key, _MESH_UNCHECKED)
        if runner is _MESH_UNCHECKED:
            try:
                runner = MeshServingRunner(dag, self.mesh)
            except ValueError:
                runner = None  # not an aggregation DAG — cached so repeat
                # requests skip re-probing (single-device path)
            self._mesh_runners[key] = runner
            while len(self._mesh_runners) > 16:
                self._mesh_runners.pop(next(iter(self._mesh_runners)))
        return runner

    def _region_cache_for(self, req: CoprRequest, snap, tracker):
        """Resolve the request against the region column cache.  Returns
        (filled block cache | None, outcome) and stamps the tracker with the
        outcome + delta size so responses carry the cache behavior."""
        if self.region_cache is None:
            return None, ""
        from .dag import TableScan

        execs = req.dag.executors if req.dag is not None else []
        if not execs or type(execs[0]) is not TableScan:
            return None, ""
        context = self._snap_context(req, snap)
        apply_index = context.get("apply_index")
        rp = getattr(snap, "read_progress", None)
        if rp is not None:
            # RegionReadProgress pairing invariant (docs/stale_reads.md): a
            # stale snapshot's claimed apply_index sits at/above the pair's
            # required index (raftkv refuses otherwise) and the DAG reads
            # at/below the paired watermark — which is exactly why the
            # (region_id, epoch, apply_index) image key stays correct for
            # follower warm serving: the image can never claim data the
            # watermark hasn't covered
            assert apply_index is not None and apply_index >= rp[1], \
                f"stale snapshot apply_index {apply_index} below required {rp[1]}"
            assert req.start_ts <= rp[0], \
                f"stale DAG read at {req.start_ts} above resolved ts {rp[0]}"
        cache, outcome, delta_rows = self.region_cache.serve(
            snap, context, execs[0].columns_info, req.ranges, req.start_ts
        )
        if outcome != "off":
            tracker.metrics.region_cache = outcome
            tracker.metrics.region_cache_delta_rows = delta_rows
        return cache, outcome

    @staticmethod
    def _snap_context(req: CoprRequest, snap) -> dict:
        """The request context enriched from the snapshot: a raft
        RegionSnapshot carries its own identity and data version — serving
        paths need no context plumbing; explicit context still wins (tests,
        embedded use over plain engines)."""
        context = dict(req.context or {})
        region = getattr(snap, "region", None)
        if region is not None:
            context.setdefault("region_id", region.id)
            context.setdefault(
                "region_epoch", (region.epoch.conf_ver, region.epoch.version)
            )
        apply_index = getattr(snap, "apply_index", None)
        if apply_index is not None:
            context.setdefault("apply_index", apply_index)
        return context

    def _block_cache_for(self, req: CoprRequest):
        """Decoded-block cache, valid only while the region data is unchanged:
        the caller must supply a data version (apply index / resolved ts) in
        context["cache_version"]; without one, every request is cold (the
        reference's cop-cache likewise keys on region apply version,
        cache.rs:10).  Deliberately NOT defaulted from the snapshot's
        apply_index: every ad-hoc start_ts would mint a fresh entry and
        churn warm ones out of the shared LRU — the region column cache is
        the apply_index-keyed layer (docs/write_path.md)."""
        version = (req.context or {}).get("cache_version")
        if version is None:
            return None
        key = (
            req.context.get("region_id"),
            tuple(req.ranges),
            req.start_ts,
            version,
        )
        return self.cop_cache.get_or_create(key)
