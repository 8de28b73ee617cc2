"""Unified coprocessor read scheduler: cross-region continuous batching.

The reference serves coprocessor reads through a unified read pool (yatp,
``src/read_pool.rs``): many regions' requests multiplex onto shared workers
with high/normal/low priorities.  This module is the device-serving
re-expression: instead of sharing CPU workers, concurrent device-eligible
DAG requests share **XLA dispatches**.

* Requests are keyed by their **plan signature** (``copr/plan_shape.py`` —
  scalar ops normalized through ``sig_map`` so wire-level ScalarFuncSig
  spellings and kernel names key identically), held as the pair
  ``(shape signature, parameters)`` that ``plan_shape.split`` returns: the
  whole pair is the identity of a slot and of an xregion group, its first
  half (the plan without its selection's literals) keys what is per
  program: evaluators and the eligibility memo.
* Requests with the same signature but different regions batch into ONE
  device program: each region's cached column image (PR 1's
  ``region_cache.py``) is padded to a shared block geometry and stacked
  along a new leading region axis (``jax_eval.launch_xregion_cached``),
  with per-region row-count masks so padding never changes results.
* With a multi-device mesh the scheduler is DEVICE-AWARE: the region cache
  places images on owner devices, slots pack per owner, and the batch runs
  as one ``shard_map`` program over device-local shards
  (``jax_eval.launch_xregion_sharded`` → ``parallel/mesh.py``), partial
  aggregate states merging over ICI.  Padding-shed then accounts for the
  (devices × slabs) geometry — the slab axis rounds up to the mesh's
  per-device maximum — and per-device occupancy is reported.  Double-
  buffered prepare fills the NEXT batch's shards on their owner devices
  while the current batch executes.
* Requests over the SAME cached region view with different plans keep the
  old fused path (``jax_eval.run_batch_cached``), now living here instead
  of ``endpoint._try_fused_batch``.
* Everything else — ineligible plans, cold/unresolvable caches, shed
  requests — serves through ``endpoint.handle_request`` unchanged, so the
  scheduler only ever *removes* dispatches, never changes bytes.

Continuous-batching semantics:

* three priority lanes (``high`` / ``normal`` / ``low``, mirroring the
  read-pool priorities) with per-lane max-wait knobs;
* a bounded queue — beyond ``max_queue`` pending requests, admission
  control sheds new arrivals straight to the per-request path;
* ``max_batch`` bounds one program's fan-in; oversize groups chunk;
* a padding budget sheds block-count outliers from a cross-region batch
  (one giant region would otherwise pad every small region up to its
  geometry — the giant serves per-request, where its size already
  amortizes the dispatch);
* double-buffering: batch N executes on device (async dispatch) while the
  host runs batch N+1's cache resolution — the region cache's fill/delta
  pass — and batch N's pull happens only after N+1 is launched.

Metrics: queue depth, batch occupancy, padding waste, per-lane wait — see
``docs/copr_scheduler.md`` and the coprocessor Grafana dashboard.
"""

from __future__ import annotations

import threading
import time
from collections import Counter
from dataclasses import dataclass, field

from ..analysis.sanitizer import make_condition, make_lock
from ..util import trace
from ..util.metrics import REGISTRY
from . import observatory as _obs
from . import overload as _overload
from ..util.retry import DeadlineExceeded, ServerBusyError, deadline_from_context
from . import jax_eval
from .dag import Aggregation
from .endpoint import (
    REQ_TYPE_DAG,
    CoprRequest,
    CoprResponse,
    resolve_encode_type,
    stale_read_ctx,
)
from .plan_shape import split
from .region_cache import _epoch_of

LANES = ("high", "normal", "low")

# what the dispatcher was doing while a rider waited (docs/tracing.md, "What
# a rider waited behind"): the activities of its trace.WaitClock, in order
BEHIND = ("linger", "repair", "device", "serve")
_LINGER, _REPAIR, _DEVICE, _SERVE = range(4)
_WAIT_BEHIND = tuple(
    REGISTRY.counter(
        "tikv_coprocessor_sched_wait_behind_seconds_total",
        "Queue wait before dispatch, by what the dispatcher did meanwhile",
    ).labels(behind=behind)
    for behind in BEHIND)


def _behind_of(stage: str) -> int | None:
    """Which of ``BEHIND`` the dispatcher's time inside ``stage`` counts as:
    ``repair`` in ``cache.repair`` and ``fill.*``, ``device`` in
    ``device.*``; None for any other stage, whose time counts as what the
    dispatcher was doing around it (``serve``)."""
    if stage == "cache.repair" or stage.startswith("fill."):
        return _REPAIR
    if stage.startswith("device."):
        return _DEVICE
    return None


@dataclass
class SchedulerConfig:
    """Admission-control knobs (read_pool.rs's pool sizing analog)."""

    max_batch: int = 64            # regions/queries fused into one program
    max_queue: int = 256           # pending cap before admission sheds
    padding_budget: float = 0.5    # max wasted fraction of padded block slots
    # per-lane linger: the LONGEST a partial batch waits for more riders.
    # Where the store counts its inbound reads (watch_inbound) the wait of
    # riders that can share a program ends as soon as no read is on its way;
    # without that count, and for a rider alone of its plan, it runs to the end
    max_wait_s: float = 0.004
    high_max_wait_s: float = 0.001
    low_max_wait_s: float = 0.02
    # busy_reject=True turns queue-full admission into a ServerIsBusy-style
    # REJECTION carrying a retry-after hint (honored by util.retry), instead
    # of silently serving on the caller's thread — rejecting is the right
    # call when the store is saturated: the direct path would add load
    # exactly when there is none to spare
    busy_reject: bool = False
    busy_retry_after_s: float = 0.05
    # lane ceiling for client-declared priorities — SERVER policy, applied
    # even with no overload control wired (docs/robustness.md "Overload"):
    # "high" admits every declared lane (historical behavior); "normal"
    # stops clients from jumping the high lane.  A wired OverloadControl's
    # per-tenant ceilings clamp further.
    max_priority: str = "high"

    def wait_for(self, lane: str) -> float:
        if lane == "high":
            return self.high_max_wait_s
        if lane == "low":
            return self.low_max_wait_s
        return self.max_wait_s


def _lane_of(req: CoprRequest) -> str:
    lane = (req.context or {}).get("priority", "normal")
    return lane if lane in LANES else "normal"


def _clamped_lane(req: CoprRequest, cfg: SchedulerConfig, overload) -> str:
    """The request's EFFECTIVE lane: the client-declared priority clamped
    to the global ceiling (``cfg.max_priority``) and, with an overload
    control wired, the tenant's configured ceiling — the client never
    picks a higher lane than policy grants it.  Demotions are counted per
    tenant (tikv_overload_demote_total)."""
    lane = _lane_of(req)
    ceiling = cfg.max_priority
    if overload is not None:
        tc = overload.lane_ceiling(req.context)
        if tc is not None:
            ceiling = _overload.clamp_lane(ceiling, tc)
    eff = _overload.clamp_lane(lane, ceiling)
    if eff != lane:
        _overload.count_demotion(_overload.tenant_of(req.context), eff)
    return eff


@dataclass
class _Item:
    req: CoprRequest
    index: int
    lane: str = "normal"
    ticket: "_Ticket | None" = None
    enqueue_t: float = 0.0
    sig: tuple | None = None  # plan signature: at admission, or grouping
    # absolute monotonic deadline (context "deadline"/"timeout_ms", see
    # util.retry.deadline_from_context); expired items shed BEFORE dispatch
    deadline: float | None = None
    # trace handoff (docs/tracing.md): the submitting thread's span context,
    # so dispatcher-side work lands in the request's own trace; batch_ref
    # names the shared device-dispatch span the item coalesced into
    trace_ctx: dict | None = None
    batch_ref: str | None = None
    # the dispatcher's wait clock as of enqueue_t, while the plane is on
    behind0: list | None = None


class _Ticket:
    """One continuous-mode submission: the caller blocks on ``done`` while
    the dispatcher batches and serves.  ``direct`` hands the request back
    to the caller's thread (shed / ineligible work must not serialize the
    whole dispatcher behind one slow per-request execution)."""

    __slots__ = ("done", "resp", "error", "direct", "handed_t")

    def __init__(self):
        self.done = threading.Event()
        self.resp: CoprResponse | None = None
        self.error: BaseException | None = None
        self.direct = False
        self.handed_t = 0.0

    def hand_back(self) -> None:
        """Wake the caller; the instant is kept so that the caller can say
        how long its thread took to come back (stage ``sched.handoff``)."""
        self.handed_t = time.perf_counter()
        self.done.set()


@dataclass
class _Slot:
    """One distinct (plan, region view) execution slot in a micro-batch.
    Multiple identical requests share the slot (and its response bytes)."""

    items: list = field(default_factory=list)
    cache: object = None
    outcome: str = ""
    # shadow-read sampling (docs/integrity.md): set at resolve time to the
    # slot's snapshot when the sampler picks this warm serve — the finalize
    # pass then byte-compares the device answer against the CPU oracle
    shadow_snap: object = None


class CoprReadScheduler:
    """The unified read scheduler over one :class:`~.endpoint.Endpoint`."""

    def __init__(self, endpoint, config: SchedulerConfig | None = None):
        self.ep = endpoint
        self.cfg = config or SchedulerConfig()
        self._mu = make_condition("copr.scheduler", make_lock("copr.scheduler"))
        self._queues: dict[str, list[_Item]] = {lane: [] for lane in LANES}
        self._running = False
        self._thread: threading.Thread | None = None
        # the dispatcher's own time by activity: what a queued rider waits
        # behind (trace.WaitClock, bound to the dispatch thread)
        self._clock = trace.WaitClock(len(BEHIND), _behind_of, _SERVE)
        # the store's count of reads on their way here (util/inbound.py);
        # None = nobody counts them, so a partial batch lingers to its end
        self._inbound = None
        # per-shape memo of device eligibility (supports() re-analyzes the
        # whole plan; the literals do not change its verdict).  Evaluators
        # are the endpoint's, found by the same shape (Endpoint._bind)
        self._memo_mu = make_lock("copr.scheduler.memo")
        self._supports: dict[tuple, bool] = {}

    def reconfigure(self, changed: dict) -> None:
        """Online scheduler geometry (POST /config ``coprocessor.*`` via
        the ConfigController, and the geometry auto-tuner): the per-lane
        linger windows.  Values were validated by ``TikvConfig.validate``
        before dispatch; lanes read ``cfg.wait_for`` per pass, so changes
        apply on the next dispatch decision."""
        for key, value in changed.items():
            if key == "max_wait_s":
                self.cfg.max_wait_s = float(value)
            elif key == "high_max_wait_s":
                self.cfg.high_max_wait_s = float(value)
            elif key == "low_max_wait_s":
                self.cfg.low_max_wait_s = float(value)

    def watch_inbound(self, inbound) -> None:
        """Called where the store wires its server to this scheduler
        (``server/standalone.py``, ``server/cluster.py``): ``inbound`` counts
        the reads that are off the socket and not yet in a lane, and tells
        :meth:`_inbound_drained` when the last of them went elsewhere."""
        self._inbound = inbound
        inbound.on_drained = self._inbound_drained

    def _inbound_drained(self) -> None:
        # the unlocked look saves the lock on every read that is not ours;
        # an enqueue it misses wakes the dispatcher itself
        if any(self._queues.values()):
            with self._mu:
                self._mu.notify_all()

    # -- synchronous entry (endpoint.handle_batch / batch_coprocessor) -----

    def run_batch(self, reqs: list[CoprRequest], *, return_errors: bool = False):
        for r in reqs:
            resolve_encode_type(r)
        tctx = trace.current_context()
        # per-tenant quota admission (docs/robustness.md "Overload"): an
        # over-quota rider fails ITS slot typed (ServerBusyError with the
        # bucket's refill deficit) without deferring — a synchronous batch
        # must not sleep per rider — and siblings keep their responses
        ov = getattr(self.ep, "overload", None)
        results: list[CoprResponse | None] = [None] * len(reqs)
        errors: list[BaseException | None] = [None] * len(reqs)
        live: list[tuple[int, CoprRequest]] = []
        for i, r in enumerate(reqs):
            if ov is not None:
                try:
                    ov.admit(r.context, where="batch", wait=False)
                except ServerBusyError as exc:
                    self._count_shed("tenant_quota")
                    errors[i] = exc
                    continue
            live.append((i, r))
        items = [
            _Item(req=r, index=j, lane=_clamped_lane(r, self.cfg, ov),
                  deadline=deadline_from_context(r.context), trace_ctx=tctx)
            for j, (_i, r) in enumerate(live)
        ]
        with trace.shared([tctx]):
            sub_results, sub_errors = self._serve(items)
        for (i, _r), res, err in zip(live, sub_results, sub_errors):
            results[i] = res
            errors[i] = err
        if return_errors:
            # per-slot surface (service.coprocessor_batch): computed
            # responses survive a sibling slot's failure — one expired
            # deadline must not discard K-1 finished answers
            return results, errors
        first = next((e for e in errors if e is not None), None)
        if first is not None:
            # the pre-scheduler handle_batch aborted on the first raising
            # request; callers of the raising surface re-serve per slot —
            # keep that contract for the synchronous surface
            raise first
        return results

    # -- continuous entry (unary requests coalescing across clients) -------

    @property
    def running(self) -> bool:
        return self._running

    def start(self) -> None:
        with self._mu:
            if self._running:
                return
            self._running = True
            self._thread = threading.Thread(
                target=self._dispatch, daemon=True, name="copr-sched"
            )
            self._thread.start()

    def stop(self) -> bool:
        """False where the dispatch thread was given up on, still inside a
        task it serves itself."""
        with self._mu:
            if not self._running:
                return True
            self._running = False
            self._mu.notify_all()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
            return not t.is_alive()
        return True

    def execute(self, req: CoprRequest, timeout: float | None = None) -> CoprResponse:
        """Continuous-mode unary entry: enqueue into the request's priority
        lane and wait for the batch that serves it.  Falls back to the
        direct path when the scheduler is stopped, the request is not
        batchable, or admission control sheds it."""
        # encoding negotiation BEFORE admission: an unsupported chunk plan
        # must batch (and key) as its datum twin, never reach an evaluator
        resolve_encode_type(req)
        deadline = deadline_from_context(req.context)
        if deadline is not None and time.monotonic() >= deadline:
            # dead on arrival: admission control sheds it before it costs a
            # queue slot, let alone a device dispatch
            self._count_deadline("admission")
            raise DeadlineExceeded("deadline expired before admission")
        # stale-read admission (docs/stale_reads.md): a read_ts above this
        # replica's RegionReadProgress raises DataNotReady HERE — before a
        # queue slot, a snapshot, or any device dispatch — so the client's
        # watermark-aware backoff starts immediately
        self._check_stale_ready(req)
        # per-tenant quota admission (docs/robustness.md "Overload"): over-
        # quota work defers a bounded wait on THIS caller's thread, then
        # sheds typed with the bucket's refill deficit as retry_after_s —
        # before it can cost a queue slot, a snapshot, or a device dispatch
        ov = getattr(self.ep, "overload", None)
        if ov is not None:
            try:
                ov.admit(req.context, where="sched")
            except ServerBusyError:
                self._count_shed("tenant_quota")
                raise
        sig = None
        if self._running and self.ep._gate_ok("batch"):
            sig = self._batchable_sig(req)
        if sig is None:
            # the BATCH_FUSION gate guards this path exactly like
            # handle_batch: a mixed-version cluster keeps fusion off
            self._count_coalesce("bypass")
            return self.ep.handle_request(req)
        enqueue_t = time.perf_counter()
        item = _Item(req=req, index=0, lane=_clamped_lane(req, self.cfg, ov),
                     ticket=_Ticket(), sig=sig, enqueue_t=enqueue_t,
                     deadline=deadline,
                     behind0=self._clock.read(enqueue_t) if trace.enabled()
                     else None)
        # queue-lane span (docs/tracing.md): covers enqueue→batch-completion
        # on the submitting thread; the dispatcher stamps dispatcher-side
        # spans into this trace via the captured context
        with trace.span("sched.queue", lane=item.lane) as sp:
            item.trace_ctx = sp.context if sp else None
            depth = 0
            with self._mu:
                # re-check under the lock: a stop() racing this enqueue drains
                # the queues once — anything appended after that drain would
                # never be served and the caller would block forever
                depth = sum(len(q) for q in self._queues.values())
                # under adaptive pressure the EFFECTIVE cap shrinks with the
                # controller's scale, and queue-full becomes a busy-typed
                # rejection even with the static busy_reject off — evidence-
                # based shedding (docs/robustness.md "Overload")
                cap = ov.queue_cap(self.cfg.max_queue) if ov is not None \
                    else self.cfg.max_queue
                busy = False
                if not self._running:
                    do_direct = True
                elif depth >= cap:
                    if self.cfg.busy_reject or (
                            ov is not None and ov.pressure_reject()):
                        # ServerIsBusy with a drain hint: the retry policy
                        # (util.retry) sleeps at least retry_after_s before the
                        # request comes back — backpressure instead of serving
                        # extra work on a saturated store.  Counted under its
                        # own reason: "queue_full" means served on the direct
                        # path, and a rejection is neither served nor direct
                        busy = True
                        do_direct = False
                    else:
                        self._count_shed("queue_full")
                        do_direct = True
                else:
                    do_direct = False
                    self._queues[item.lane].append(item)
                    if self._inbound is not None:
                        # in a lane: no longer on its way (counted down here,
                        # under the lock, so the dispatcher this wakes reads
                        # the queue and the count as one state)
                        self._inbound.parked()
                    self._gauge_depth()
                    self._mu.notify_all()
            if ov is not None:
                # controller feed (outside the dispatcher lock): queue
                # fullness is the adaptive controller's primary evidence
                ov.note_queue(depth, self.cfg.max_queue)
            if busy:
                self._count_shed("busy_reject")
                self._count_coalesce("busy_reject")
                sp.tag(outcome="busy_reject")
                # the hint floor keeps the busy class's backoff hint-
                # dominated even when the knob is set to 0
                raise ServerBusyError(
                    "coprocessor scheduler queue is full",
                    retry_after_s=max(self.cfg.busy_retry_after_s, 0.001),
                )
            if do_direct:
                self._count_coalesce("queue_full")
                sp.tag(outcome="queue_full")
                return self.ep.handle_request(req)
            item.ticket.done.wait(timeout)
            if not item.ticket.done.is_set():
                sp.tag(outcome="timeout")
                raise TimeoutError("scheduler did not serve the request in time")
            # the hand-off between the scheduler's thread and this one: from
            # the ticket's release to this thread running again
            sp.record("sched.handoff", item.ticket.handed_t,
                      time.perf_counter(), stage=True)
            if item.ticket.direct:
                # the dispatcher shed this request back: serve it on OUR thread
                # so one slow per-request path cannot stall every lane — unless
                # its deadline ran out while it waited
                if deadline is not None and time.monotonic() >= deadline:
                    self._count_deadline("direct")
                    sp.tag(outcome="deadline")
                    raise DeadlineExceeded("deadline expired before direct serve")
                self._count_coalesce("direct")
                sp.tag(outcome="direct")
                return self.ep.handle_request(req)
            if item.ticket.error is not None:
                sp.tag(outcome="error")
                raise item.ticket.error
            # served out of a dispatcher micro-batch: the wire-path coalescing
            # outcome (docs/wire_path.md)
            self._count_coalesce("batched")
            sp.tag(outcome="batched")
            if item.batch_ref is not None:
                sp.link("batched_into", item.batch_ref)
            return item.ticket.resp

    def _dispatch(self) -> None:
        """The dispatch thread: the loop, with the scheduler's wait clock
        bound to it."""
        trace.bind_clock(self._clock)
        try:
            self._dispatch_loop()
        finally:
            trace.unbind_clock()

    def _dispatch_loop(self) -> None:
        cfg, clock = self.cfg, self._clock
        while True:
            with self._mu:
                while self._running and not any(self._queues.values()):
                    clock.switch(_LINGER)
                    self._mu.wait(0.5)
                    clock.switch(_SERVE)
                if not self._running:
                    # drain whatever is queued so no caller hangs forever —
                    # but SERVE it below, outside the dispatcher lock: the
                    # drain batch runs engine snapshots and device dispatch,
                    # and holding _mu across those would stall every
                    # execute() caller on a blocked enqueue re-check
                    batch = [it for lane in LANES for it in self._queues[lane]]
                    for lane in LANES:
                        self._queues[lane].clear()
                    self._gauge_depth()
                    stopping = True
                    why = "stop"
                else:
                    stopping = False
                if not stopping:
                    # linger for more riders: until max_batch are queued or
                    # the oldest item's lane deadline, and no longer than any
                    # can still come (no read inbound), unless a rider would
                    # then be served alone: that one is still worth the wait
                    now = time.perf_counter()
                    deadline = min(
                        it.enqueue_t + cfg.wait_for(lane)
                        for lane in LANES
                        for it in self._queues[lane]
                    )
                    total = sum(len(q) for q in self._queues.values())
                    if total >= cfg.max_batch:
                        why = "full"
                    elif now >= deadline:
                        why = "deadline"
                    elif (self._inbound is not None
                          and self._inbound.pending() == 0
                          and self._no_lone_rider()):
                        why = "drained"
                    else:
                        clock.switch(_LINGER)
                        self._mu.wait(min(deadline - now, 0.05))
                        clock.switch(_SERVE)
                        continue
                    batch = []
                    for lane in LANES:  # high lane drains first
                        while self._queues[lane] and len(batch) < cfg.max_batch:
                            batch.append(self._queues[lane].pop(0))
                    self._gauge_depth()
            if batch:
                # the batch's riders are picked up together, now
                self._serve_ticketed(
                    batch, why, None if stopping else time.perf_counter())
            if stopping:
                return

    def _no_lone_rider(self) -> bool:
        """Every queued rider has another of its plan beside it (under
        ``_mu``), so a pass that leaves now serves nobody per request who a
        longer linger might have batched."""
        plans = Counter(it.sig for q in self._queues.values() for it in q)
        return min(plans.values()) >= 2

    def _serve_ticketed(self, batch: list[_Item], why: str,
                        picked: float | None) -> None:
        """Serve a batch taken off the lanes for ``why`` at ``picked`` (None
        when the scheduler stops: no wait is observed)."""
        from ..util.failpoint import fail_point

        for i, it in enumerate(batch):
            it.index = i
        # every rider waits for all of the batch's stages, whichever trace
        # they land in (docs/tracing.md, "Attributed time")
        with trace.shared([it.trace_ctx for it in batch]):
            with trace.batch_stage("sched.pickup"):
                self._count_dispatch(why, len(batch))
                if picked is not None:
                    for it in batch:
                        self._observe_wait(it, picked)
            # chaos/regression hook on the DISPATCHER thread: a seeded sleep
            # here paces batch service so overload tests can saturate the
            # bounded queue deterministically (tests/test_overload.py)
            fail_point("sched_dispatch")
            try:
                results, errors = self._serve(batch)
            except BaseException as exc:  # noqa: BLE001 — scheduler bug: fail all
                for it in batch:
                    it.ticket.error = exc
                    it.ticket.hand_back()
                return
            # per-ticket delivery: one request's lock conflict or decode error
            # must not poison the riders that coalesced into the same batch
            with trace.attach(batch[0].trace_ctx), trace.stage("sched.respond"):
                for it in batch:
                    if it.ticket.done.is_set():
                        continue  # already handed back to its caller (direct)
                    if errors[it.index] is not None:
                        it.ticket.error = errors[it.index]
                    else:
                        it.ticket.resp = results[it.index]
                    it.ticket.hand_back()

    def _check_stale_ready(self, req: CoprRequest, count: bool = True) -> None:
        """Raise DataNotReady for a stale read this replica cannot admit —
        exactly what the engine's snapshot would raise, but WITHOUT freezing
        the engine (RaftKv.check_read_ready).  No-op on engines without the
        probe (plain local engines) and on non-stale contexts."""
        ctx = stale_read_ctx(req)
        if not ctx or not ctx.get("stale_read"):
            return
        ready = getattr(self.ep.engine, "check_read_ready", None)
        if ready is None:
            return
        try:
            ready(ctx)
        except Exception as exc:
            if count:
                # a witness/non-hosting replica refuses NotLeader — that is
                # a routing problem, not watermark lag; keeping the reasons
                # apart keeps the safe_ts-lag dashboards honest
                if type(exc).__name__ == "DataNotReadyError":
                    self._count_shed("data_not_ready")
                else:
                    self._count_shed("stale_not_leader")
            raise

    # -- the scheduler core -------------------------------------------------

    def _serve(self, items: list[_Item]):
        """Returns (results, errors), index-aligned with ``items``: exactly
        one of results[i] / errors[i] is set per item, so callers deliver
        failures per request instead of poisoning the whole batch."""
        results: list[CoprResponse | None] = [None] * len(items)
        errors: list[BaseException | None] = [None] * len(items)
        # deadline shed FIRST: expired work must never reach grouping, let
        # alone a device dispatch — the client has already given up, and a
        # padded slot spent on it would tax every live rider in the batch
        now = time.monotonic()
        expired = [it for it in items
                   if it.deadline is not None and now >= it.deadline]
        for it in expired:
            self._count_deadline("dispatch")
            self._count_shed("deadline")
            errors[it.index] = DeadlineExceeded("deadline expired in queue")
        if expired:
            items = [it for it in items if errors[it.index] is None]
        # stale-read admission at dispatch: a watermark-lagging item fails
        # typed BEFORE grouping — it must never cost a padded batch slot
        not_ready = []
        for it in items:
            try:
                self._check_stale_ready(it.req)
            except Exception as exc:  # noqa: BLE001 — DataNotReady/NotLeader
                errors[it.index] = exc
                not_ready.append(it)
        if not_ready:
            items = [it for it in items if errors[it.index] is None]
        with trace.attach(items[0].trace_ctx if items else None), \
                trace.stage("sched.group", items=len(items)):
            exec_groups, rest = self._group(items)

        # double-buffered pipeline: resolve (host fill/delta) group i while
        # group i-1 executes on device; pull i-1 only after i is launched
        pending = None
        for kind, meta, group in exec_groups:
            if kind == "xregion":
                launched = self._launch_xregion(meta, group, results, errors)
            else:
                launched = self._run_fused(meta, group, results, errors)
            if pending is not None:
                pending(results, errors)
            pending = launched
        if pending is not None:
            pending(results, errors)

        for it in rest:
            self._per_request(it, results, errors, kind="direct")
        return results, errors

    def _group(self, items: list[_Item]):
        """Form the batch's execution groups: ``(exec_groups, rest)`` with
        ``exec_groups`` a list of ("xregion", sig, [slots]) and ("fused",
        key, [items]) in launch order, ``rest`` the items that serve per
        request.  Two or more riders of one plan signature over distinct
        region views are an xregion group: that is the whole rule, and no
        measurement overrides it (PERF.md section 6, PR 32)."""
        # group by plan signature, then by distinct region view within a sig
        by_sig: dict[tuple, dict[tuple, _Slot]] = {}
        rest = []
        for it in items:
            # the gates are asked again; the split admission made is kept
            sig = self._batchable_sig(it.req, it.sig)
            if sig is None:
                rest.append(it)
                continue
            it.sig = sig
            rkey = self._region_key(it.req)
            by_sig.setdefault(sig, {}).setdefault(rkey, _Slot()).items.append(it)

        exec_groups: list[tuple] = []  # ("xregion", dag, [slots]) | ("fused", key, [items])
        leftovers: list[_Item] = []
        for sig, slots in by_sig.items():
            if len(slots) >= 2:
                slot_list = list(slots.values())
                for s in range(0, len(slot_list), self.cfg.max_batch):
                    exec_groups.append(("xregion", sig,
                                        slot_list[s:s + self.cfg.max_batch]))
            else:
                leftovers.extend(next(iter(slots.values())).items)
        # same region view, different plans: the old fused batch shape
        by_cache: dict[tuple, list[_Item]] = {}
        for it in leftovers:
            by_cache.setdefault(self._region_key(it.req), []).append(it)
        for key, group in by_cache.items():
            if len(group) >= 2:
                for s in range(0, len(group), self.cfg.max_batch):
                    exec_groups.append(("fused", key, group[s:s + self.cfg.max_batch]))
            else:
                rest.extend(group)

        # high-priority groups launch first
        lane_rank = {lane: i for i, lane in enumerate(LANES)}
        exec_groups.sort(key=lambda g: min(
            lane_rank[it.lane]
            for it in (sum((s.items for s in g[2]), []) if g[0] == "xregion" else g[2])
        ))
        return exec_groups, rest

    # -- eligibility & keying ----------------------------------------------

    def _batchable(self, req: CoprRequest) -> bool:
        return self._batchable_sig(req) is not None

    def _batchable_sig(self, req: CoprRequest, sig: tuple | None = None) -> tuple | None:
        """The request's plan signature, as the pair ``(shape signature,
        parameters)``, when it can join a device batch, else None.
        supports() verdicts memoize per shape.  ``sig``: the pair, where an
        earlier call made it for this request already."""
        if (req.tp != REQ_TYPE_DAG or req.dag is None
                or not self.ep.device_enabled()
                or not any(isinstance(e, Aggregation) for e in req.dag.executors)):
            return None
        ov = getattr(self.ep, "overload", None)
        if ov is not None and not ov.allow_device(req.context):
            # memory-pressure ladder, last rung (docs/robustness.md): the
            # tenant's HBM partition would not fit even after eviction and
            # pin demotion — its work must not join a device batch (the
            # per-request path CPU-falls-back for the same reason)
            return None
        if sig is None:
            with trace.stage("copr.bind"):
                sig = split(req.dag)
        ok = self._supports.get(sig[0])
        if ok is None:
            ok = jax_eval.supports(req.dag)
            # memo mutation under its own lock: _batchable runs on client
            # threads AND the dispatcher; racing evictions of the same key
            # would KeyError
            with self._memo_mu:
                self._supports[sig[0]] = ok
                while len(self._supports) > 256:
                    self._supports.pop(next(iter(self._supports)))
        return sig if ok else None

    def _region_key(self, req: CoprRequest) -> tuple:
        ctx = req.context or {}
        return (
            ctx.get("region_id"),
            tuple(req.ranges),
            req.start_ts,
            ctx.get("cache_version"),
            ctx.get("apply_index"),
            _epoch_of(ctx.get("region_epoch")),  # normalizes tuple/list/object
        )

    # -- cache resolution (the host-side fill/delta pass) -------------------

    def _resolve_slot(self, slot: _Slot) -> bool:
        """Resolve a slot's region view to a FILLED block cache, running the
        region cache's build/delta pass if needed.  Returns False when the
        slot must shed to the per-request path."""
        # the slot's stages land in its first rider's trace; the code
        # around them is the batch's own bookkeeping
        with trace.batch_stage("sched.resolve"), \
                trace.attach(slot.items[0].trace_ctx):
            return self._resolve_slot_attached(slot)

    def _resolve_slot_attached(self, slot: _Slot) -> bool:
        from .tracker import Tracker

        req = slot.items[0].req
        if self.ep.cm is not None:
            # every item in a slot shares (ranges, start_ts) by construction
            # of _region_key — one lock-range scan covers the whole slot
            from ..storage.txn_types import Key

            for start, end in req.ranges:
                self.ep.cm.read_range_check(
                    Key.from_raw(start), Key.from_raw(end), req.start_ts
                )
        with trace.stage("copr.snapshot"):
            snap = self.ep.engine.snapshot(stale_read_ctx(req))
        tracker = Tracker()
        cache, outcome = self.ep._region_cache_for(req, snap, tracker)
        if cache is None:
            cache = self.ep._block_cache_for(req)
            outcome = ""
        if cache is None:
            return False
        if getattr(snap, "stale", False):
            # warm follower device serving: the slot's whole fan-in rides a
            # stale-read snapshot (docs/stale_reads.md)
            self.ep.count_follower_read("batch")
        if not cache.filled:
            # cold block cache: the first request fills it through the
            # normal per-request path (and keeps its own answer); the rest
            # of the slot then serves from the filled blocks
            filler = slot.items[0]
            resp = self.ep.handle_request(filler.req)
            self._stamp(resp, filler, kind="fill", occupancy=1)
            filler._filled_resp = resp  # type: ignore[attr-defined]
            if not cache.filled or not cache.blocks:
                return False
        slot.cache = cache
        slot.outcome = outcome
        if (outcome in ("hit", "delta", "wt_delta")
                and self.ep.shadow.pick("batch")):
            slot.shadow_snap = snap
        return True

    # -- execution groups ---------------------------------------------------

    def _sharded_mesh(self, ev):
        """The endpoint's mesh when this batch should run the SHARDED warm
        launcher: >1 real device, MESH_SERVING gate open, and every
        aggregate has a mesh merge rule (no rule → the single-device
        xregion program, which needs none)."""
        mesh = self.ep.mesh
        if (mesh is None or getattr(mesh, "size", 1) <= 1
                or getattr(mesh, "devices", None) is None
                or not getattr(self.ep, "shard_cache", True)
                or not self.ep._gate_ok("mesh")):
            return None
        from ..parallel.mesh import mesh_mergeable

        return mesh if mesh_mergeable(ev.device_aggs) else None

    def _launch_xregion(self, sig: tuple, slots: list[_Slot], results, errors):
        """Resolve every slot's cache (host), shed what cannot batch, and
        dispatch ONE cross-region program — over the mesh (one shard_map
        program, slabs on their owner devices) when the endpoint has one,
        else the single-device vmapped program.  Returns the finalize
        closure."""
        from ..storage.mvcc.reader import KeyIsLockedError

        live: list[_Slot] = []
        for slot in slots:
            ok = False
            try:
                ok = self._resolve_slot(slot)
            except KeyIsLockedError as exc:
                # its readers met a lock: that is their answer (the client
                # backs off and retries), not a reason to serve them again
                for it in slot.items:
                    errors[it.index] = exc
                continue
            except Exception:  # noqa: BLE001 — resolution must not kill the batch
                ok = False
            if ok:
                live.append(slot)
                # a cold-fill answered the slot's first request already
                for it in slot.items:
                    resp = getattr(it, "_filled_resp", None)
                    if resp is not None:
                        results[it.index] = resp
            else:
                self._shed(slot, "no_cache", results, errors)
        # two slots (different start_ts / apply_index) can resolve to the
        # SAME region image — the region cache keys images on (region_id,
        # ranges, schema) only.  Each holds the version its own resolution
        # saw (a repair makes a new version and leaves the old one whole),
        # but a batch with more slots than images is a block-count tuple no
        # warm-up built: only the LAST resolution's slot batches, earlier
        # aliases shed to the per-request path, where serve() resolves them
        # again at their own snapshots.
        def image_of(slot):
            return slot.cache.key if slot.cache.key is not None else id(slot.cache)

        by_image: dict = {}
        for slot in live:
            prev = by_image.get(image_of(slot))
            if prev is not None:
                self._shed(prev, "aliased_image", results, errors)
            by_image[image_of(slot)] = slot
        live = [s for s in live if by_image.get(image_of(s)) is s]
        if not live:
            return None
        # admission to the batch is still forming it: evaluator, mesh and
        # breaker verdicts, the padding shed (zone pruning included)
        with trace.attach(live[0].items[0].trace_ctx), \
                trace.stage("sched.group", slots=len(live)):
            # riders of one group share shape AND literals (sig is both)
            ev, params = self.ep._bind(
                live[0].items[0].req.dag, split=sig,
                tasks=sum(len(s.items) for s in live))
            mesh = self._sharded_mesh(ev)
            breaker = self.ep.breaker
            if mesh is not None and not breaker.allow("mesh"):
                # mesh path tripped: degrade to the single-device cross-region
                # program instead of losing batching entirely
                from .tracker import count_path_fallback

                count_path_fallback("mesh", "breaker_open")
                mesh = None
            if mesh is None and not breaker.allow("xregion"):
                from .tracker import count_path_fallback

                count_path_fallback("xregion", "breaker_open")
                for slot in live:
                    self._shed(slot, "breaker_open", results, errors)
                return None
            path = "mesh" if mesh is not None else "xregion"
            if mesh is not None:
                live, device_load, sh_waste = self._shed_for_padding_sharded(
                    live, mesh, results, errors)
            else:
                live = self._shed_for_padding(live, results, errors)
                device_load, sh_waste = None, 0.0
            if len(live) < 2:
                breaker.release_probe(path)  # nothing launched on this path
                for slot in live:
                    self._shed(slot, "underfull", results, errors, path=path)
                return None
            # cold-fills were answered (and counted) by their own handle_request
            # — the program serves the rest; occupancy counts the whole fan-in.
            # Counted over the FINAL live set: a filled slot shed above (alias /
            # padding) must not deflate this batch's request count.
            n_batch = sum(len(s.items) for s in live)
            n_filled = sum(
                1 for s in live for it in s.items
                if getattr(it, "_filled_resp", None) is not None
            )
            n_reqs = max(n_batch - n_filled, 1)
            kind = "xregion" if mesh is None else "xregion_sharded"
            waste = (self._padding_waste(live, ev=ev, params=params)
                     if mesh is None else sh_waste)
        # fan-in linkage (docs/tracing.md): ONE device-dispatch span — its
        # own one-span trace naming every participating parent trace — and
        # each rider links back to it.  A shared dispatch can't be a child
        # of N parents; this is the honest shape for shared-slot serving.
        # the batch's own code up to the device, which every rider waits for
        with trace.batch_stage("sched.launch"):
            riders = [it for s in live for it in s.items]
            bsp = trace.fanin_span(
                "sched.device_dispatch", [it.trace_ctx for it in riders],
                kind=kind, regions=len(live), occupancy=len(riders))
            if bsp:
                ref = f"{bsp.rec.trace_id}:{bsp.span_id}"
                for it in riders:
                    it.batch_ref = ref
        t0 = time.perf_counter()
        try:
            # the batch's region images carry their ENCODING DESCRIPTORS on
            # the block caches (copr/encoding.py) alongside the dict
            # radices: the launchers read them to ship encoded HBM payloads
            # when every region agrees on one signature, and decode-ship
            # (counted per-cause) when not — sharded and fused paths stay
            # eligible for compressed-resident regions either way
            with bsp.active(), trace.batch_stage("sched.launch"):
                if mesh is not None:
                    pending = jax_eval.launch_xregion_sharded(
                        ev, [s.cache for s in live], mesh, params)
                else:
                    pending = jax_eval.launch_xregion_cached(
                        ev, [s.cache for s in live], params)
        except ValueError:
            # "not batchable" (empty blocks, unstable dictionaries) is a
            # documented decline, not a device failure — shed without
            # polluting the fallback counter
            breaker.release_probe(path)
            bsp.tag(outcome="ineligible").finish()
            for slot in live:
                self._shed(slot, "ineligible", results, errors, path=path)
            return None
        except Exception as exc:  # noqa: BLE001 — CPU pipeline is the oracle
            self._device_failed(exc, path)
            bsp.tag(outcome="device_error").finish()
            for slot in live:
                self._shed(slot, "device_error", results, errors, path=path)
            return None
        t_launched = time.perf_counter()

        def finalize(results, errors):
            t_fin = time.perf_counter()
            try:
                # and from the device's answer to each rider's response
                with bsp.active(), trace.batch_stage("sched.answer"):
                    resps = pending.finalize()
            except Exception as exc:  # noqa: BLE001
                self._device_failed(exc, path)
                bsp.tag(outcome="device_error").finish()
                for slot in live:
                    self._shed(slot, "device_error", results, errors,
                               path=path)
                return
            self.ep.breaker.record_success(path)
            with trace.batch_stage("sched.answer"):
                pull_dt = time.perf_counter() - t_fin
                # latency = this group's own host work (launch) + the blocking
                # pull (residual device time).  The gap between launch and
                # finalize is the NEXT group's prepare pass — double-buffered
                # overlap, not this batch's cost; attributing it here would
                # inflate the device-path percentiles with unrelated host work.
                dt = (t_launched - t0) + pull_dt
                with trace.attach(riders[0].trace_ctx), \
                        trace.stage("copr.obs", batch=kind):
                    self._batch_metrics(kind, n_reqs, dt, waste, n_batch=n_batch)
                if bsp:
                    bsp.tag(outcome="ok").finish()
                    # each rider's trace gets a span for the shared dispatch it
                    # rode, linked to the dispatch span's own trace
                    for it in riders:
                        # batch_ref was already stamped at fanin-span creation
                        trace.remote_span(it.trace_ctx, "sched.batched",
                                          start=t0, end=t_fin + pull_dt,
                                          batched_into=ref, kind=kind,
                                          occupancy=n_batch)
                if mesh is not None:
                    self._sharded_metrics(device_load, pull_dt)
                # observatory profiles (docs/observatory.md): every rider the
                # program answered records its attributed share on the batch
                # path, with the queue wait it actually paid and the dispatch
                # trace as its exemplar
                obs_path = "mesh" if mesh is not None else "xregion"
                obs_enc = getattr(pending, "obs_encoding", "plain")
                for slot, resp in zip(live, resps):
                    rows = slot.cache.total_rows if slot.cache is not None else 0
                    for it in slot.items:
                        if results[it.index] is not None:
                            continue  # cold-fill: recorded by its handle_request
                        self._record_obs(
                            it, ev, obs_path, dt / n_reqs, rows=rows,
                            encoding=obs_enc, occupancy=n_batch, waste=waste,
                            dispatch_t=t0, resp=resp)
                for slot, resp in zip(live, resps):
                    # per-region chunk payloads: every rider of this slot shares
                    # the SAME unjoined column-slab parts, so one multi-response
                    # frame gather-writes each region's slabs once
                    data = None
                    from_device = True
                    # the slot's stages land in its first rider's trace
                    with trace.attach(slot.items[0].trace_ctx):
                        parts, enc_tp = self.ep._encode_response(resp)
                        if slot.shadow_snap is not None:
                            # sampled slot: CPU-oracle byte compare; a mismatch
                            # quarantines the image and this slot serves the oracle
                            fixed = self.ep.shadow_compare(
                                slot.items[0].req, slot.shadow_snap,
                                b"".join(bytes(p) for p in parts), "batch")
                            if fixed is not None:
                                data, parts = fixed, None
                                from_device = False
                    from_cache = from_device and slot.outcome not in (
                        "", "miss", "too_big")
                    for it in slot.items:
                        if results[it.index] is not None:
                            continue  # the cold-fill already answered this one
                        r = CoprResponse(data, from_device=from_device,
                                         from_cache=from_cache,
                                         data_parts=parts, encode_type=enc_tp)
                        self._stamp(r, it, kind=kind, occupancy=n_batch,
                                    waste=waste, total_s=dt / n_reqs)
                        results[it.index] = r

        return finalize

    def _run_fused(self, key, items: list[_Item], results, errors):
        """Same region view, K different plans: the fused batch inherited
        from endpoint._try_fused_batch (run_batch_cached fuses all K into
        one program over the shared cache)."""
        if not self.ep.breaker.allow("fused"):
            from .tracker import count_path_fallback

            count_path_fallback("fused", "breaker_open")
            self._shed(_Slot(items=items), "breaker_open", results, errors,
                       path="fused")
            return None
        slot = _Slot(items=items)
        try:
            ok = self._resolve_slot(slot)
        except Exception:  # noqa: BLE001
            ok = False
        if not ok:
            self.ep.breaker.release_probe("fused")
            self._shed(slot, "no_cache", results, errors, path="fused")
            return None
        cache = slot.cache
        # the filler (cold cache) already answered slot.items[0]
        todo = [it for it in items if getattr(it, "_filled_resp", None) is None]
        for it in items:
            resp = getattr(it, "_filled_resp", None)
            if resp is not None:
                results[it.index] = resp
        if not todo:
            self.ep.breaker.release_probe("fused")  # cold-fill served it all
            return None
        n_reqs = len(todo)
        # identical requests (same signature over this region view) share one
        # query in the fused program — the cross-client dedupe
        uniq: dict[tuple, list[_Item]] = {}
        for it in todo:
            uniq.setdefault(it.sig, []).append(it)
        bsp = trace.fanin_span(
            "sched.device_dispatch", [it.trace_ctx for it in todo],
            kind="fused", plans=len(uniq), occupancy=len(todo))
        t0 = time.perf_counter()
        try:
            bound = [self.ep._bind(group[0].req.dag, split=sig, tasks=len(group))
                     for sig, group in uniq.items()]
            evs = [ev for ev, _params in bound]
            with bsp.active():
                resps = jax_eval.run_batch_cached(
                    evs, cache, [params for _ev, params in bound])
        except ValueError:
            # a documented decline (non-stable group dictionaries, empty
            # cache) — per-request path, no device-failure attribution
            self.ep.breaker.release_probe("fused")
            bsp.tag(outcome="ineligible").finish()
            self._shed(_Slot(items=todo), "ineligible", results, errors,
                       path="fused")
            return None
        except Exception as exc:  # noqa: BLE001
            # _resolve_slot guarantees a filled cache here, so there is no
            # partial fill to clean up (the cold-fill path owns that)
            self._device_failed(exc, "fused")
            bsp.tag(outcome="device_error").finish()
            self._shed(_Slot(items=todo), "device_error", results, errors,
                       path="fused")
            return None
        self.ep.breaker.record_success("fused")
        dt = time.perf_counter() - t0
        if bsp:
            ref = f"{bsp.rec.trace_id}:{bsp.span_id}"
            bsp.tag(outcome="ok").finish()
            for it in todo:
                trace.remote_span(it.trace_ctx, "sched.batched", start=t0,
                                  end=t0 + dt, batched_into=ref,
                                  kind="fused", occupancy=n_reqs)
                it.batch_ref = ref
        self._batch_metrics("fused", n_reqs, dt, 0.0, n_batch=len(items))
        # observatory profiles: each rider's plan records its share of the
        # fused dispatch under its OWN signature (docs/observatory.md).
        # Recorded AFTER the shadow verdict: on a mismatch the non-probe
        # groups re-execute per-request (which records them on the path
        # that actually serves) — recording them here too would double
        # count and skew the fused rows/s floors.
        rows = cache.total_rows if cache is not None else 0

        def _rec_fused(group, g_ev, g_resp=None):
            for it in group:
                self._record_obs(it, g_ev, "fused", dt / n_reqs, rows=rows,
                                 occupancy=n_reqs, dispatch_t=t0, resp=g_resp)

        if slot.shadow_snap is not None:
            groups = list(uniq.values())
            with trace.attach(groups[0][0].trace_ctx):
                fixed = self.ep.shadow_compare(
                    groups[0][0].req, slot.shadow_snap, resps[0].encode(),
                    "batch")
            if fixed is not None:
                # the SHARED image is corrupt (and quarantined): the probe's
                # signature group serves the oracle bytes already in hand;
                # the other groups — whose oracle answers were never
                # computed — re-execute per-request over the rebuilt state
                _rec_fused(groups[0], evs[0], resps[0])
                for it in groups[0]:
                    r = CoprResponse(fixed, from_device=False,
                                     encode_type=resps[0].encode_type)
                    self._stamp(r, it, kind="fused", occupancy=n_reqs,
                                total_s=dt / n_reqs)
                    results[it.index] = r
                for group in groups[1:]:
                    for it in group:
                        self._per_request(it, results, errors, kind="shadow")
                return None
        for group, g_ev, g_resp in zip(uniq.values(), evs, resps):
            _rec_fused(group, g_ev, g_resp)
        from_cache = slot.outcome not in ("", "miss", "too_big")
        for group, resp in zip(uniq.values(), resps):
            with trace.attach(group[0].trace_ctx):
                parts, enc_tp = self.ep._encode_response(resp)
            for it in group:
                r = CoprResponse(None, from_device=True, from_cache=from_cache,
                                 data_parts=parts, encode_type=enc_tp)
                self._stamp(r, it, kind="fused", occupancy=n_reqs,
                            total_s=dt / n_reqs)
                results[it.index] = r
        return None

    # -- admission ----------------------------------------------------------

    @staticmethod
    def _padding_waste(slots: list[_Slot], ev=None, params=()) -> float:
        if not slots:
            return 0.0
        counts = [len(s.cache.blocks) for s in slots]
        b = max(counts)
        if ev is not None:
            # zone-aware effective waste (docs/zone_maps.md): a pruned block
            # ships n_valid == 0 and scans as padding, so the batch's useful
            # fraction is its SURVIVOR count — the reported waste says so.
            # The shed predicate stays on raw block counts (no ev): pruning
            # never changes the padded shapes, so shedding can't recover it.
            from . import zone_maps as _zm

            sel_rpns = ev.bound_sel_rpns(params)
            counts = [
                int(keep.sum()) if (keep := _zm.prune_blocks(
                    s.cache, sel_rpns, count=False)) is not None else c
                for s, c in zip(slots, counts)
            ]
        return 1.0 - sum(counts) / (len(counts) * b)

    def _shed_for_padding(self, slots: list[_Slot], results, errors) -> list[_Slot]:
        """Shed block-count outliers until the padded geometry wastes no
        more than the budget.  The LARGEST region sheds (its per-request
        dispatch is already amortized over its rows; keeping it would pad
        every smaller region up to its block count)."""
        live = list(slots)
        while len(live) > 1 and self._padding_waste(live) > self.cfg.padding_budget:
            biggest = max(live, key=lambda s: len(s.cache.blocks))
            live.remove(biggest)
            self._shed(biggest, "padding", results, errors)
        return live

    # -- sharded (mesh) geometry --------------------------------------------

    @staticmethod
    def _device_load(slots: list[_Slot], mesh) -> dict[int, int]:
        """Slabs per device for a prospective batch — the launcher's OWN
        geometry (``parallel.mesh.device_slab_load``), so shed decisions
        and occupancy metrics can never diverge from what launches."""
        from ..parallel.mesh import device_slab_load

        return device_slab_load([s.cache for s in slots], mesh)

    @staticmethod
    def _load_waste(load: dict[int, int]) -> float:
        """Wasted fraction of the (devices × slabs) geometry.  Devices with
        zero load are EXCLUDED: a 3-region batch on an 8-chip mesh leaves 5
        chips idle by region count, which shedding regions can only worsen —
        idle capacity shows in the per-device occupancy series instead.
        Counted waste is slab-count IMBALANCE among loaded devices (the
        regions-axis padding the slab axis rounds up to)."""
        loaded = [v for v in load.values() if v > 0]
        if not loaded:
            return 0.0
        return 1.0 - sum(loaded) / (len(loaded) * max(loaded))

    def _padding_waste_sharded(self, slots: list[_Slot], mesh) -> float:
        return self._load_waste(self._device_load(slots, mesh)) if slots else 0.0

    def _shed_for_padding_sharded(self, slots, mesh, results, errors):
        """Sharded-geometry padding shed: the largest region sheds while
        the loaded-device slab imbalance exceeds the budget.  Returns
        (live slots, final device load, final waste) — one assignment pass
        per iteration, and callers reuse the final geometry instead of
        recomputing it."""
        live = list(slots)
        load = self._device_load(live, mesh)
        waste = self._load_waste(load)
        while len(live) > 1 and waste > self.cfg.padding_budget:
            biggest = max(live, key=lambda s: len(s.cache.blocks))
            live.remove(biggest)
            self._shed(biggest, "padding", results, errors, path="mesh")
            load = self._device_load(live, mesh)
            waste = self._load_waste(load)
        return live, load, waste

    def _sharded_metrics(self, device_load: dict[int, int], pull_dt: float) -> None:
        """Per-device shard occupancy (used slabs / slab-axis size, idle
        devices included) + the collective-merge/pull time of the batch."""
        from ..util.metrics import REGISTRY

        s = max(max(device_load.values()), 1) if device_load else 1
        h = REGISTRY.histogram(
            "tikv_coprocessor_sched_device_occupancy",
            "Per-device slab occupancy of sharded cross-region batches",
            buckets=(0.0, 0.125, 0.25, 0.5, 0.75, 0.9, 1.0),
        )
        for did, n in device_load.items():
            h.observe(n / s, device=str(did))
        REGISTRY.histogram(
            "tikv_coprocessor_sharded_merge_seconds",
            "Collective-merge + packed-pull time of sharded batches",
        ).observe(pull_dt)

    def _per_request(self, it: _Item, results, errors, kind: str) -> None:
        """Serve one item on the per-request path, capturing its failure in
        ``errors`` so it stays its own (old unary semantics per request).
        Ticketed (continuous-mode) items are handed back to their caller's
        thread instead — executing them here would serialize every lane
        behind the dispatcher."""
        if results[it.index] is not None or errors[it.index] is not None:
            return
        if it.ticket is not None and not it.ticket.done.is_set():
            it.ticket.direct = True
            # it serves itself from here: the batch's later stages are not
            # its wait
            trace.unshare(it.trace_ctx)
            it.ticket.hand_back()
            return
        try:
            # explicit pool-boundary handoff: the dispatcher serves this on
            # the rider's behalf, so its spans land in the rider's trace
            with trace.attach(it.trace_ctx):
                resp = self.ep.handle_request(it.req)
        except BaseException as exc:  # noqa: BLE001 — delivered per item
            errors[it.index] = exc
            return
        self._stamp(resp, it, kind=kind, occupancy=1)
        results[it.index] = resp

    def _record_obs(self, it: _Item, ev, path: str, latency_s: float, *,
                    rows: int = 0, encoding: str = "plain",
                    occupancy: int = 1, waste: float | None = None,
                    dispatch_t: float | None = None, resp=None) -> None:
        """One batch-served rider into the observatory: attributed latency
        share, the queue wait it actually paid, and its own trace id as the
        profile exemplar (docs/observatory.md)."""
        if not _obs.OBSERVATORY.enabled:
            return
        with trace.attach(it.trace_ctx), trace.stage("copr.obs"):
            sig = getattr(ev, "obs_sig", "")
            if not sig and it.sig is not None:
                sig = _obs.sig_id(it.sig[0])
            qwait = (max(dispatch_t - it.enqueue_t, 0.0)
                     if dispatch_t is not None and it.enqueue_t else 0.0)
            prune = getattr(resp, "_obs_prune", None) or (0, 0)
            _obs.OBSERVATORY.record_serve(
                sig, path, latency_s, rows=rows, encoding=encoding,
                occupancy=occupancy, queue_wait_s=qwait, padding_waste=waste,
                trace_id=(it.trace_ctx or {}).get("trace_id"),
                desc=getattr(ev, "obs_desc", ""),
                blocks_examined=prune[0], blocks_pruned=prune[1])

    def _shed(self, slot: _Slot, reason: str, results, errors,
              path: str = "xregion") -> None:
        self._count_shed(reason)
        it0 = slot.items[0] if slot.items else None
        _obs.OBSERVATORY.record_decline(
            _obs.sig_id(it0.sig[0]) if it0 is not None and it0.sig is not None
            else None,
            path, reason)
        for it in slot.items:
            self._per_request(it, results, errors, kind="shed:" + reason)

    def _device_failed(self, exc: BaseException, path: str) -> None:
        from ..util.metrics import REGISTRY
        from .tracker import count_path_fallback

        self.ep.device_fallbacks += 1
        self.ep.last_device_error = repr(exc)
        self.ep.breaker.record_failure(path)
        count_path_fallback(path, "device_error")
        REGISTRY.counter(
            "tikv_coprocessor_device_fallback_total",
            "Device-path failures that re-ran on the CPU pipeline",
        ).inc()

    # -- metrics ------------------------------------------------------------

    def _stamp(self, resp: CoprResponse, it: _Item, kind: str, occupancy: int,
               waste: float | None = None, total_s: float | None = None) -> None:
        from .tracker import stamp_sched

        resp.metrics = stamp_sched(resp.metrics, it.lane, kind, occupancy,
                                   waste=waste, total_s=total_s)

    def _batch_metrics(self, kind: str, n_reqs: int, dt: float, waste: float,
                       n_batch: int | None = None) -> None:
        """``n_reqs``: requests the device program answered (request_total /
        duration series — exactly-once, so a cold-fill counted by its own
        handle_request is excluded).  ``n_batch``: the batch's whole fan-in
        including the fill (batch/occupancy series)."""
        from ..util.metrics import REGISTRY

        n_batch = n_batch or n_reqs
        # the per-request series stay truthful under batch serving — one
        # duration observation PER REQUEST (each at the per-request share),
        # not a single mean observation, so count-weighted percentiles
        # compare honestly against the unary path
        REGISTRY.counter(
            "tikv_coprocessor_request_total", "Coprocessor requests, by type/path"
        ).inc(n_reqs, tp=str(REQ_TYPE_DAG), path="device")
        h = REGISTRY.histogram(
            "tikv_coprocessor_request_duration_seconds", "Coprocessor latency"
        )
        for _ in range(n_reqs):
            h.observe(dt / n_reqs, tp=str(REQ_TYPE_DAG))
        REGISTRY.counter(
            "tikv_coprocessor_batch_total", "Fused coprocessor batches"
        ).inc()
        REGISTRY.counter(
            "tikv_coprocessor_batch_queries_total", "Queries served fused"
        ).inc(n_batch)
        REGISTRY.counter(
            "tikv_coprocessor_sched_batches_total",
            "Scheduler micro-batches dispatched, by kind",
        ).inc(kind=kind)
        REGISTRY.histogram(
            "tikv_coprocessor_sched_batch_occupancy",
            "Requests per scheduler micro-batch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128),
        ).observe(n_batch, kind=kind)
        REGISTRY.histogram(
            "tikv_coprocessor_sched_padding_waste",
            "Wasted fraction of padded block slots per cross-region batch",
            buckets=(0.01, 0.05, 0.1, 0.2, 0.3, 0.5, 0.75, 1.0),
        ).observe(waste, kind=kind)

    def _count_dispatch(self, why: str, riders: int) -> None:
        """One pass of the dispatch loop that took riders off the lanes,
        whichever rung serves them afterwards, by what released it:
        ``drained`` (nobody else on the way), ``deadline`` (the oldest
        rider's linger ran out), ``full`` (max_batch queued), ``stop``."""
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_sched_dispatch_total",
            "Dispatcher passes that took riders off the lanes, by release",
        ).inc(why=why)
        REGISTRY.histogram(
            "tikv_coprocessor_sched_dispatch_riders",
            "Riders per dispatcher pass, by release",
            buckets=(1, 2, 4, 8, 16, 32, 64),
        ).observe(riders, why=why)

    def _count_shed(self, reason: str) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_sched_shed_total",
            "Requests shed to the per-request path, by reason",
        ).inc(reason=reason)

    def _count_coalesce(self, outcome: str) -> None:
        """Continuous-mode admission outcomes for wire-coalesced unary
        requests: ``batched`` (served out of a dispatcher micro-batch),
        ``direct`` (handed back to the caller's thread), ``bypass``
        (scheduler off / plan not batchable), ``queue_full`` /
        ``busy_reject`` (admission control)."""
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_wire_coalesce_total",
            "Server-side RPC coalescing admissions, by outcome",
        ).inc(outcome=outcome)

    def _count_deadline(self, at: str) -> None:
        from ..util.metrics import REGISTRY

        REGISTRY.counter(
            "tikv_coprocessor_deadline_expired_total",
            "Requests shed because their deadline expired, by detection point",
        ).inc(at=at)

    def _gauge_depth(self) -> None:
        from ..util.metrics import REGISTRY

        g = REGISTRY.gauge(
            "tikv_coprocessor_sched_queue_depth",
            "Requests waiting in the scheduler, by priority lane",
        )
        for lane in LANES:
            g.set(len(self._queues[lane]), lane=lane)

    def _observe_wait(self, it: _Item, now: float) -> None:
        from ..util.metrics import REGISTRY

        wait = now - it.enqueue_t
        REGISTRY.histogram(
            "tikv_coprocessor_sched_lane_wait_seconds",
            "Queue wait before dispatch, by priority lane",
        ).observe(wait, lane=it.lane)
        # the same wait as a stage of the rider's trace: enqueue, on the
        # connection's thread, to pick-up, on this one
        trace.remote_span(it.trace_ctx, "sched.wait", start=it.enqueue_t,
                          end=now, stage=True, lane=it.lane)
        if it.behind0 is not None and trace.enabled():
            # and split by what this thread did since the enqueue: the four
            # parts tile the wait (trace.WaitClock, in BEHIND's order)
            for series, part, then in zip(_WAIT_BEHIND, self._clock.read(now),
                                          it.behind0):
                series.inc(part - then)
        ov = getattr(self.ep, "overload", None)
        if ov is not None:
            # adaptive-controller evidence: sampled lane waits say whether
            # admitted work is actually draining (docs/robustness.md)
            ov.note_wait(wait)
