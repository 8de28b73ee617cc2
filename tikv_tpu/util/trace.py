"""End-to-end distributed tracing plane (docs/tracing.md).

Re-expression of the reference's minitrace integration (TiKV v5.1 threads
trace spans through the kvproto request Context and surfaces them in the
slow log): causally-linked spans from the client wire frame through the
read-plane ladder, the coprocessor scheduler's queue lanes, device dispatch,
and the txn scheduler's raft propose→apply — ONE trace per request no matter
how many stores, threads, or micro-batches it crosses.

Model
-----
* A **trace** is a tree of spans sharing one ``trace_id``.  A **span** has a
  ``span_id``, a ``parent_id``, a monotonic start/duration, a wall-clock
  anchor (cross-store ordering), and typed tags.
* The **current span** is thread-local; ``span(name)`` nests under it.
  Thread/pool boundaries hand off EXPLICITLY: capture ``current_context()``
  on the submitting thread, ``attach(ctx)`` (or ``remote_span``) on the
  worker — implicit inheritance across pools would misattribute every
  borrowed thread.
* **Wire propagation**: ``inject(ctx)`` stamps ``trace_id``/``span_id``/
  ``sampled`` into a request context dict; the serving store's RPC layer
  joins the same trace via ``start_trace(..., ctx=ctx)``.  Read-plane
  forwards, device-owner hops and client retries therefore produce one
  trace spanning stores.
* **Fan-in** (shared-slot batch serving): a coalesced device dispatch is its
  own one-span trace (``fanin_span``) recording the participating parent
  trace ids; each rider gets a ``batched_into`` link pointing at it
  (``remote_span``).  That is the only honest shape — one dispatch span
  cannot be a child of N different parents.

Sampling
--------
Head-based: a fresh trace is recorded iff ``random() < sample_rate`` marks
it ``sampled`` — but when the rate is in (0, 1) EVERY request still records
spans into a bounded live table, because tail-based **promotion** keeps any
trace whose root crosses ``slow_threshold_s`` even when the head decision
said drop ("the slow request you could not predict").  ``sample_rate == 0``
turns the plane off: every entry point is ONE branch returning the no-op
span, no allocation beyond the call itself.

Stages
------
A **stage** (``stage(name)``) is a leaf span with three faces: a child span
of the thread's current span (flagged ``stage``), a wall and a thread-CPU
total per stage name in the registry, and, while a mirror is installed
(``copr/jax_eval.py`` installs ``jax.profiler.TraceAnnotation``), an
annotation of the same name in the device profiler's trace, so that the
device trace names its idle gaps after the program's own stages.  Stages
never nest: one entered inside another suspends the outer one, which
resumes as a new segment when the inner one ends.  When a root ``rpc.*``
span commits, the stage time in its tree plus the stage time a shared
batch spent on its behalf (``shared``) is its **attributed** time; the rest
is what the instrumentation cannot see.

The tracer's lock is a LEAF by construction — span operations touch only
tracer state, never another subsystem's lock — so spans are safe to open or
finish while holding scheduler/cache/raft locks (the sanitizer's order graph
can never find a cycle through it).
"""

from __future__ import annotations

import gc
import os
import random
import threading
import time
from collections import deque

from ..analysis.sanitizer import make_lock
from .metrics import REGISTRY

__all__ = [
    "Span", "attach", "begin", "current", "current_context",
    "current_trace_id", "enabled", "fanin_span", "inject",
    "install_gc_hook", "record", "remote_span", "sample_rate", "set_mirror",
    "set_sample_rate", "set_slow_threshold", "shared", "slow_threshold",
    "snapshot", "span", "stage", "start_trace", "timed_stage", "timeline",
    "TRACER",
]

#: per-trace span cap: one runaway loop must not balloon the live table
MAX_SPANS = 128
#: live (unfinished) trace cap: beyond it, new traces are dropped+counted
MAX_LIVE = 2048
#: finished-trace rings (recent = every kept trace, slow = promoted/slow)
RING = 64

_CTX_KEYS = ("trace_id", "span_id", "sampled")


_TRACE_TOTAL = REGISTRY.counter(
    "tikv_trace_total",
    "Trace head/tail sampling decisions at trace completion, by outcome")
_RING_TRACES = REGISTRY.gauge(
    "tikv_trace_ring_traces",
    "Traces held per tracer ring (live = still open)")
_STAGE_SECONDS = REGISTRY.histogram(
    "tikv_trace_stage_seconds",
    "Wall time inside served-path stages (docs/tracing.md), by stage")
_STAGE_CPU = REGISTRY.counter(
    "tikv_trace_stage_cpu_seconds_total",
    "The thread's own CPU time inside served-path stages, by stage")
_REQUEST_SECONDS = REGISTRY.counter(
    "tikv_trace_request_seconds_total",
    "Root rpc span time of finished traces, by method")
_REQUEST_ATTRIBUTED = REGISTRY.counter(
    "tikv_trace_request_attributed_seconds_total",
    "Stage time attributed to finished rpc traces, by method")
_GC_PAUSE = REGISTRY.counter(
    "tikv_process_gc_pause_seconds_total",
    "Time inside the interpreter's cyclic collector, by generation")


_STAGE_SERIES: dict[str, tuple] = {}


def _stage_series(name: str) -> tuple:
    """(wall histogram, CPU counter) of one stage, its label key made once."""
    series = _STAGE_SERIES.get(name)
    if series is None:
        series = _STAGE_SERIES[name] = (_STAGE_SECONDS.labels(stage=name),
                                        _STAGE_CPU.labels(stage=name))
    return series


def _count(outcome: str) -> None:
    _TRACE_TOTAL.inc(outcome=outcome)


class _Noop:
    """The disabled-path span: one shared instance, every operation a no-op.
    Falsy so hot call sites can skip tag computation with ``if sp:``."""

    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def tag(self, **kv):
        return self

    def link(self, kind, ref):
        return self

    def finish(self, end=None):
        return None

    def child(self, name, start=None, **tags):
        return self

    def record(self, name, start, end, **tags):
        return self

    def active(self):
        return self

    context = None


NOOP = _Noop()


class _Active:
    """Span.active(): current-span push/pop without finishing."""

    __slots__ = ("_sp", "_prev")

    def __init__(self, sp: "Span"):
        self._sp = sp
        self._prev = None

    def __enter__(self):
        st = self._sp._tracer._state
        self._prev = getattr(st, "cur", None)
        st.cur = self._sp
        return self._sp

    def __exit__(self, *exc):
        st = self._sp._tracer._state
        if getattr(st, "cur", None) is self._sp:
            st.cur = self._prev
        return False


class _Stage:
    """One stage of a served request (module docstring, "Stages").  The
    clock is read once on the way in and once on the way out; the span, the
    totals and the mirror all take those two readings."""

    __slots__ = ("_tracer", "name", "tags", "seconds", "_sp", "_ann", "_t0",
                 "_c0", "_outer")

    def __init__(self, tracer: "Tracer", name: str, tags: dict):
        self._tracer = tracer
        self.name = name
        self.tags = tags
        self.seconds = 0.0  # wall time so far (timed_stage's sites read it)
        self._sp = None
        self._ann = None
        self._outer = None

    def __bool__(self):
        return True

    def tag(self, **kv) -> "_Stage":
        self.tags.update(kv)
        return self

    def __enter__(self) -> "_Stage":
        st = self._tracer._state
        outer = getattr(st, "stage", None)
        if outer is not None:
            outer._close()  # suspended: stages never nest
        self._outer = outer
        st.stage = self
        self._open()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None and "error" not in self.tags:
            self.tags["error"] = repr(exc)
        self._close()
        outer = self._outer
        self._tracer._state.stage = outer
        if outer is not None:
            outer._open()  # resumes as a new segment
        return False

    def _open(self) -> None:
        tracer = self._tracer
        mirror = tracer._mirror
        if mirror is not None:
            self._ann = mirror(self.name)
            self._ann.__enter__()
        self._c0 = time.thread_time()
        self._t0 = t0 = time.perf_counter()
        cur = getattr(tracer._state, "cur", None)
        if cur is not None:
            self._sp = tracer._child(cur.rec, cur.span_id, self.name, None,
                                     start=t0, stage=True)

    def _close(self) -> None:
        t1 = time.perf_counter()
        cpu = time.thread_time() - self._c0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        dt = t1 - self._t0
        self.seconds += dt
        wall_total, cpu_total = _stage_series(self.name)
        wall_total.observe(dt)
        cpu_total.inc(cpu)
        sp, self._sp = self._sp, None
        shared = getattr(self._tracer._state, "shared", None)
        if shared:
            own = sp.rec if sp is not None else None
            for rec in shared:
                if rec is not own:
                    rec.shared_s += dt
        if sp is not None:
            sp.tags.update(self.tags)
            sp.finish(end=t1)


class _Clock:
    """What ``timed_stage`` gives while the plane is off: the two clock
    reads its site needs for a histogram of its own, and nothing else.
    Falsy and tag-deaf like the no-op span."""

    __slots__ = ("seconds", "_t0")

    def __bool__(self):
        return False

    def tag(self, **kv) -> "_Clock":
        return self

    def __enter__(self) -> "_Clock":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        return False


class _Shared:
    """Tracer.shared(): while active on this thread, every stage that closes
    here also counts as attributed time of each listed trace, but for the
    trace the stage's own span belongs to."""

    __slots__ = ("_tracer", "_recs", "_prev")

    def __init__(self, tracer: "Tracer", recs: list):
        self._tracer = tracer
        self._recs = recs
        self._prev = None

    def __enter__(self):
        st = self._tracer._state
        self._prev = getattr(st, "shared", None)
        st.shared = self._recs
        return self

    def __exit__(self, *exc):
        self._tracer._state.shared = self._prev
        return False


class _Rec:
    """One live trace: its spans plus the open-span refcount that decides
    when the trace is complete and the sampling verdict applies."""

    __slots__ = ("trace_id", "sampled", "spans", "open", "had_root",
                 "root", "root_dur", "truncated", "t0", "stage_s", "shared_s")

    def __init__(self, trace_id: str, sampled: bool):
        self.trace_id = trace_id
        self.sampled = sampled
        self.spans: list[Span] = []
        self.open = 0
        self.had_root = False
        self.root: Span | None = None
        self.root_dur: float | None = None
        self.truncated = 0
        self.t0 = time.time()
        # attributed time: stage spans of this tree, and stage time a shared
        # batch spent while this request waited for it (Tracer.shared)
        self.stage_s = 0.0
        self.shared_s = 0.0


class Span:
    __slots__ = ("rec", "name", "span_id", "parent_id", "wall", "t0",
                 "dur", "tags", "root", "stage", "_tracer", "_prev", "_pushed")

    def __init__(self, tracer: "Tracer", rec: _Rec, name: str,
                 parent_id: str | None, root: bool,
                 start: float | None = None, tags: dict | None = None,
                 stage: bool = False):
        self.rec = rec
        self.name = name
        self.span_id = tracer._new_id()
        self.parent_id = parent_id
        self.t0 = time.perf_counter() if start is None else start
        self.wall = time.time() - (time.perf_counter() - self.t0)
        self.dur: float | None = None
        self.tags = dict(tags) if tags else {}
        self.root = root
        self.stage = stage
        self._tracer = tracer
        self._prev = None
        self._pushed = False

    def __bool__(self):
        return True

    @property
    def context(self) -> dict:
        return {"trace_id": self.rec.trace_id, "span_id": self.span_id,
                "sampled": self.rec.sampled}

    def tag(self, **kv) -> "Span":
        self.tags.update(kv)
        return self

    def link(self, kind: str, ref: str) -> "Span":
        self.tags[kind] = ref
        return self

    def child(self, name: str, start: float | None = None, **tags) -> "Span":
        """A child of THIS span regardless of the thread-local current —
        the explicit form the RPC layer uses for its stage spans."""
        return self._tracer._child(self.rec, self.span_id, name, tags,
                                   start=start)

    def record(self, name: str, start: float, end: float,
               stage: bool = False, **tags) -> "Span":
        """A finished child with explicit perf_counter bounds (stages
        measured before/after the span tree could be current).  With
        ``stage`` it is a recorded stage: it counts as attributed time and
        moves the stage's wall total, with no CPU total and no mirror (its
        bounds lie on two threads or before the tree existed)."""
        sp = self._tracer._child(self.rec, self.span_id, name, tags,
                                 start=start, stage=stage)
        sp.finish(end=end)
        if stage:
            _stage_series(name)[0].observe(end - start)
        return sp

    def active(self) -> "_Active":
        """Push this span as the thread-local current for a block WITHOUT
        finishing it on exit — the cross-thread activation used when a pool
        worker executes under a span its submitter owns."""
        return _Active(self)

    # -- context-manager use (same-thread nesting) --------------------------

    def __enter__(self) -> "Span":
        st = self._tracer._state
        self._prev = getattr(st, "cur", None)
        st.cur = self
        self._pushed = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc is not None and "error" not in self.tags:
            self.tags["error"] = repr(exc)
        st = self._tracer._state
        if getattr(st, "cur", None) is self:
            st.cur = self._prev
        self._pushed = False
        self.finish()
        return False

    # -- explicit finish (cross-thread handles: raft apply callbacks) -------

    def finish(self, end: float | None = None) -> None:
        if self.dur is not None:
            return  # fast path; the real exactly-once gate is in _span_done
        self._tracer._span_done(
            self, time.perf_counter() if end is None else end)

    def to_dict(self) -> dict:
        d = {
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "start": round(self.wall, 6),
            "duration_ms": round((self.dur or 0.0) * 1000, 3),
            "tags": {k: _plain(v) for k, v in self.tags.items()},
        }
        if self.stage:
            d["stage"] = True
        return d


def _plain(v):
    """Wire/JSON-codable tag value (the debug_traces RPC re-frames these)."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (bytes, bytearray)):
        return bytes(v).hex()
    if isinstance(v, (list, tuple)):
        return [_plain(x) for x in v]
    return repr(v)


class Tracer:
    """Process-global trace store: live table + finished rings."""

    def __init__(self, sample_rate: float | None = None,
                 slow_threshold_s: float | None = None):
        if sample_rate is None:
            sample_rate = float(os.environ.get("TIKV_TPU_TRACE_SAMPLE", "0.01"))
        if slow_threshold_s is None:
            slow_threshold_s = float(
                os.environ.get("TIKV_TPU_TRACE_SLOW_S", "0.3"))
        self._rate = max(0.0, min(1.0, sample_rate))
        self._slow_s = slow_threshold_s
        self._mu = make_lock("util.trace")
        self._state = threading.local()
        self._live: dict[str, _Rec] = {}
        self._recent: deque[dict] = deque(maxlen=RING)
        self._slow: deque[dict] = deque(maxlen=RING)
        self._rng = random.Random()
        self._idgen = random.Random()
        # stage mirror: a factory of context managers (jax.profiler.
        # TraceAnnotation, installed by copr/jax_eval.py: this module stays
        # importable without jax)
        self._mirror = None
        # collector pauses, noted by the gc callback and moved into the
        # registry later: the callback runs wherever an allocation tripped
        # the collector, also inside this module's and the registry's locked
        # regions, so it takes no lock
        self._gc_pauses: deque = deque()
        self._gc_open = None

    # -- knobs (online-config controller + ctl.py trace set-sample-rate) ----

    def set_sample_rate(self, rate: float) -> None:
        self._rate = max(0.0, min(1.0, float(rate)))

    def sample_rate(self) -> float:
        return self._rate

    def set_slow_threshold(self, seconds: float) -> None:
        self._slow_s = float(seconds)

    def slow_threshold(self) -> float:
        return self._slow_s

    def enabled(self) -> bool:
        return self._rate > 0.0

    # -- ids ----------------------------------------------------------------

    def _new_id(self) -> str:
        return f"{self._idgen.getrandbits(64):016x}"

    def _room_locked(self) -> bool:
        """Live-table admission (caller holds the lock): at the cap, evict
        ONE stale record (open > 60s — a span handle leaked by a crashed
        worker) rather than letting a slow leak starve all future traces."""
        if len(self._live) < MAX_LIVE:
            return True
        now = time.time()
        oldest = min(self._live.values(), key=lambda r: r.t0, default=None)
        if oldest is not None and now - oldest.t0 > 60.0:
            del self._live[oldest.trace_id]
            return True
        return False

    # -- trace/span creation ------------------------------------------------

    def start_trace(self, name: str, ctx: dict | None = None,
                    start: float | None = None, **tags):
        """Root (or wire-joined) span of a request on this store.

        ``ctx`` carrying ``trace_id`` + ``sampled`` JOINS the remote trace
        (the span parents onto the remote ``span_id``); otherwise a fresh
        trace starts iff sampling is on.  Joined spans are not roots — the
        originating store's root closes the trace."""
        # join whenever the context names a trace this process should record:
        # a head-SAMPLED trace always (keeps distributed traces whole even on
        # a rate-0 store), an unsampled one only while tail promotion is on
        # locally (rate > 0) — its spans matter exactly when the request
        # turns out slow
        joined = bool(ctx) and bool(ctx.get("trace_id")) and (
            bool(ctx.get("sampled")) or self._rate > 0.0)
        if not joined and self._rate <= 0.0:
            return NOOP
        with self._mu:
            if joined:
                rec = self._live.get(ctx["trace_id"])
                if rec is None and self._room_locked():
                    # cross-process join: this store records its fragment of
                    # the trace (committed rootless when its spans close)
                    rec = _Rec(ctx["trace_id"], bool(ctx.get("sampled")))
                    self._live[rec.trace_id] = rec
                parent = ctx.get("span_id")
                root = False
            else:
                rec = None
                if self._room_locked():
                    rec = _Rec(self._new_id(),
                               self._rng.random() < self._rate)
                    self._live[rec.trace_id] = rec
                parent = None
                root = True
            if rec is not None:
                rec.open += 1
                rec.had_root = rec.had_root or root
        if rec is None:
            _count("dropped")
            return NOOP
        return Span(self, rec, name, parent, root, start=start, tags=tags)

    def span(self, name: str, **tags):
        """Child of the current span; NOOP when no trace is active here."""
        cur = getattr(self._state, "cur", None)
        if cur is None:
            return NOOP
        return self._child(cur.rec, cur.span_id, name, tags)

    def begin(self, name: str, **tags):
        """Like :meth:`span` but NOT pushed as current: a handle the caller
        finishes explicitly, possibly from another thread (the raft write
        callback).  The tracer lock is a leaf, so finishing from any thread
        is safe."""
        cur = getattr(self._state, "cur", None)
        if cur is None:
            return NOOP
        return self._child(cur.rec, cur.span_id, name, tags)

    def record(self, name: str, start: float, end: float, **tags):
        """A finished child span with explicit perf_counter bounds — the
        wire stages measured before a span could exist (frame decode)."""
        cur = getattr(self._state, "cur", None)
        if cur is None:
            return NOOP
        sp = self._child(cur.rec, cur.span_id, name, tags, start=start)
        sp.finish(end=end)
        return sp

    def _child(self, rec: _Rec, parent_id: str | None, name: str,
               tags: dict | None, start: float | None = None,
               stage: bool = False) -> Span:
        with self._mu:
            rec.open += 1
        return Span(self, rec, name, parent_id, False, start=start, tags=tags,
                    stage=stage)

    # -- stages --------------------------------------------------------------

    def stage(self, name: str, **tags):
        """A leaf span of the current span that also moves the stage's wall
        and CPU totals and is mirrored into the device profiler's trace
        (module docstring, "Stages").  With no current span it does the
        latter two; with the plane off it is the shared NOOP."""
        if self._rate <= 0.0 and getattr(self._state, "cur", None) is None:
            return NOOP
        if self._gc_pauses:
            self._drain_gc()
        return _Stage(self, name, tags)

    def timed_stage(self, name: str, **tags):
        """A stage whose site also reports its wall time elsewhere (a
        histogram that predates the stage): ``.seconds`` holds it after the
        block whether the plane is on or off, from one pair of clock reads."""
        return self.stage(name, **tags) or _Clock()

    def set_mirror(self, factory) -> None:
        """``factory(name)`` gives the context manager a stage enters around
        its block; None takes the mirror away."""
        self._mirror = factory

    def shared(self, ctxs: list):
        """For a block on this thread, stages count as attributed time of
        every live trace among ``ctxs`` too: a rider of a shared batch
        waits for all of the batch's stages, whichever trace they land in."""
        if self._rate <= 0.0 and not any(ctxs):
            return NOOP
        recs: list = []
        with self._mu:
            for ctx in ctxs:
                rec = self._live.get(ctx["trace_id"]) if ctx else None
                if rec is not None and rec not in recs:
                    recs.append(rec)
        return _Shared(self, recs)

    # -- collector pauses ----------------------------------------------------

    def install_gc_hook(self) -> None:
        """Time every pause of the interpreter's cyclic collector as the
        stage ``host.gc`` (no span: a pause belongs to no request) and into
        ``tikv_process_gc_pause_seconds_total``.  Installed once a process,
        by the store at start."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            if self._rate <= 0.0:
                return
            ann = None
            if self._mirror is not None:
                ann = self._mirror("host.gc")
                ann.__enter__()
            self._gc_open = (time.perf_counter(), ann)
        elif self._gc_open is not None:
            t0, ann = self._gc_open
            self._gc_open = None
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self._gc_pauses.append((info.get("generation"), dt))

    def _drain_gc(self) -> None:
        while True:
            try:
                generation, dt = self._gc_pauses.popleft()
            except IndexError:
                return
            _GC_PAUSE.inc(dt, generation=str(generation))
            _stage_series("host.gc")[0].observe(dt)

    # -- explicit handoff ----------------------------------------------------

    def current(self):
        return getattr(self._state, "cur", None)

    def current_context(self) -> dict | None:
        cur = getattr(self._state, "cur", None)
        return cur.context if cur is not None else None

    def current_trace_id(self) -> str | None:
        cur = getattr(self._state, "cur", None)
        return cur.rec.trace_id if cur is not None else None

    def inject(self, ctx: dict) -> dict:
        """Stamp the current span's identity into a request context dict
        (mutates and returns it).  No-op without an active span."""
        cur = getattr(self._state, "cur", None)
        if cur is not None:
            ctx["trace_id"] = cur.rec.trace_id
            ctx["span_id"] = cur.span_id
            ctx["sampled"] = cur.rec.sampled
        return ctx

    def attach(self, ctx: dict | None) -> "_Attach":
        """Make a captured context current for a block on THIS thread (the
        pool-boundary handoff): spans opened inside nest under the remote
        parent.  ``attach(None)`` is a no-op block."""
        return _Attach(self, ctx)

    def remote_span(self, ctx: dict | None, name: str,
                    start: float | None = None, end: float | None = None,
                    stage: bool = False, **tags):
        """Record a span directly into the trace named by ``ctx`` without
        touching this thread's current stack — how a dispatcher thread
        stamps per-rider spans for work it served on their behalf.  Applies
        to unsampled live records too: tail promotion exists to keep
        exactly these phases when the request turns out slow.  ``stage``
        (with both bounds) makes it a recorded stage, as ``Span.record``."""
        if not ctx or not ctx.get("trace_id"):
            return NOOP
        with self._mu:
            rec = self._live.get(ctx["trace_id"])
            if rec is None:
                return NOOP  # trace already finished (or cross-process)
            rec.open += 1
        sp = Span(self, rec, name, ctx.get("span_id"), False,
                  start=start, tags=tags, stage=stage)
        if end is not None or start is not None:
            sp.finish(end=end)
        if stage and sp.dur is not None:
            _stage_series(name)[0].observe(sp.dur)
        return sp

    def fanin_span(self, name: str, parents: list[dict | None], **tags):
        """The shared device-dispatch span: a one-span trace of its own,
        tagged with every participating parent trace id.  Sampled iff any
        participant is (a batch serving one kept trace must be kept)."""
        live = [p for p in parents if p and p.get("trace_id")]
        if not live:
            return NOOP
        sampled = any(p.get("sampled") for p in live)
        if not sampled and self._rate <= 0.0:
            return NOOP
        with self._mu:
            rec = None
            if self._room_locked():
                rec = _Rec(self._new_id(), sampled)
                rec.had_root = True
                rec.open = 1
                self._live[rec.trace_id] = rec
        if rec is None:
            _count("dropped")
            return NOOP
        tags = dict(tags)
        tags["participants"] = sorted({p["trace_id"] for p in live})
        return Span(self, rec, name, None, True, tags=tags)

    # -- completion ----------------------------------------------------------

    def _span_done(self, sp: Span, t_end: float) -> None:
        rec = sp.rec
        finished = None
        with self._mu:
            if sp.dur is not None:
                return  # exactly-once under the lock: a racing double
                # finish (apply callback vs. propose-timeout cleanup) must
                # not double-decrement the record's open count
            sp.dur = t_end - sp.t0
            if len(rec.spans) < MAX_SPANS:
                rec.spans.append(sp)
            else:
                rec.truncated += 1
            rec.open -= 1
            if sp.stage:
                rec.stage_s += sp.dur
            if sp.root:
                rec.root = sp
                rec.root_dur = sp.dur
            if rec.open <= 0 and self._live.get(rec.trace_id) is rec:
                del self._live[rec.trace_id]
                finished = rec
        if finished is not None:
            self._commit(finished)

    def _commit(self, rec: _Rec) -> None:
        root = rec.root
        if root is not None and root.name.startswith("rpc."):
            # what the stages account for, and what they cannot see
            method = root.tags.get("method") or root.name[4:]
            _REQUEST_SECONDS.inc(root.dur, method=method)
            _REQUEST_ATTRIBUTED.inc(rec.stage_s + rec.shared_s, method=method)
        dur = rec.root_dur
        if dur is None and rec.spans:
            # rootless (joined-only, cross-process): the local fragment's
            # wall extent stands in for the root
            dur = max((s.dur or 0.0) for s in rec.spans)
        slow = dur is not None and dur >= self._slow_s
        if not rec.sampled and not slow:
            _count("dropped")
            return
        d = self._trace_dict(rec, dur, slow)
        with self._mu:
            if rec.sampled:
                self._recent.append(d)
            if slow:
                self._slow.append(d)
        _count("sampled" if rec.sampled else "promoted")

    def _trace_dict(self, rec: _Rec, dur, slow: bool) -> dict:
        return {
            "trace_id": rec.trace_id,
            "sampled": rec.sampled,
            "promoted": slow and not rec.sampled,
            "slow": slow,
            "start": round(rec.t0, 6),
            "duration_ms": round((dur or 0.0) * 1000, 3),
            "attributed_ms": round((rec.stage_s + rec.shared_s) * 1000, 3),
            "shared_ms": round(rec.shared_s * 1000, 3),
            "truncated": rec.truncated,
            "spans": [s.to_dict() for s in
                      sorted(rec.spans, key=lambda s: s.wall)],
        }

    def publish(self) -> None:
        """What only a reader of the registry needs, computed when it
        renders: the rings' sizes and the collector pauses noted so far."""
        _RING_TRACES.set(len(self._live), ring="live")
        _RING_TRACES.set(len(self._recent), ring="recent")
        _RING_TRACES.set(len(self._slow), ring="slow")
        self._drain_gc()

    # -- export (debug_traces RPC, /debug/traces, ctl.py trace) --------------

    def snapshot(self, limit: int = 20) -> dict:
        with self._mu:
            # limit<=0 means none: [-0:] would slice the WHOLE ring
            recent = list(self._recent)[-limit:] if limit > 0 else []
            slow = list(self._slow)[-limit:] if limit > 0 else []
            live = len(self._live)
        return {
            "sample_rate": self._rate,
            "slow_threshold_s": self._slow_s,
            "live": live,
            "recent": recent,
            "slow": slow,
        }

    def get(self, trace_id: str) -> dict | None:
        with self._mu:
            for ring in (self._slow, self._recent):
                for d in reversed(ring):
                    if d["trace_id"] == trace_id:
                        return d
        return None

    def reset(self) -> None:
        """Test isolation: drop every live record and both rings."""
        with self._mu:
            self._live.clear()
            self._recent.clear()
            self._slow.clear()
        self._state = threading.local()


def timeline(trace: dict) -> str:
    """Indented text rendering of one trace dict: children nested under
    parents, ordered by wall-clock start, durations in ms."""
    spans = trace.get("spans", [])
    by_parent: dict = {}
    ids = {s["span_id"] for s in spans}
    for s in spans:
        parent = s["parent_id"] if s["parent_id"] in ids else None
        by_parent.setdefault(parent, []).append(s)
    t0 = min((s["start"] for s in spans), default=trace.get("start", 0.0))
    out = [f"trace {trace['trace_id']} "
           f"({trace.get('duration_ms', 0)}ms"
           f"{', slow' if trace.get('slow') else ''}"
           f"{', promoted' if trace.get('promoted') else ''})"]

    def walk(parent, depth):
        for s in sorted(by_parent.get(parent, ()), key=lambda s: s["start"]):
            off = (s["start"] - t0) * 1000
            tags = " ".join(f"{k}={v}" for k, v in sorted(s["tags"].items()))
            out.append(f"{'  ' * depth}+{off:9.3f}ms {s['name']} "
                       f"[{s['duration_ms']}ms]{' ' + tags if tags else ''}")
            walk(s["span_id"], depth + 1)

    walk(None, 1)
    return "\n".join(out)


class _Attach:
    __slots__ = ("_tracer", "_sp", "_ctx")

    def __init__(self, tracer: Tracer, ctx: dict | None):
        self._tracer = tracer
        self._ctx = ctx
        self._sp = None

    def __enter__(self):
        ctx = self._ctx
        # unsampled live records attach too — their worker-side spans are
        # what tail promotion retroactively keeps on a slow request
        if not ctx or not ctx.get("trace_id"):
            return NOOP
        with self._tracer._mu:
            rec = self._tracer._live.get(ctx["trace_id"])
            if rec is None:
                return NOOP
        # a zero-cost anchor span is NOT created: attaching just points the
        # thread-local current at the remote parent so children nest there
        sp = Span.__new__(Span)
        sp.rec = rec
        sp.name = "<attached>"
        sp.span_id = ctx.get("span_id")
        sp.parent_id = None
        sp.t0 = time.perf_counter()
        sp.wall = time.time()
        sp.dur = 0.0  # never finished/recorded: a handle, not a span
        sp.tags = {}
        sp.root = False
        sp._tracer = self._tracer
        sp._prev = getattr(self._tracer._state, "cur", None)
        sp._pushed = True
        self._tracer._state.cur = sp
        self._sp = sp
        return sp

    def __exit__(self, *exc):
        if self._sp is not None:
            st = self._tracer._state
            if getattr(st, "cur", None) is self._sp:
                st.cur = self._sp._prev
            self._sp = None
        return False


TRACER = Tracer()
REGISTRY.on_render(TRACER.publish)

# -- module-level facade (the call-site API) --------------------------------


def enabled() -> bool:
    return TRACER.enabled()


def sample_rate() -> float:
    return TRACER.sample_rate()


def set_sample_rate(rate: float) -> None:
    TRACER.set_sample_rate(rate)


def slow_threshold() -> float:
    return TRACER.slow_threshold()


def set_slow_threshold(seconds: float) -> None:
    TRACER.set_slow_threshold(seconds)


def start_trace(name: str, ctx: dict | None = None,
                start: float | None = None, **tags):
    return TRACER.start_trace(name, ctx=ctx, start=start, **tags)


def span(name: str, **tags):
    return TRACER.span(name, **tags)


def stage(name: str, **tags):
    return TRACER.stage(name, **tags)


def timed_stage(name: str, **tags):
    return TRACER.timed_stage(name, **tags)


def set_mirror(factory) -> None:
    TRACER.set_mirror(factory)


def shared(ctxs: list):
    return TRACER.shared(ctxs)


def install_gc_hook() -> None:
    TRACER.install_gc_hook()


def begin(name: str, **tags):
    return TRACER.begin(name, **tags)


def record(name: str, start: float, end: float, **tags):
    return TRACER.record(name, start, end, **tags)


def current():
    return TRACER.current()


def current_context():
    return TRACER.current_context()


def current_trace_id():
    return TRACER.current_trace_id()


def inject(ctx: dict) -> dict:
    return TRACER.inject(ctx)


def attach(ctx: dict | None):
    return TRACER.attach(ctx)


def remote_span(ctx: dict | None, name: str, start: float | None = None,
                end: float | None = None, stage: bool = False, **tags):
    return TRACER.remote_span(ctx, name, start=start, end=end, stage=stage,
                              **tags)


def fanin_span(name: str, parents: list, **tags):
    return TRACER.fanin_span(name, parents, **tags)


def snapshot(limit: int = 20) -> dict:
    return TRACER.snapshot(limit)
