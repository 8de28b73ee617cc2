"""Chaos nemesis: seeded, deterministic fault injection over the raft plane.

Re-expression of the reference's nemesis layer — ``transport_simulate.rs``
filters composed into Jepsen-style schedules (the ``tests/failpoints/cases/``
suite drives the same machinery through the ``fail`` crate).  One
:class:`Nemesis` wraps a cluster's raft transport and injects:

* message **drop** (rate-based, optionally scoped to a region or an
  (src, dst) direction),
* message **delay** (held and re-injected later),
* message **duplication** and **reorder** (windowed shuffle),
* **asymmetric partitions** (A→B dropped while B→A flows) and symmetric
  ones,
* node **crash/restart** (delegating to the cluster harness),
* **disk stall** (the apply path wedged through the existing failpoints),

plus :meth:`heal`, which ends every fault, flushes held traffic, lifts
failpoints, and restarts crashed nodes — so every scenario ends in a state
the test can verify convergence from.

Works over BOTH cluster harnesses through their shared ``Filter`` API:

* :class:`~tikv_tpu.raft.cluster.Cluster` (in-memory ChannelTransport):
  fully deterministic.  Delays are measured in nemesis *steps*; the test
  pumps :meth:`Nemesis.advance` alongside ``cluster.tick()``.
* :class:`~tikv_tpu.server.cluster.ServerCluster` (framed TCP through
  ``RaftClient``): delays are wall-clock seconds, re-injection runs on a
  background delivery thread.  The *schedule* stays seeded/deterministic;
  thread interleaving is not (that is the point of the networked suite).

Determinism contract: every random decision (drop coin, delay draw, shuffle
order, schedule composition) comes from ONE ``random.Random(seed)``, so a
channel-mode scenario replays identically from its seed.

Re-injected (delayed/duplicated/reordered) messages bypass the filter stack
on purpose: a delay fault must not re-capture its own release, and raft
tolerates the resulting at-least-once delivery by design.

See ``docs/robustness.md`` for the scenario catalog.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field

from ..analysis.sanitizer import make_condition, make_lock
from . import failpoint
from .metrics import REGISTRY


def _count(fault: str) -> None:
    REGISTRY.counter(
        "tikv_chaos_injected_total", "Nemesis fault injections, by fault kind"
    ).inc(fault=fault)


# ---------------------------------------------------------------------------
# Faults
# ---------------------------------------------------------------------------

@dataclass
class Fault:
    """One active transport fault.  ``src``/``dst`` (store-id sets) scope
    directional faults; ``region_id`` scopes to one region's traffic."""

    kind: str                      # drop | delay | dup | reorder | partition
    rate: float = 1.0
    region_id: int | None = None
    src: frozenset | None = None   # match: from_peer.store_id in src
    dst: frozenset | None = None   # match: to_peer.store_id in dst
    delay: tuple[float, float] = (0.0, 0.0)  # seconds (server) / steps (channel)
    window: int = 4                # reorder shuffle window
    buf: list = field(default_factory=list, repr=False)  # reorder holding pen

    def matches(self, rmsg) -> bool:
        if self.region_id is not None and rmsg.region_id != self.region_id:
            return False
        if self.src is not None and rmsg.from_peer.store_id not in self.src:
            return False
        if self.dst is not None and rmsg.to_peer.store_id not in self.dst:
            return False
        return True


@dataclass
class _Held:
    due: float          # step count (channel) or monotonic seconds (server)
    seq: int
    to_store: int
    rmsg: object


class _NemesisFilter:
    """The transport-facing shim: one instance attached to every wrapped
    transport's ``filters`` list, delegating to the owning Nemesis."""

    def __init__(self, nemesis: "Nemesis"):
        self.nemesis = nemesis

    def before(self, rmsg) -> bool:
        return self.nemesis._on_send(rmsg)


# ---------------------------------------------------------------------------
# Cluster adapters
# ---------------------------------------------------------------------------

class _ChannelAdapter:
    """raft.cluster.Cluster: one shared ChannelTransport, logical time.

    Attaching also hooks every store's ``process_messages`` so each pump
    round advances the nemesis' step clock and re-injects due held
    messages — harness loops that pump internally (``RaftKv`` write/read
    barriers, admin waits, pre-existing ``pump=`` references) then make
    progress under delay faults without knowing a nemesis exists.  One
    step elapses per store-process call, so a delay of K steps spans
    roughly K/n_stores pump rounds — still fully deterministic.  Explicit
    :meth:`Nemesis.advance` remains available for hand-driven time."""

    realtime = False

    def __init__(self, cluster):
        self.cluster = cluster
        self._orig_pm: dict[int, object] = {}

    def attach(self, filt) -> None:
        self.cluster.transport.filters.append(filt)
        nemesis = filt.nemesis
        for sid, store in self.cluster.stores.items():
            orig = store.process_messages
            self._orig_pm[sid] = orig

            def pm(_orig=orig):
                nemesis.advance(1)
                return _orig()

            store.process_messages = pm

    def detach(self, filt) -> None:
        if filt in self.cluster.transport.filters:
            self.cluster.transport.filters.remove(filt)
        for sid, orig in self._orig_pm.items():
            store = self.cluster.stores.get(sid)
            if store is not None:
                store.process_messages = orig
        self._orig_pm.clear()

    def store_ids(self) -> list[int]:
        return list(self.cluster.stores)

    def reinject(self, to_store: int, rmsg) -> None:
        if to_store in self.cluster.stopped:
            return
        store = self.cluster.stores.get(to_store)
        if store is not None:
            store.enqueue_message(rmsg)

    def crash(self, store_id: int) -> None:
        self.cluster.stop_node(store_id)

    def restart(self, store_id: int) -> None:
        self.cluster.restart_node(store_id)


class _ServerAdapter:
    """server.cluster.ServerCluster: per-node RemoteTransports, wall clock."""

    realtime = True

    def __init__(self, cluster):
        self.cluster = cluster
        self._filter = None
        self._attached: list = []

    def attach(self, filt) -> None:
        self._filter = filt
        for node in self.cluster.nodes.values():
            node.transport.filters.append(filt)
            self._attached.append(node.transport)

    def detach(self, filt) -> None:
        for tr in self._attached:
            if filt in tr.filters:
                tr.filters.remove(filt)
        self._attached.clear()
        self._filter = None

    def store_ids(self) -> list[int]:
        return list(self.cluster.nodes)

    def reinject(self, to_store: int, rmsg) -> None:
        # below the filter stack: straight into the SENDER's connection pool
        frm = rmsg.from_peer.store_id
        node = self.cluster.nodes.get(frm)
        if node is None or not node.running:
            return
        node.transport.client.send(to_store, rmsg)

    def crash(self, store_id: int) -> None:
        self.cluster.stop_node(store_id)

    def restart(self, store_id: int) -> None:
        # a server-mode restart builds a NEW StoreNode (fresh transport):
        # the nemesis filter must follow it or the rebooted node's outbound
        # traffic would escape injection
        self.cluster.restart_node(store_id)
        if self._filter is not None:
            tr = self.cluster.nodes[store_id].transport
            tr.filters.append(self._filter)
            self._attached.append(tr)


class _NullAdapter:
    """No transport: a nemesis over ``cluster=None`` injects LOAD-shaped
    faults only (hot_tenant / slow_consumer / memory_squeeze) — useful for
    single-endpoint overload scenarios with no raft plane at all."""

    realtime = True

    def attach(self, filt) -> None:
        pass

    def detach(self, filt) -> None:
        pass

    def store_ids(self) -> list[int]:
        return []

    def reinject(self, to_store: int, rmsg) -> None:
        pass

    def crash(self, store_id: int) -> None:
        raise ValueError("no cluster attached to this nemesis")

    restart = crash


def _adapter_for(cluster):
    if cluster is None:
        return _NullAdapter()
    if hasattr(cluster, "nodes"):
        return _ServerAdapter(cluster)
    if hasattr(cluster, "transport"):
        return _ChannelAdapter(cluster)
    raise TypeError(f"unsupported cluster harness: {type(cluster).__name__}")


# ---------------------------------------------------------------------------
# The nemesis
# ---------------------------------------------------------------------------

_STALL_POINT = "apply_before_exec"  # the raft apply path's write gate


class Nemesis:
    def __init__(self, cluster, seed: int = 0):
        import random

        self.adapter = _adapter_for(cluster)
        self.rng = random.Random(seed)
        self.seed = seed
        self._mu = make_condition("util.chaos", make_lock("util.chaos"))
        self._faults: list[Fault] = []
        self._held: list[_Held] = []
        self._seq = 0
        self._step = 0              # logical clock (channel mode)
        self._crashed: set[int] = set()
        self._stalled: str | None = None
        # load-shaped faults (docs/robustness.md "Overload"): seeded flood
        # threads + squeezed cache budgets, all undone by heal()
        self._load_stop = threading.Event()
        self._load_threads: list[threading.Thread] = []
        self._squeezed: list[tuple[object, int, dict]] = []
        self._closed = False
        self._deliverer: threading.Thread | None = None
        self._filter = _NemesisFilter(self)
        self.adapter.attach(self._filter)
        # observability for test debugging
        self.stats = {"dropped": 0, "delayed": 0, "duplicated": 0,
                      "reordered": 0, "delivered_late": 0}

    # -- fault surface ------------------------------------------------------

    def _add(self, f: Fault) -> Fault:
        _count(f.kind)
        with self._mu:
            self._faults.append(f)
            self._mu.notify_all()
        return f

    def drop(self, rate: float = 1.0, region_id: int | None = None,
             src=None, dst=None) -> Fault:
        return self._add(Fault("drop", rate=rate, region_id=region_id,
                               src=_fset(src), dst=_fset(dst)))

    def delay(self, lo: float, hi: float, rate: float = 1.0,
              region_id: int | None = None, src=None, dst=None) -> Fault:
        """Hold matching messages for uniform(lo, hi) — seconds in server
        mode, :meth:`advance` steps in channel mode."""
        return self._add(Fault("delay", rate=rate, delay=(lo, hi),
                               region_id=region_id, src=_fset(src), dst=_fset(dst)))

    def duplicate(self, rate: float = 0.2, region_id: int | None = None) -> Fault:
        return self._add(Fault("dup", rate=rate, region_id=region_id))

    def reorder(self, window: int = 4, rate: float = 1.0,
                region_id: int | None = None) -> Fault:
        """Capture matching messages; every ``window`` captures release the
        pen in a seeded shuffle (at latest on heal/advance)."""
        return self._add(Fault("reorder", rate=rate, window=window,
                               region_id=region_id))

    def partition(self, side_a, side_b, symmetric: bool = True) -> list[Fault]:
        """Cut side_a → side_b (and the reverse when symmetric).  With
        ``symmetric=False`` this is the nasty half-open link: A's messages
        die while B still reaches A."""
        a, b = _fset(side_a), _fset(side_b)
        faults = [self._add(Fault("partition", src=a, dst=b))]
        if symmetric:
            faults.append(self._add(Fault("partition", src=b, dst=a)))
        return faults

    def isolate(self, store_id: int, incoming: bool = True,
                outgoing: bool = True) -> list[Fault]:
        others = [s for s in self.adapter.store_ids() if s != store_id]
        faults = []
        if outgoing:
            faults += self.partition({store_id}, others, symmetric=False)
        if incoming:
            faults += self.partition(others, {store_id}, symmetric=False)
        return faults

    def remove(self, fault) -> None:
        faults = fault if isinstance(fault, list) else [fault]
        with self._mu:
            for f in faults:
                if f in self._faults:
                    self._faults.remove(f)
                self._flush_reorder_locked(f)
            self._mu.notify_all()

    def crash(self, store_id: int) -> None:
        _count("crash")
        with self._mu:
            self._crashed.add(store_id)
        self.adapter.crash(store_id)

    def restart(self, store_id: int) -> None:
        _count("restart")
        with self._mu:
            self._crashed.discard(store_id)
        self.adapter.restart(store_id)

    def corrupt_image(self, cache, region_id: int | None = None,
                      mode: str | None = None, bits: int = 1):
        """Silent-data-corruption fault (docs/integrity.md): flip bits in a
        warm region image's DERIVED state — decoded cached block columns
        (``mode="block"``: the post-decode plane the device serves, caught
        by shadow reads and the deep scrub), the ENCODED payload of a
        compressed-resident column (``mode="encoded"``: bitpacked lanes /
        RLE run values, docs/compressed_columns.md — proves detection
        covers the encoded plane), or a buffered write-through
        pending delta (``mode="pending"``: a bad fold input, caught by the
        fingerprint-vs-oracle hash scrub).  Direct-injection like
        :meth:`disk_stall` — it targets a cache, not the transport — so it
        composes with any transport schedule.  Seeded off the nemesis rng;
        returns a description of what was corrupted, or None when nothing
        matched."""
        _count("corrupt_image")
        info = corrupt_image(cache, self.rng, region_id=region_id,
                             mode=mode, bits=bits)
        if info is not None:
            self.stats["corrupted"] = self.stats.get("corrupted", 0) + 1
        return info

    # -- load-shaped faults (docs/robustness.md "Overload") ------------------

    def hot_tenant(self, submit, qps: float = 200.0, tenant: str = "hot",
                   threads: int = 2, hold_s: float = 0.0,
                   fault: str = "hot_tenant") -> None:
        """One tenant floods the serving plane: seeded threads call
        ``submit(i, tenant)`` at ~``qps`` total until :meth:`heal` (every
        outcome — served, shed, error — is counted, never raised; the
        overload plane under test decides which it is).  Pacing draws from
        a per-thread rng DERIVED from the nemesis seed, so the schedule
        replays while live threads stay independent."""
        import random

        _count(fault)
        self.stats.setdefault(f"{fault}_requests", 0)
        self.stats.setdefault(f"{fault}_errors", 0)
        interval = threads / max(qps, 0.001)
        stop = self._load_stop

        def flood(idx: int):
            rng = random.Random(f"{self.seed}:{fault}:{idx}")
            i = 0
            while not stop.is_set():
                try:
                    submit(i, tenant)
                except Exception:  # noqa: BLE001 — shed/busy IS the point
                    with self._mu:
                        self.stats[f"{fault}_errors"] += 1
                else:
                    with self._mu:
                        self.stats[f"{fault}_requests"] += 1
                i += 1
                if hold_s:
                    # slow consumer: sit on the response/stream slot before
                    # asking for more — the client that drains too slowly
                    stop.wait(hold_s)
                stop.wait(interval * rng.uniform(0.5, 1.5))

        for idx in range(max(threads, 1)):
            t = threading.Thread(target=flood, args=(idx,), daemon=True,
                                 name=f"chaos-{fault}-{idx}")
            with self._mu:
                self._load_threads.append(t)
            t.start()

    def slow_consumer(self, submit, qps: float = 20.0, hold_s: float = 0.05,
                      tenant: str = "slow", threads: int = 1) -> None:
        """A tenant that consumes responses slowly: each ``submit`` is
        followed by a ``hold_s`` pause modelling a client sitting on its
        response before requesting more (the stream-backpressure shape)."""
        self.hot_tenant(submit, qps=qps, tenant=tenant, threads=threads,
                        hold_s=hold_s, fault="slow_consumer")

    def memory_squeeze(self, cache, fraction: float = 0.5) -> None:
        """Shrink a region column cache's byte budget (and every tenant
        partition) to ``fraction`` of its current value — memory pressure
        without traffic.  Enforcement (and the per-tenant degradation
        ladder) runs immediately; :meth:`heal` restores the budgets."""
        _count("memory_squeeze")
        with self._mu:
            self._squeezed.append((cache, cache.byte_budget,
                                   dict(cache._tenant_budgets)))
            self.stats["squeezed"] = self.stats.get("squeezed", 0) + 1
        cache.set_tenant_budgets({
            t: max(int(b * fraction), 1)
            for t, b in cache._tenant_budgets.items()
        })
        cache.resize_budget(max(int(cache.byte_budget * fraction), 1))

    def _stop_load_locked(self):
        threads, self._load_threads = self._load_threads, []
        squeezed, self._squeezed = self._squeezed, []
        return threads, squeezed

    def disk_stall(self, ms: float | None = None, count: int | None = None) -> None:
        """Wedge the apply path through the existing ``apply_before_exec``
        failpoint: ``ms`` → every apply sleeps that long (slow disk);
        ``ms=None`` → a hard pause until heal.  Process-global (failpoints
        are), so this models a cluster-wide slow/stuck disk."""
        _count("stall")
        action = "pause" if ms is None else f"sleep({ms})"
        if count is not None:
            action = f"{count}*{action}"
        with self._mu:
            self._stalled = _STALL_POINT
        failpoint.cfg(_STALL_POINT, action)

    # -- heal ---------------------------------------------------------------

    def heal(self) -> None:
        """End EVERY fault: clear the fault set, release held/penned
        messages, lift the disk stall, and restart crashed nodes.  After
        heal the transport is transparent again — convergence asserts run
        from here."""
        _count("heal")
        with self._mu:
            for f in self._faults:
                self._flush_reorder_locked(f)
            self._faults.clear()
            for h in self._held:
                h.due = 0.0  # everything is due now
            self._mu.notify_all()
            crashed = sorted(self._crashed)
            self._crashed.clear()
            stalled = self._stalled
            self._stalled = None
            # load faults end with everything else: flood threads stop,
            # squeezed budgets restore
            threads, squeezed = self._stop_load_locked()
            stop_evt = self._load_stop
            self._load_stop = threading.Event()
        stop_evt.set()
        for t in threads:
            t.join(timeout=2.0)
        # restore NEWEST-first: stacked squeezes of one cache snapshot the
        # already-squeezed budgets, so the earliest (true original)
        # snapshot must win
        for cache, byte_budget, tenant_budgets in reversed(squeezed):
            cache.set_tenant_budgets(tenant_budgets)
            cache.resize_budget(byte_budget)
        if stalled is not None:
            failpoint.remove(stalled)
        self._deliver_due(float("inf"))
        for sid in crashed:
            self.adapter.restart(sid)

    def close(self) -> None:
        with self._mu:
            self._closed = True
            self._mu.notify_all()
            threads, _squeezed = self._stop_load_locked()
        self._load_stop.set()
        for t in threads:
            t.join(timeout=2.0)
        self.adapter.detach(self._filter)
        if self._deliverer is not None:
            self._deliverer.join(timeout=2.0)
            self._deliverer = None

    # -- logical time (channel mode) ----------------------------------------

    def advance(self, steps: int = 1) -> int:
        """Advance the nemesis' logical clock (channel mode): deliver held
        messages whose step came due, and flush any filled/stale reorder
        pens.  Returns how many messages were re-injected."""
        with self._mu:
            self._step += steps
            for f in self._faults:
                self._flush_reorder_locked(f)
            now = float(self._step)
        return self._deliver_due(now)

    # -- schedules ----------------------------------------------------------

    def random_steps(self, n: int, ops=("drop", "delay", "partition",
                                        "crash_restart", "dup", "reorder")):
        """A seeded schedule: n (op, kwargs) tuples drawn from ``ops``.
        Pure data — the caller applies them via :meth:`apply_step` with
        whatever pacing its harness needs — so a failing scenario replays
        from (seed, n, ops) alone."""
        import random

        # a DERIVED rng: the schedule must replay from (seed, n, ops) even
        # when live traffic has already consumed draws from self.rng
        rng = random.Random(f"{self.seed}:{n}:{sorted(ops)}")
        sids = self.adapter.store_ids()
        steps = []
        for _ in range(n):
            op = rng.choice(list(ops))
            if op == "drop":
                steps.append(("drop", {"rate": rng.uniform(0.1, 0.6)}))
            elif op == "delay":
                lo = rng.uniform(0.001, 0.01)
                steps.append(("delay", {"lo": lo, "hi": lo * 4,
                                        "rate": rng.uniform(0.2, 0.8)}))
            elif op == "dup":
                steps.append(("dup", {"rate": rng.uniform(0.1, 0.5)}))
            elif op == "reorder":
                steps.append(("reorder", {"window": rng.randint(2, 6)}))
            elif op == "partition":
                k = max(1, len(sids) // 2)
                side = rng.sample(sids, k)
                steps.append(("partition", {
                    "side_a": side,
                    "side_b": [s for s in sids if s not in side],
                    "symmetric": rng.random() < 0.5,
                }))
            elif op == "crash_restart":
                steps.append(("crash_restart", {"store_id": rng.choice(sids)}))
        return steps

    def apply_step(self, op: str, kw: dict):
        if op == "crash_restart":
            sid = kw["store_id"]
            if sid in self._crashed:
                self.restart(sid)
            else:
                self.crash(sid)
            return None
        if op == "dup":
            return self.duplicate(**kw)
        return getattr(self, op)(**kw)

    # -- the filter path ----------------------------------------------------

    def _on_send(self, rmsg) -> bool:
        """True = let the transport deliver; False = we dropped or took it."""
        with self._mu:
            if self._closed:
                return True
            for f in self._faults:
                if not f.matches(rmsg):
                    continue
                if f.kind == "partition":
                    self.stats["dropped"] += 1
                    _count("partition_drop")
                    return False
                if f.rate < 1.0 and self.rng.random() >= f.rate:
                    continue
                if f.kind == "drop":
                    self.stats["dropped"] += 1
                    _count("drop")
                    return False
                if f.kind == "dup":
                    self.stats["duplicated"] += 1
                    _count("dup")
                    self._hold_locked(rmsg, 0.0)
                    return True  # original delivers now, the copy follows
                if f.kind == "delay":
                    self.stats["delayed"] += 1
                    _count("delay")
                    self._hold_locked(rmsg, self.rng.uniform(*f.delay))
                    return False
                if f.kind == "reorder":
                    self.stats["reordered"] += 1
                    _count("reorder")
                    f.buf.append(rmsg)
                    if len(f.buf) >= f.window:
                        self._flush_reorder_locked(f)
                    return False
            return True

    # -- held-message plumbing ----------------------------------------------

    def _hold_locked(self, rmsg, delay: float) -> None:
        now = float(self._step) if not self.adapter.realtime else time.monotonic()
        self._seq += 1
        self._held.append(_Held(now + delay, self._seq,
                                rmsg.to_peer.store_id, rmsg))
        if self.adapter.realtime:
            self._ensure_deliverer_locked()
            self._mu.notify_all()

    def _flush_reorder_locked(self, f: Fault) -> None:
        if f.kind != "reorder" or not f.buf:
            return
        pen, f.buf = f.buf, []
        self.rng.shuffle(pen)
        for rmsg in pen:
            self._hold_locked(rmsg, 0.0)

    def _deliver_due(self, now: float) -> int:
        with self._mu:
            due = [h for h in self._held if h.due <= now]
            self._held = [h for h in self._held if h.due > now]
            due.sort(key=lambda h: (h.due, h.seq))
        for h in due:
            # outside the lock: re-injection walks the receiving store's
            # enqueue path (channel) or the sender's socket pool (server)
            self.adapter.reinject(h.to_store, h.rmsg)
            self.stats["delivered_late"] += 1
        return len(due)

    def _ensure_deliverer_locked(self) -> None:
        if self._deliverer is not None or self._closed:
            return
        self._deliverer = threading.Thread(
            target=self._deliver_loop, daemon=True, name="chaos-deliver"
        )
        self._deliverer.start()

    def _deliver_loop(self) -> None:
        while True:
            with self._mu:
                if self._closed:
                    return
                if not self._held:
                    self._mu.wait(0.5)
                    continue
                next_due = min(h.due for h in self._held)
                wait = next_due - time.monotonic()
                if wait > 0:
                    self._mu.wait(min(wait, 0.05))
                    continue
            self._deliver_due(time.monotonic())


def corrupt_image(cache, rng, region_id: int | None = None,
                  mode: str | None = None, bits: int = 1):
    """Flip bits in a resident region image (SDC injection core; see
    :meth:`Nemesis.corrupt_image`).  Mutates under the cache's manager lock
    and drops the image's device pins so the next warm serve re-pins the
    corrupted host state — modelling decode/fold/device corruption that the
    serving path would actually return."""
    import numpy as np

    with cache._mu:
        imgs = [(k, img) for k, img in cache._images.items()
                if region_id is None or k[0] == region_id]
        if not imgs:
            return None
        key, img = imgs[rng.randrange(len(imgs))]
        has_pending = bool(img.wt_pending and img.wt_pending["changed"])
        if mode is None:
            mode = "pending" if has_pending and rng.random() < 0.5 else "block"
        if mode == "pending":
            if not has_pending:
                return None
            handles = sorted(img.wt_pending["changed"])
            h = handles[rng.randrange(len(handles))]
            # the handle's newest write in the chain is the one a fold takes
            bi, wi = max((bi, wi) for bi, (_i, ws, _l) in enumerate(img.chain)
                         for wi, w in enumerate(ws) if w[0] == h)
            _h, cts, v = img.chain[bi][1][wi]
            if not v:
                return None
            ba = bytearray(v)
            for _ in range(max(bits, 1)):
                i = rng.randrange(len(ba))
                ba[i] ^= 1 << rng.randrange(8)
            img.chain[bi][1][wi] = (h, cts, bytes(ba))
            return {"mode": "pending", "region_id": key[0], "handle": int(h)}
        blocks = img.block_cache.blocks
        if not blocks:
            return None
        from ..copr.encoding import EncodedColumn

        for _ in range(64):  # retry until a corruptible cell is found
            bi = rng.randrange(len(blocks))
            blk = blocks[bi]
            if blk.n_valid == 0:
                continue
            ci = rng.randrange(len(blk.cols))
            col = blk.cols[ci]
            if mode == "encoded" and not isinstance(col, EncodedColumn):
                continue
            r = rng.randrange(blk.n_valid)
            if bool(np.asarray(col.nulls)[r]):
                continue
            if isinstance(col, EncodedColumn):
                # flip the ENCODED payload bytes — the resident form the
                # device actually serves (docs/compressed_columns.md); the
                # materialized decode cache is purged so host consumers
                # (deep scrub, late-materialize gathers) see the flip too
                if col.kind == "bp":
                    arr = col.packed
                    arr[r] ^= np.asarray(
                        1 << rng.randrange(max(arr.dtype.itemsize * 8 - 1, 1)),
                        dtype=arr.dtype)
                else:
                    run = int(np.searchsorted(col.run_ends, r, side="right"))
                    col.run_values[run] ^= np.int64(1) << np.int64(
                        rng.randrange(63))
                col.purge_decoded()
                blk.zones = None  # zone maps rebuild from the flipped bytes
                img.block_cache.drop_device()
                # mode="block" over an encoded column IS an encoded flip —
                # the payload is that column's resident block plane
                return {"mode": mode, "region_id": key[0], "block": bi,
                        "column": ci, "row": r, "kind": col.kind}
            data = col.data
            if col.is_dict_encoded:
                dlen = len(col.dictionary)
                if dlen < 2:
                    continue
                data[r] = (int(data[r]) + 1 + rng.randrange(dlen - 1)) % dlen
            elif isinstance(data, np.ndarray) and data.dtype == object:
                v = data[r]
                if not isinstance(v, (bytes, bytearray)) or len(v) == 0:
                    continue
                ba = bytearray(v)
                i = rng.randrange(len(ba))
                ba[i] ^= 1 << rng.randrange(8)
                data[r] = bytes(ba)
            else:
                arr = np.asarray(data)
                if arr.dtype.itemsize != 8:
                    continue
                # bit-flip through a u64 view (int64 and float64 alike);
                # bit 63 excluded so int corruption stays value-level, not
                # a sign explosion that might overflow downstream casts
                arr.view(np.uint64)[r] ^= np.uint64(1) << np.uint64(
                    rng.randrange(63))
            blk.zones = None  # zone maps rebuild from the flipped bytes
            img.block_cache.drop_device()
            return {"mode": "block", "region_id": key[0], "block": bi,
                    "column": ci, "row": r}
        return None


def _fset(v) -> frozenset | None:
    if v is None:
        return None
    if isinstance(v, (int,)):
        return frozenset((v,))
    return frozenset(v)
