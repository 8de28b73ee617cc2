"""Cost-based path router + online geometry auto-tuner (docs/cost_router.md).

The serving plane has six execution paths (zone full-tile, unary encoded,
fused, xregion-cached, mesh-sharded, CPU fallback) and, since PR 13, a
performance observatory that measures what each path actually costs per
plan signature.  This module closes the loop:

* :class:`CostRouter` — per plan signature, pick the cheapest *eligible*
  path from the observatory's measured profiles (windowed mean latency
  plus compile-ledger amortization) instead of the static rule ladder.
  An explore/exploit guard keeps the profiles honest: a bounded epsilon
  re-probes warm non-best paths, and cold eligible paths are probed at a
  budgeted rate so no path starves and new shapes still get measured.
  When profiles are cold the router falls back to the static order — the
  candidate list callers pass is already in today's ladder order, so a
  cold router IS the old behavior.  Kill switch:
  ``TIKV_TPU_COST_ROUTER=0`` (or ``--no-cost-router``) routes every
  decision to the static head with reason ``kill_switch``.

* :class:`GeometryTuner` — periodically proposes geometry changes
  (``block_rows``, per-lane ``max_wait_s``) from the same measured
  profiles: hill-climb within validated bounds, ONE change in flight at
  a time, judged against the pre-change throughput baseline
  (``Observatory.totals`` deltas — robust to window aging), with
  automatic revert when the change regresses below
  ``revert_ratio`` x baseline.  Changes apply through the same validated
  setters POST /config uses, so out-of-range proposals are rejected, not
  applied.

Every decision is observable: ``tikv_coprocessor_cost_route_total
{path,reason}``, ``tikv_coprocessor_cost_route_delta_ms_total`` (chosen
minus best measured cost — also fed to PR 15's ``AdaptiveController`` so
overload tightening and path choice share evidence),
``tikv_coprocessor_geometry_tune_total{knob,action}``, per-sig decision
records in the observatory, and ``GET /debug/cost_router``.

Locking: ONE leaf lock owned by this module guards the rng / rotation
sequence / decision ring; observatory queries and metric increments
happen outside it (sanitizer-verified, module is in
``_SANITIZER_WIRED``).
"""

from __future__ import annotations

import os
import random
import time

from ..analysis.sanitizer import make_lock
from ..util.metrics import REGISTRY
from .observatory import OBSERVATORY

__all__ = [
    "CostRouter",
    "Decision",
    "GeometryTuner",
    "RouterConfig",
    "TunerConfig",
]

_DECISION_RING = 64
_HISTORY_RING = 32

ROUTE_REASONS = ("measured", "explore", "cold", "static_fallback",
                 "kill_switch")


def _enabled_env() -> bool:
    return os.environ.get("TIKV_TPU_COST_ROUTER", "1") not in ("0", "off", "")


class RouterConfig:
    """Explore/exploit knobs.  ``epsilon`` bounds the share of decisions
    that deliberately pick a warm non-best path; ``cold_probe_rate``
    budgets probes of eligible paths with no warm profile yet;
    ``min_count`` is the windowed serve count below which a profile is
    considered cold; ``compile_amortize_floor`` is the minimum serve count
    the compile ledger's wall time is spread over when pricing a path (a
    freshly compiled path must not price above the interpreter forever
    just because traffic hasn't amortized its one-time compile yet)."""

    __slots__ = ("epsilon", "cold_probe_rate", "min_count", "seed",
                 "compile_amortize_floor")

    def __init__(self, epsilon: float = 0.05, cold_probe_rate: float = 0.02,
                 min_count: int = 5, seed: int | None = None,
                 compile_amortize_floor: int = 64):
        if not 0.0 <= epsilon <= 0.5:
            raise ValueError("costmodel.epsilon must be in [0, 0.5]")
        if not 0.0 <= cold_probe_rate <= 0.5:
            raise ValueError("costmodel.cold_probe_rate must be in [0, 0.5]")
        if min_count < 1:
            raise ValueError("costmodel.min_count must be >= 1")
        if compile_amortize_floor < 1:
            raise ValueError("costmodel.compile_amortize_floor must be >= 1")
        self.epsilon = epsilon
        self.cold_probe_rate = cold_probe_rate
        self.min_count = min_count
        self.seed = seed
        self.compile_amortize_floor = compile_amortize_floor


class Decision:
    """One routing decision: the chosen path, why it won, and the cost
    table it was judged against (``delta_ms`` = chosen minus best measured
    cost; ``None`` when the chosen path has no warm profile yet)."""

    __slots__ = ("path", "reason", "cost_ms", "best_ms", "delta_ms")

    def __init__(self, path: str, reason: str, cost_ms: float | None = None,
                 best_ms: float | None = None):
        self.path = path
        self.reason = reason
        self.cost_ms = cost_ms
        self.best_ms = best_ms
        self.delta_ms = (round(cost_ms - best_ms, 4)
                         if cost_ms is not None and best_ms is not None
                         else None)

    def as_dict(self) -> dict:
        return {"path": self.path, "reason": self.reason,
                "cost_ms": self.cost_ms, "best_ms": self.best_ms,
                "delta_ms": self.delta_ms}


class CostRouter:
    """Pick the cheapest eligible path per plan signature from measured
    profiles, with bounded exploration and strict static fallback."""

    def __init__(self, observatory=None, config: RouterConfig | None = None,
                 enabled: bool | None = None, delta_sink=None):
        self.obs = observatory if observatory is not None else OBSERVATORY
        self.cfg = config or RouterConfig()
        self.enabled = _enabled_env() if enabled is None else enabled
        # chosen-vs-best deltas feed the overload AdaptiveController
        # (PR 15) so path waste and queue pressure share evidence
        self.delta_sink = delta_sink
        # LEAF lock: guards rng / rotation counters / rings only — the
        # observatory query and every metric increment happen outside it
        self._mu = make_lock("copr.costmodel")
        self._rng = random.Random(self.cfg.seed)
        self._seq: dict[str, int] = {}  # sig -> probe rotation counter
        self._recent: list[dict] = []
        self._reasons = dict.fromkeys(ROUTE_REASONS, 0)
        self._started = time.monotonic()

    def route(self, sig: str, candidates: list[str], *,
              desc: str = "") -> Decision:
        """Route one request.  ``candidates`` MUST be in static-ladder
        order (head = what today's rules would pick)."""
        if not candidates:
            raise ValueError("route() needs at least one candidate path")
        if not self.enabled:
            d = Decision(candidates[0], "kill_switch")
            self._note(sig, d, desc)
            return d
        table = self.obs.path_costs(
            sig, amortize_floor=self.cfg.compile_amortize_floor)
        warm = {p: c for p, c in table.items()
                if p in candidates and c.get("count", 0) >= self.cfg.min_count}
        cold = [p for p in candidates if p not in warm]
        if not warm:
            d = Decision(candidates[0], "static_fallback")
            self._note(sig, d, desc)
            return d
        best = min(warm, key=lambda p: warm[p]["cost_ms"])
        best_ms = warm[best]["cost_ms"]
        others = sorted(set(warm) - {best})
        with self._mu:
            r = self._rng.random()
            seq = self._seq[sig] = self._seq.get(sig, -1) + 1
            if len(self._seq) > 4 * _DECISION_RING:
                self._seq.pop(next(iter(self._seq)))
        p_cold = self.cfg.cold_probe_rate if cold else 0.0
        if r < p_cold:
            path = cold[seq % len(cold)]
            d = Decision(path, "cold", None, best_ms)
        elif others and r < p_cold + self.cfg.epsilon:
            path = others[seq % len(others)]
            d = Decision(path, "explore", warm[path]["cost_ms"], best_ms)
        else:
            d = Decision(best, "measured", best_ms, best_ms)
        self._note(sig, d, desc)
        return d

    def _note(self, sig: str, d: Decision, desc: str) -> None:
        with self._mu:
            self._reasons[d.reason] = self._reasons.get(d.reason, 0) + 1
            self._recent.append({"sig": sig, **d.as_dict()})
            if len(self._recent) > _DECISION_RING:
                del self._recent[: len(self._recent) - _DECISION_RING]
        REGISTRY.counter(
            "tikv_coprocessor_cost_route_total",
            "Cost-router path decisions, by chosen path and reason",
        ).inc(path=d.path, reason=d.reason)
        if d.delta_ms is not None and d.delta_ms > 0:
            REGISTRY.counter(
                "tikv_coprocessor_cost_route_delta_ms_total",
                "Chosen-vs-best measured cost gap across route decisions (ms)",
            ).inc(d.delta_ms)
        if sig:
            self.obs.record_route(sig, d.path, d.reason, desc=desc)
        if self.delta_sink is not None and d.delta_ms is not None:
            try:
                self.delta_sink(d.delta_ms, d.best_ms)
            except Exception:  # noqa: BLE001 — evidence feed is best-effort
                pass

    def snapshot(self) -> dict:
        with self._mu:
            return {
                "enabled": self.enabled,
                "epsilon": self.cfg.epsilon,
                "cold_probe_rate": self.cfg.cold_probe_rate,
                "min_count": self.cfg.min_count,
                "uptime_s": round(time.monotonic() - self._started, 1),
                "decisions_by_reason": dict(self._reasons),
                "recent": list(self._recent),
            }


class TunerConfig:
    """Geometry auto-tuning knobs.  ``min_serves`` is how many serves the
    in-flight change must accumulate before judging; ``revert_ratio`` is
    the throughput floor — measured rate below ``revert_ratio`` x the
    pre-change baseline triggers automatic revert; ``warmup_ticks`` ticks
    after a change are DISCARDED before measurement starts (a block_rows
    change invalidates warm images, so the first window pays rebuild +
    recompile — judging that transient would revert every good move);
    ``settle_ticks`` bounds how long a change may sit unjudged after
    warmup before it is abandoned (kept) for lack of traffic."""

    __slots__ = ("min_serves", "revert_ratio", "settle_ticks", "warmup_ticks")

    def __init__(self, min_serves: int = 16, revert_ratio: float = 0.7,
                 settle_ticks: int = 4, warmup_ticks: int = 1):
        if min_serves < 1:
            raise ValueError("tuner.min_serves must be >= 1")
        if not 0.0 < revert_ratio < 1.0:
            raise ValueError("tuner.revert_ratio must be in (0, 1)")
        if settle_ticks < 1:
            raise ValueError("tuner.settle_ticks must be >= 1")
        if warmup_ticks < 0:
            raise ValueError("tuner.warmup_ticks must be >= 0")
        self.min_serves = min_serves
        self.revert_ratio = revert_ratio
        self.settle_ticks = settle_ticks
        self.warmup_ticks = warmup_ticks


class _Knob:
    __slots__ = ("name", "get", "apply", "lo", "hi", "direction", "integer")

    def __init__(self, name, get, apply, lo, hi, integer):
        self.name = name
        self.get = get
        self.apply = apply
        self.lo = lo
        self.hi = hi
        # hill-climb direction: -1 halves, +1 doubles; flipped on revert
        # or when a proposal would leave the validated bounds
        self.direction = -1
        self.integer = integer

    def propose(self, cur):
        for _ in range(2):  # current direction, then the flip
            new = cur * 2 if self.direction > 0 else cur / 2
            if self.integer:
                new = int(new)
            if self.lo <= new <= self.hi:
                return new
            self.direction = -self.direction
        return None


class GeometryTuner:
    """Hill-climb serving geometry from measured throughput, one change in
    flight, with automatic revert on floor regression.

    ``tick()`` is the whole control loop: called periodically (the
    standalone server runs it on a background thread; tests and bench call
    it directly).  Idle tick: measure the baseline rate from observatory
    lifetime-total deltas, pick the next knob round-robin, propose a step,
    apply it through the registered setter (the same validated path POST
    /config uses — a rejected proposal counts, nothing is applied).
    In-flight tick: once ``min_serves`` serves have landed on the new
    geometry, judge the measured rate against the baseline and keep or
    revert."""

    def __init__(self, observatory=None, config: TunerConfig | None = None,
                 enabled: bool = True):
        self.obs = observatory if observatory is not None else OBSERVATORY
        self.cfg = config or TunerConfig()
        self.enabled = enabled
        self._mu = make_lock("copr.costmodel.tuner")
        self._knobs: list[_Knob] = []
        self._idx = 0
        self._inflight: dict | None = None
        self._last_totals: dict | None = None
        self._counts = {"propose": 0, "keep": 0, "revert": 0, "reject": 0}
        self._history: list[dict] = []

    def register(self, name: str, get, apply, lo, hi,
                 integer: bool = False) -> None:
        """Register a tunable knob: ``get()`` reads the live value,
        ``apply(v)`` installs one (raising rejects the proposal), and
        ``[lo, hi]`` are the validated bounds the hill-climb stays in."""
        self._knobs.append(_Knob(name, get, apply, lo, hi, integer))

    @staticmethod
    def _rate(before: dict, after: dict) -> tuple[float, int]:
        """(rows per busy-second, serves) accumulated between two
        ``Observatory.totals`` snapshots."""
        serves = after["serves"] - before["serves"]
        rows = after["rows"] - before["rows"]
        busy = after["busy_s"] - before["busy_s"]
        return (rows / busy if busy > 0 else 0.0), serves

    def _count(self, knob: str, action: str, **extra) -> None:
        self._counts[action] = self._counts.get(action, 0) + 1
        self._history.append({"knob": knob, "action": action, **extra})
        if len(self._history) > _HISTORY_RING:
            del self._history[: len(self._history) - _HISTORY_RING]

    def tick(self) -> dict | None:
        """One control-loop step; returns the action taken (or None)."""
        if not self.enabled or not self._knobs:
            return None
        totals = self.obs.totals()
        inflight = self._inflight
        if inflight is not None:
            if inflight["warmup"] < self.cfg.warmup_ticks:
                # discard the post-change transient (image rebuild +
                # recompile): re-anchor the measurement window and wait
                inflight["warmup"] += 1
                inflight["totals"] = totals
                return None
            rate, serves = self._rate(inflight["totals"], totals)
            inflight["ticks"] += 1
            if (serves < self.cfg.min_serves
                    and inflight["ticks"] < self.cfg.settle_ticks):
                return None  # still settling
            knob = inflight["knob"]
            base = inflight["baseline"]
            self._inflight = None
            self._last_totals = totals
            if (serves >= self.cfg.min_serves and base > 0
                    and rate < self.cfg.revert_ratio * base):
                # floor regression: put the old value back, flip direction
                try:
                    knob.apply(inflight["old"])
                except Exception:  # noqa: BLE001 — revert must not raise
                    pass
                knob.direction = -knob.direction
                ev = {"old": inflight["new"], "new": inflight["old"],
                      "rate": round(rate, 1), "baseline": round(base, 1)}
                with self._mu:
                    self._count(knob.name, "revert", **ev)
                self._metric(knob.name, "revert")
                return {"action": "revert", "knob": knob.name, **ev}
            ev = {"value": inflight["new"], "rate": round(rate, 1),
                  "baseline": round(base, 1), "serves": serves}
            with self._mu:
                self._count(knob.name, "keep", **ev)
            self._metric(knob.name, "keep")
            return {"action": "keep", "knob": knob.name, **ev}
        # idle: refresh the baseline window, then propose the next step
        last = self._last_totals
        self._last_totals = totals
        if last is None:
            return None
        rate, serves = self._rate(last, totals)
        if serves < self.cfg.min_serves:
            return None  # not enough traffic to judge anything
        knob = self._knobs[self._idx % len(self._knobs)]
        self._idx += 1
        cur = knob.get()
        new = knob.propose(cur)
        if new is None or new == cur:
            return None
        try:
            knob.apply(new)
        except Exception as exc:  # noqa: BLE001 — validated setter rejected
            with self._mu:
                self._count(knob.name, "reject", value=new, error=str(exc))
            self._metric(knob.name, "reject")
            return {"action": "reject", "knob": knob.name, "value": new}
        self._inflight = {"knob": knob, "old": cur, "new": new,
                          "baseline": rate, "totals": totals, "ticks": 0,
                          "warmup": 0}
        ev = {"old": cur, "new": new, "baseline": round(rate, 1)}
        with self._mu:
            self._count(knob.name, "propose", **ev)
        self._metric(knob.name, "propose")
        return {"action": "propose", "knob": knob.name, **ev}

    @staticmethod
    def _metric(knob: str, action: str) -> None:
        REGISTRY.counter(
            "tikv_coprocessor_geometry_tune_total",
            "Geometry auto-tuner steps, by knob and action",
        ).inc(knob=knob, action=action)

    def snapshot(self) -> dict:
        # knob getters may take their owners' locks — read them OUTSIDE
        # the tuner's leaf lock
        knobs = [
            {"name": k.name, "value": k.get(), "lo": k.lo, "hi": k.hi,
             "direction": k.direction}
            for k in self._knobs
        ]
        f = self._inflight
        inflight = ({"knob": f["knob"].name, "old": f["old"], "new": f["new"],
                     "baseline": round(f["baseline"], 1)}
                    if f is not None else None)
        with self._mu:
            return {
                "enabled": self.enabled,
                "knobs": knobs,
                "in_flight": inflight,
                "counts": dict(self._counts),
                "history": list(self._history),
            }
