"""Performance observatory: per-plan-signature path cost profiles.

The serving plane has six execution paths (zone full-tile, unary encoded,
fused, xregion-cached, mesh-sharded, CPU fallback) chosen by static
eligibility rules — and until now nobody *measured* what each path costs
per plan shape.  This module is the always-on, bounded, queryable
cost-measurement plane (docs/observatory.md):

* **Path cost profiles** — per (plan signature, path, encoding) streaming
  profiles over ring-buffered time windows: latency histogram with
  p50/p95/p99 accessors (the bucket-interpolation core is shared with
  ``util.metrics.Histogram.percentile``), rows/s, batch occupancy,
  padding-waste share, queue wait, decline/fallback causes, and exemplar
  trace ids from the tracing plane (docs/tracing.md) so "this sig's p99
  regressed" pivots straight to the exact slow trace.
* **Device-cost ledger** — every compile event at the jit boundary
  (``timed_jit`` wraps the jitted callables in jax_eval / jax_zone /
  parallel.mesh): wall time, plan sig, path, per-site executable cache
  size, and XLA ``cost_analysis()`` flops / bytes when the backend exposes
  them (gated behind ``TIKV_TPU_OBS_XLA_ANALYSIS=1`` — the AOT analysis
  pass costs a second lowering).  Recompile storms become a visible
  series instead of a latency mystery.
* **Pinned-HBM watermarks** — per pin-kind current bytes + high-water
  marks, fed by ``ColumnBlockCache.device_arrays`` build/evict deltas.
* **Regression floors** — ``write_floor``/``floor_diff`` snapshot per-sig
  baselines to disk; ``scripts/obs_diff.py`` gates any sig whose measured
  rows/s dropped more than the ratio (default 2x) against the stored
  floor.

Bounds: at most ``max_sigs`` signature entries (LRU, evictions counted),
``N_WINDOWS`` time windows per profile, ``_MAX_EXEMPLARS`` exemplars per
window, ``_LEDGER_CAP`` compile events.  The report hot path takes ONE
leaf lock owned by this module and calls nothing under it — it shares no
lock with serving (sanitizer-verified; the module is in
``_SANITIZER_WIRED``).

Kill switch: ``TIKV_TPU_OBSERVATORY=0`` turns every record call into a
no-op (the surfaces then report ``enabled: false``).
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from ..analysis.sanitizer import make_lock
from ..util import trace
from ..util.metrics import REGISTRY, percentile_from_buckets

__all__ = [
    "OBSERVATORY",
    "Observatory",
    "dag_sig",
    "floor_diff",
    "timed_jit",
]

# latency buckets (seconds) — finer than the metrics default at the fast
# end: warm device serves sit well under a millisecond
BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025,
           0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

N_WINDOWS = 8
_MAX_EXEMPLARS = 4
_LEDGER_CAP = 256
_MAX_DECLINE_CAUSES = 16

# pin-signature kind → watermark path label (docs/observatory.md): the
# stacked/nvoff pins are shared by the unary warm path and the xregion
# launcher, so they gauge under one "stacked" family label
PIN_PATHS = {
    "zone_layout": "zone",
    "shardslab": "mesh",
    "blockenc": "unary",
    "stackedenc": "stacked",
    "nvoff": "stacked",
}


def _enabled_env() -> bool:
    return os.environ.get("TIKV_TPU_OBSERVATORY", "1") not in ("0", "off", "")


def sig_id(sig: tuple) -> str:
    """Stable short id of a raw plan-signature tuple (the scheduler's
    grouping key) — what profiles, slow-log entries, and the compile
    ledger key on."""
    return hashlib.blake2b(repr(sig).encode(), digest_size=6).hexdigest()


def dag_sig(dag) -> tuple[str, str]:
    """(sig id, human description) for a DAG: the observatory's profile
    key.  The id hashes the plan's SHAPE signature (``plan_shape.split``:
    the normalization that decides which requests share a program, with the
    selection's literals taken out), so one statement is one profile
    whatever literals its executions carry, and the cost router learns a
    path once per shape."""
    from .plan_shape import split  # lazy: it imports the region cache

    shape = split(dag)[0]
    return sig_id(shape), _describe(shape)


def _describe(sig: tuple) -> str:
    """Compact plan string for operator displays (``ctl.py observatory``)."""
    parts = []
    for p in sig:
        k = p[0]
        if k == "tablescan":
            parts.append(f"scan(t{p[1]})")
        elif k == "indexscan":
            parts.append(f"iscan(t{p[1]}.i{p[2]})")
        elif k == "sel":
            parts.append(f"sel[{len(p[1])}]")
        elif k == "agg":
            ops = ",".join(str(a[0]) for a in p[3]) or "-"
            parts.append(f"agg({ops};g{len(p[2])})")
        elif k == "topn":
            parts.append(f"topn({p[1]})")
        elif k == "limit":
            parts.append(f"limit({p[1]})")
        elif k == "proj":
            parts.append(f"proj[{len(p[1])}]")
        elif k == "join":
            parts.append(f"join({p[1]};k{p[2]}=k{p[3]};b={_describe(p[4])})")
        elif k != "out":
            parts.append(str(k))
    return "|".join(parts)


class _Window:
    """One time window of a profile: non-cumulative latency buckets plus
    the secondary cost axes.  Exemplars keep the ``_MAX_EXEMPLARS`` slowest
    sampled trace ids of the window."""

    __slots__ = ("start", "count", "lat_sum", "rows", "occ_sum", "waste_sum",
                 "waste_n", "qwait_sum", "blk_exam", "blk_pruned",
                 "join_build", "join_probe", "join_out", "buckets",
                 "exemplars")

    def __init__(self, start: float):
        self.start = start
        self.count = 0
        self.lat_sum = 0.0
        self.rows = 0
        self.occ_sum = 0
        self.waste_sum = 0.0
        self.waste_n = 0
        self.qwait_sum = 0.0
        self.blk_exam = 0
        self.blk_pruned = 0
        self.join_build = 0
        self.join_probe = 0
        self.join_out = 0
        self.buckets = [0] * (len(BUCKETS) + 1)
        self.exemplars: list[tuple[float, str]] = []

    def add(self, latency_s, rows, occupancy, queue_wait_s, padding_waste,
            trace_id, blocks_examined=0, blocks_pruned=0,
            join_build_rows=0, join_probe_rows=0, join_out_rows=0) -> None:
        self.count += 1
        self.lat_sum += latency_s
        self.rows += rows
        self.occ_sum += occupancy
        self.qwait_sum += queue_wait_s
        self.blk_exam += blocks_examined
        self.blk_pruned += blocks_pruned
        self.join_build += join_build_rows
        self.join_probe += join_probe_rows
        self.join_out += join_out_rows
        if padding_waste is not None:
            self.waste_sum += padding_waste
            self.waste_n += 1
        for i, b in enumerate(BUCKETS):
            if latency_s <= b:
                self.buckets[i] += 1
                break
        else:
            self.buckets[-1] += 1
        if trace_id:
            ex = self.exemplars
            if len(ex) < _MAX_EXEMPLARS:
                ex.append((latency_s, trace_id))
            else:
                mi = min(range(len(ex)), key=lambda i: ex[i][0])
                if latency_s > ex[mi][0]:
                    ex[mi] = (latency_s, trace_id)


class _Profile:
    """Streaming cost profile for one (sig, path, encoding) key: a ring of
    time windows plus lifetime totals (the `top` sort key is lifetime time
    spent, like a profiler's cumulative column)."""

    __slots__ = ("window_s", "windows", "total_count", "total_lat",
                 "total_rows", "declines")

    def __init__(self, window_s: float, now: float):
        self.window_s = window_s
        self.windows: list[_Window] = [_Window(now)]
        self.total_count = 0
        self.total_lat = 0.0
        self.total_rows = 0
        self.declines: dict[str, int] = {}

    def _current(self, now: float) -> _Window:
        w = self.windows[-1]
        if now - w.start >= self.window_s:
            w = _Window(now)
            self.windows.append(w)
            if len(self.windows) > N_WINDOWS:
                del self.windows[: len(self.windows) - N_WINDOWS]
        return w

    def add(self, now, latency_s, rows, occupancy, queue_wait_s,
            padding_waste, trace_id, blocks_examined=0,
            blocks_pruned=0, join_build_rows=0, join_probe_rows=0,
            join_out_rows=0) -> None:
        self.total_count += 1
        self.total_lat += latency_s
        self.total_rows += rows
        self._current(now).add(latency_s, rows, occupancy, queue_wait_s,
                               padding_waste, trace_id,
                               blocks_examined, blocks_pruned,
                               join_build_rows, join_probe_rows,
                               join_out_rows)

    def decline(self, cause: str) -> None:
        if cause in self.declines or len(self.declines) < _MAX_DECLINE_CAUSES:
            self.declines[cause] = self.declines.get(cause, 0) + 1
        else:
            self.declines["other"] = self.declines.get("other", 0) + 1

    def view(self) -> dict:
        """Aggregate the retained windows into the reportable profile."""
        counts = [0] * (len(BUCKETS) + 1)
        n = lat = rows = occ = qwait = waste = 0.0
        waste_n = blk_exam = blk_pruned = 0
        j_build = j_probe = j_out = 0
        exemplars: list[tuple[float, str]] = []
        for w in self.windows:
            for i, c in enumerate(w.buckets):
                counts[i] += c
            n += w.count
            lat += w.lat_sum
            rows += w.rows
            occ += w.occ_sum
            qwait += w.qwait_sum
            waste += w.waste_sum
            waste_n += w.waste_n
            blk_exam += w.blk_exam
            blk_pruned += w.blk_pruned
            j_build += w.join_build
            j_probe += w.join_probe
            j_out += w.join_out
            exemplars.extend(w.exemplars)
        exemplars.sort(reverse=True)
        pct = lambda q: percentile_from_buckets(BUCKETS, counts, int(n), q)
        return {
            "count": int(n),
            "total_count": self.total_count,
            "time_spent_s": round(self.total_lat, 6),
            "window_count": int(n),
            "window_time_s": round(lat, 6),
            "rows": int(rows),
            "rows_per_s": round(rows / lat, 3) if lat > 0 else 0.0,
            "p50_ms": round(pct(0.50) * 1e3, 4),
            "p95_ms": round(pct(0.95) * 1e3, 4),
            "p99_ms": round(pct(0.99) * 1e3, 4),
            "mean_ms": round(lat / n * 1e3, 4) if n else 0.0,
            "mean_occupancy": round(occ / n, 3) if n else 0.0,
            "padding_waste": round(waste / waste_n, 4) if waste_n else None,
            # zone-map pruning effectiveness (docs/zone_maps.md): blocks the
            # serve paths examined vs proved empty and skipped/masked
            "blocks_examined": blk_exam,
            "blocks_pruned": blk_pruned,
            "pruned_fraction": (round(blk_pruned / blk_exam, 4)
                                if blk_exam else None),
            # device join profile (docs/device_join.md): per-sig build and
            # probe magnitudes plus output selectivity (out rows per probe
            # row) — what the cost router's join pricing keys on
            "join_build_rows": j_build,
            "join_probe_rows": j_probe,
            "join_out_rows": j_out,
            "join_selectivity": (round(j_out / j_probe, 4)
                                 if j_probe else None),
            "queue_wait_ms_mean": round(qwait / n * 1e3, 4) if n else 0.0,
            "declines": dict(self.declines),
            "exemplar_traces": [tid for _lat, tid in exemplars[:_MAX_EXEMPLARS]],
        }


class _SigEntry:
    __slots__ = ("desc", "paths", "last_used", "routes")

    def __init__(self, desc: str, now: float):
        self.desc = desc
        self.paths: dict[tuple[str, str], _Profile] = {}
        self.last_used = now
        # cost-router decisions for this sig: (path, reason) -> count
        self.routes: dict[tuple[str, str], int] = {}


class Observatory:
    """The bounded in-memory flight recorder every serve path reports into.

    One process-global instance (``OBSERVATORY``) serves the status
    server's ``/debug/observatory``, the ``debug_observatory`` RPC, and
    ``ctl.py observatory`` — mirroring how the tracer is surfaced."""

    def __init__(self, window_s: float | None = None,
                 max_sigs: int | None = None, enabled: bool | None = None):
        self.enabled = _enabled_env() if enabled is None else enabled
        self.window_s = window_s if window_s is not None else float(
            os.environ.get("TIKV_TPU_OBS_WINDOW_S", "15"))
        self.max_sigs = max_sigs if max_sigs is not None else int(
            os.environ.get("TIKV_TPU_OBS_MAX_SIGS", "64"))
        self.xla_analysis = os.environ.get(
            "TIKV_TPU_OBS_XLA_ANALYSIS", "0") == "1"
        # LEAF lock by construction: nothing is called while holding it —
        # the report hot path shares no lock with serving
        self._mu = make_lock("copr.observatory")
        self._sigs: dict[str, _SigEntry] = {}
        self._evicted = 0
        self._started = time.monotonic()
        # compile ledger: bounded event ring + per-(sig, path) aggregates +
        # per-site executable cache sizes
        self._compiles: list[dict] = []
        self._compile_agg: dict[tuple[str, str], dict] = {}
        self._cache_sizes: dict[str, int] = {}
        # pinned-HBM accounting by pin kind (PIN_PATHS): current + watermark
        self._hbm: dict[str, list[float]] = {}  # path -> [current, watermark]

    # -- report hot path ----------------------------------------------------

    def record_serve(self, sig: str, path: str, latency_s: float, *,
                     rows: int = 0, encoding: str = "plain",
                     occupancy: int = 1, queue_wait_s: float = 0.0,
                     padding_waste: float | None = None,
                     trace_id: str | None = None, desc: str = "",
                     blocks_examined: int = 0,
                     blocks_pruned: int = 0,
                     join_build_rows: int = 0,
                     join_probe_rows: int = 0,
                     join_out_rows: int = 0) -> None:
        """One served request on ``path`` under plan signature ``sig``.
        ``latency_s`` is the request's attributed share for batch-served
        riders (the scheduler's per-request share), the tracked total for
        unary serves."""
        if not self.enabled or not sig:
            return
        now = time.monotonic()
        with self._mu:
            entry = self._touch_locked(sig, desc, now)
            prof = entry.paths.get((path, encoding))
            if prof is None:
                prof = entry.paths[(path, encoding)] = _Profile(self.window_s, now)
            prof.add(now, latency_s, rows, occupancy, queue_wait_s,
                     padding_waste, trace_id, blocks_examined, blocks_pruned,
                     join_build_rows, join_probe_rows, join_out_rows)
        REGISTRY.counter(
            "tikv_observatory_serve_total",
            "Requests recorded by the performance observatory, by path",
        ).inc(path=path)
        REGISTRY.gauge(
            "tikv_observatory_evicted_sigs",
            "Profile signatures evicted by the observatory's LRU bound",
        ).set(self._evicted)
        REGISTRY.histogram(
            "tikv_observatory_serve_seconds",
            "Per-request attributed latency recorded by the observatory",
            buckets=BUCKETS,
        ).observe(latency_s, path=path)
        if rows:
            REGISTRY.counter(
                "tikv_observatory_rows_total",
                "Rows processed by recorded serves, by path",
            ).inc(rows, path=path)

    def record_decline(self, sig: str | None, path: str, cause: str) -> None:
        """A decline/fallback/shed on ``path`` — the per-sig half of the
        global ``tikv_coprocessor_path_fallback_total`` story: WHY does
        *this plan shape* keep missing its fast path."""
        if not self.enabled:
            return
        if sig:
            now = time.monotonic()
            with self._mu:
                entry = self._touch_locked(sig, "", now)
                prof = None
                for (p, _e), pr in entry.paths.items():
                    # attach to the path's existing encoding profile
                    if p == path:
                        prof = pr
                        break
                if prof is None:
                    prof = entry.paths[(path, "plain")] = _Profile(
                        self.window_s, now)
                prof.decline(cause)
        REGISTRY.counter(
            "tikv_observatory_decline_total",
            "Path declines/sheds recorded by the observatory, by path and cause",
        ).inc(path=path, cause=cause)

    def record_route(self, sig: str, path: str, reason: str,
                     desc: str = "") -> None:
        """One cost-router decision for ``sig`` (docs/cost_router.md):
        which path won and why (measured / explore / cold / static_fallback
        / kill_switch).  Kept per-sig so ``format_sig`` shows decisions next
        to the measured profiles they came from."""
        if not self.enabled or not sig:
            return
        now = time.monotonic()
        with self._mu:
            entry = self._touch_locked(sig, desc, now)
            key = (path, reason)
            entry.routes[key] = entry.routes.get(key, 0) + 1

    def path_costs(self, sig: str, amortize_floor: int = 1) -> dict[str, dict]:
        """Per-path cost view for the router: merge this sig's encodings
        per path label (highest window count wins — the encoding actually
        serving now), and fold the compile ledger's amortized cost in.
        ``cost_ms`` is the router's scalar: windowed p50 latency (the
        median is robust to the compile-laden first serve, which would
        otherwise double-count compile — it is already in the ledger) plus
        the sig's compile wall time amortized over its lifetime serves —
        ``amortize_floor`` caps the penalty for freshly compiled paths by
        assuming at least that many serves will share the compile (without
        it a just-compiled device path prices above the CPU pipeline until
        enough traffic has drained, and explore-rate trickle never
        un-sticks it)."""
        with self._mu:
            entry = self._sigs.get(sig)
            views: dict[str, dict] = {}
            if entry is not None:
                for (p, _e), prof in entry.paths.items():
                    v = prof.view()
                    if p in views and views[p]["count"] >= v["count"]:
                        continue
                    views[p] = v
            agg = {p: dict(a) for (s, p), a in self._compile_agg.items()
                   if s == sig}
        out: dict[str, dict] = {}
        for p, v in views.items():
            compile_ms = 0.0
            a = agg.get(p)
            if a and v["total_count"]:
                compile_ms = (a["wall_s"] * 1e3
                              / max(v["total_count"], amortize_floor))
            out[p] = {
                "count": v["count"],
                "total_count": v["total_count"],
                "mean_ms": v["mean_ms"],
                "p50_ms": v["p50_ms"],
                "p95_ms": v["p95_ms"],
                "rows_per_s": v["rows_per_s"],
                "queue_wait_ms_mean": v["queue_wait_ms_mean"],
                "mean_occupancy": v["mean_occupancy"],
                "compile_amortized_ms": round(compile_ms, 4),
                "cost_ms": round(v["p50_ms"] + compile_ms, 4),
            }
        return out

    def totals(self) -> dict:
        """Lifetime aggregate across every live sig/path — the geometry
        tuner's throughput probe: deltas of (rows, busy seconds, serves)
        between ticks are robust to window aging, unlike windowed rates."""
        with self._mu:
            count = rows = 0
            lat = 0.0
            for entry in self._sigs.values():
                for prof in entry.paths.values():
                    count += prof.total_count
                    rows += prof.total_rows
                    lat += prof.total_lat
        return {"serves": count, "rows": rows, "busy_s": round(lat, 6)}

    def _touch_locked(self, sig: str, desc: str, now: float) -> _SigEntry:
        entry = self._sigs.pop(sig, None)
        if entry is None:
            entry = _SigEntry(desc, now)
            while len(self._sigs) >= self.max_sigs:
                self._sigs.pop(next(iter(self._sigs)))
                self._evicted += 1
        else:
            if desc and not entry.desc:
                entry.desc = desc
            entry.last_used = now
        self._sigs[sig] = entry  # reinsert = LRU touch
        return entry

    # -- device-cost ledger -------------------------------------------------

    def record_compile(self, site: str, path: str, wall_s: float, *,
                       sig: str = "", cache_size: int | None = None,
                       flops: float | None = None,
                       bytes_accessed: float | None = None) -> None:
        """One compile event at the jit boundary: ``wall_s`` is the
        first-call wall time (trace + XLA compile + the first execute —
        the cost a request actually pays when it triggers the compile)."""
        if not self.enabled:
            return
        ev = {
            "t": round(time.monotonic() - self._started, 3),
            "site": site,
            "path": path,
            "sig": sig,
            "wall_s": round(wall_s, 6),
        }
        if cache_size is not None:
            ev["cache_size"] = cache_size
        if flops is not None:
            ev["flops"] = flops
        if bytes_accessed is not None:
            ev["bytes_accessed"] = bytes_accessed
        with self._mu:
            self._compiles.append(ev)
            if len(self._compiles) > _LEDGER_CAP:
                del self._compiles[: len(self._compiles) - _LEDGER_CAP]
            agg = self._compile_agg.setdefault(
                (sig, path), {"count": 0, "wall_s": 0.0})
            agg["count"] += 1
            agg["wall_s"] += wall_s
            while len(self._compile_agg) > self.max_sigs * 4:
                self._compile_agg.pop(next(iter(self._compile_agg)))
            if cache_size is not None:
                self._cache_sizes[site] = cache_size
                while len(self._cache_sizes) > 64:
                    self._cache_sizes.pop(next(iter(self._cache_sizes)))
        REGISTRY.counter(
            "tikv_observatory_compile_total",
            "XLA compile events at the jit boundary, by path",
        ).inc(path=path)
        REGISTRY.histogram(
            "tikv_observatory_compile_seconds",
            "First-call wall time of compile events (trace+compile+execute)",
            buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30),
        ).observe(wall_s, path=path)

    def note_pin(self, kind: str, delta_bytes: int) -> None:
        """Pinned-HBM delta for one pin-signature kind (fed by
        ``ColumnBlockCache.device_arrays``): maintains the current bytes
        and the high-water mark per path label."""
        if not self.enabled or not delta_bytes:
            return
        path = PIN_PATHS.get(kind, "stacked")
        with self._mu:
            cur = self._hbm.setdefault(path, [0.0, 0.0])
            cur[0] = max(cur[0] + delta_bytes, 0.0)
            cur[1] = max(cur[1], cur[0])
            snap_cur, snap_max = cur
        g = REGISTRY.gauge(
            "tikv_observatory_pinned_hbm_bytes",
            "Bytes currently pinned on devices, by pin path",
        )
        g.set(snap_cur, path=path)
        REGISTRY.gauge(
            "tikv_observatory_pinned_hbm_watermark_bytes",
            "High-water mark of device-pinned bytes, by pin path",
        ).set(snap_max, path=path)

    # -- queryable surfaces -------------------------------------------------

    def snapshot(self, sig: str | None = None) -> dict:
        """The full observatory view (``/debug/observatory``,
        ``debug_observatory``): per-sig path profiles, the compile ledger,
        and the HBM watermarks.  ``sig`` narrows to one signature."""
        with self._mu:
            sigs = {}
            for s, entry in self._sigs.items():
                if sig is not None and s != sig:
                    continue
                sigs[s] = {
                    "desc": entry.desc,
                    "paths": {
                        f"{p}|{e}": prof.view()
                        for (p, e), prof in entry.paths.items()
                    },
                }
                if entry.routes:
                    sigs[s]["routes"] = {
                        f"{p}|{r}": n for (p, r), n in entry.routes.items()
                    }
            compiles = list(self._compiles) if sig is None else [
                ev for ev in self._compiles if ev.get("sig") == sig]
            compile_agg = {
                f"{s or '-'}|{p}": dict(agg)
                for (s, p), agg in self._compile_agg.items()
                if sig is None or s == sig
            }
            out = {
                "enabled": self.enabled,
                "window_s": self.window_s,
                "n_windows": N_WINDOWS,
                "max_sigs": self.max_sigs,
                "live_sigs": len(self._sigs),
                "evicted_sigs": self._evicted,
                "uptime_s": round(time.monotonic() - self._started, 1),
                "sigs": sigs,
                "compiles": {
                    "events": compiles,
                    "by_sig_path": compile_agg,
                    "executable_cache_sizes": dict(self._cache_sizes),
                },
                "hbm": {
                    p: {"bytes": int(v[0]), "watermark_bytes": int(v[1])}
                    for p, v in self._hbm.items()
                },
            }
        REGISTRY.gauge(
            "tikv_observatory_sigs",
            "Plan signatures currently profiled by the observatory",
        ).set(out["live_sigs"])
        return out

    def top(self, n: int = 20) -> list[dict]:
        """(sig, path) rows sorted by lifetime time spent — a live
        profiler's cumulative-time top for the serving plane."""
        with self._mu:
            rows = []
            for s, entry in self._sigs.items():
                for (p, e), prof in entry.paths.items():
                    v = prof.view()
                    rows.append({
                        "sig": s,
                        "desc": entry.desc,
                        "path": p,
                        "encoding": e,
                        **{k: v[k] for k in (
                            "time_spent_s", "total_count", "count",
                            "rows_per_s", "p50_ms", "p95_ms", "p99_ms",
                            "mean_occupancy")},
                    })
        rows.sort(key=lambda r: r["time_spent_s"], reverse=True)
        return rows[:n]

    # -- regression floors --------------------------------------------------

    def floor(self, min_count: int = 3) -> dict:
        """Per-(sig, path) rows/s baselines from the current windows —
        what ``write_floor`` persists and ``scripts/obs_diff.py`` gates
        against."""
        snap = self.snapshot()
        sigs = {}
        for s, entry in snap["sigs"].items():
            paths = {}
            for pk, v in entry["paths"].items():
                if v["count"] >= min_count and v["rows_per_s"] > 0:
                    paths[pk] = {
                        "rows_per_s": v["rows_per_s"],
                        "p95_ms": v["p95_ms"],
                        "count": v["count"],
                        "desc": entry["desc"],
                    }
                    if v.get("pruned_fraction") is not None:
                        # zone-map effectiveness floor (docs/zone_maps.md)
                        paths[pk]["pruned_fraction"] = v["pruned_fraction"]
            if paths:
                sigs[s] = paths
        return {"version": 1, "written_at": time.time(), "sigs": sigs}

    def write_floor(self, path: str, min_count: int = 3) -> dict:
        fl = self.floor(min_count=min_count)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(fl, f, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return fl

    def reset(self) -> None:
        with self._mu:
            self._sigs.clear()
            self._compiles.clear()
            self._compile_agg.clear()
            self._cache_sizes.clear()
            self._hbm.clear()
            self._evicted = 0
            self._started = time.monotonic()


def floor_diff(floor: dict, current: dict, ratio: float = 2.0,
               min_count: int = 3) -> dict:
    """Compare a live/current observatory snapshot against a stored floor:
    any (sig, path) whose measured rows/s dropped more than ``ratio``
    below the floor is a regression.  ``current`` may be a full
    ``snapshot()`` dict or another ``floor()`` dict — both carry
    ``sigs``."""
    regressions = []
    checked = 0
    missing = []
    for s, paths in (floor.get("sigs") or {}).items():
        cur_entry = (current.get("sigs") or {}).get(s)
        for pk, base in paths.items():
            if cur_entry is None:
                missing.append(f"{s}/{pk}")
                continue
            cur = cur_entry.get("paths", cur_entry).get(pk)
            if isinstance(cur, dict) and "paths" in cur:  # defensive
                cur = None
            if cur is None:
                missing.append(f"{s}/{pk}")
                continue
            if cur.get("count", 0) < min_count:
                missing.append(f"{s}/{pk}")
                continue
            checked += 1
            base_r = float(base["rows_per_s"])
            cur_r = float(cur.get("rows_per_s") or 0.0)
            if cur_r <= 0 or base_r / max(cur_r, 1e-12) > ratio:
                regressions.append({
                    "sig": s,
                    "path": pk,
                    "desc": base.get("desc", ""),
                    "floor_rows_per_s": base_r,
                    "rows_per_s": cur_r,
                    "drop": round(base_r / max(cur_r, 1e-12), 2),
                })
            # zone-map pruning regression (docs/zone_maps.md): a plan whose
            # floor recorded meaningful pruning must keep pruning — a sharp
            # drop means zones stopped proving emptiness (a maintenance bug
            # or an eligibility regression), even when rows/s still passes
            # because the serve got cheaper elsewhere
            base_pf = base.get("pruned_fraction")
            cur_pf = cur.get("pruned_fraction")
            if (base_pf is not None and base_pf >= 0.05
                    and (cur_pf or 0.0) < base_pf / ratio):
                regressions.append({
                    "sig": s,
                    "path": pk,
                    "desc": base.get("desc", ""),
                    "kind": "pruning",
                    "floor_pruned_fraction": base_pf,
                    "pruned_fraction": cur_pf or 0.0,
                })
    return {
        "ok": not regressions,
        "checked": checked,
        "ratio": ratio,
        "regressions": regressions,
        "missing": missing,
    }


# ---------------------------------------------------------------------------
# jit-boundary hook
# ---------------------------------------------------------------------------


class _TimedJit:
    """Wraps an ALREADY-jitted callable, the one funnel of every jitted
    dispatch: the call is the stage ``device.launch`` (docs/tracing.md: host
    time inside the jitted call — argument handling, dispatch, and compile
    or cache fetch when there is one).  Steady-state calls also pay one
    C-level ``_cache_size()`` probe and an int compare; a call that grew
    the executable cache records a compile event (wall = that call's whole
    duration).  XLA cost/memory analysis is attempted only under
    ``TIKV_TPU_OBS_XLA_ANALYSIS=1`` (it costs a second lowering, and
    donated buffers can make it impossible after the fact — failures are
    silently skipped)."""

    __slots__ = ("fn", "site", "path", "sig", "_seen")

    def __init__(self, fn, site: str, path: str, sig: str = ""):
        self.fn = fn
        self.site = site
        self.path = path
        self.sig = sig or ""
        self._seen = -1

    def _cache_size(self):
        try:
            return self.fn._cache_size()
        except Exception:  # noqa: BLE001 — non-pjit callable: no ledger
            return None

    def __call__(self, *args):
        with trace.timed_stage("device.launch", site=self.site) as st:
            out = self.fn(*args)
            # the post-call probe is the only reliable compile detector: a
            # new argument SHAPE compiles even when the cache was already
            # warm, so a pre-call fast path would miss every recompile
            # after the first
            after = self._cache_size()
            grew = after is not None and after != self._seen
            if grew:
                st.tag(compiled=True)
        if grew:
            self._seen = after
            _DEVICE_COMPILES.inc(site=self.site)
            if OBSERVATORY.enabled:
                self._record_compile(args, st.seconds, after)
        return out

    def _record_compile(self, args, wall: float, cache_size: int) -> None:
        flops = nbytes = None
        if OBSERVATORY.xla_analysis:
            try:
                ca = self.fn.lower(*args).compile().cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0] if ca else {}
                flops = float(ca.get("flops", 0.0)) or None
                nbytes = float(ca.get("bytes accessed", 0.0)) or None
            except Exception:  # noqa: BLE001 — analysis is best-effort
                pass
        OBSERVATORY.record_compile(
            self.site, self.path, wall, sig=self.sig,
            cache_size=cache_size, flops=flops, bytes_accessed=nbytes)


_DEVICE_COMPILES = REGISTRY.counter(
    "tikv_device_compile_total",
    "Jitted calls that grew their executable cache (a compile or a fetch "
    "from the persistent cache), by timed_jit site")


def timed_jit(fn, site: str, path: str, sig: str = ""):
    """The funnel of every jitted dispatch.  Call sites keep their literal
    ``jax.jit(...)`` (the static-analysis jit rules still see it) and wrap
    the result: ``timed_jit(jax.jit(f), "jax_eval.scan", "unary",
    sig=self.obs_sig)``.  The program takes its site's name, the dot as an
    underscore (``jit_jax_eval_scan``): that is the XLA module's name in the
    device profiler's trace, and JAX reads it when it first traces ``f``.
    The wrapper is the stage ``device.launch`` and feeds the device-cost
    ledger."""
    inner = getattr(fn, "__wrapped__", None)
    if inner is not None:
        try:
            inner.__name__ = inner.__qualname__ = site.replace(".", "_")
        except (AttributeError, TypeError):  # no writable name: XLA's default
            pass
    return _TimedJit(fn, site, path, sig)


def format_top(rows: list[dict]) -> str:
    """Aligned text table for ``ctl.py observatory top`` and the status
    server's ``/debug/observatory`` — a live profiler top sorted by time
    spent."""
    hdr = (f"{'SIG':>12} {'PATH':>8} {'ENC':>7} {'SPENT_S':>9} {'REQS':>7} "
           f"{'ROWS/S':>12} {'P50_MS':>9} {'P95_MS':>9} {'P99_MS':>9} "
           f"{'OCC':>5}  DESC")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"{r['sig']:>12} {r['path']:>8} {r['encoding']:>7} "
            f"{r['time_spent_s']:>9.3f} {r['total_count']:>7} "
            f"{r['rows_per_s']:>12.1f} {r['p50_ms']:>9.3f} "
            f"{r['p95_ms']:>9.3f} {r['p99_ms']:>9.3f} "
            f"{r['mean_occupancy']:>5.1f}  {r['desc']}")
    return "\n".join(lines)


def format_sig(sig: str, entry: dict) -> str:
    """One signature's full profile as text (``ctl.py observatory sig``)."""
    lines = [f"sig {sig}  {entry.get('desc', '')}"]
    for pk, v in sorted(entry.get("paths", {}).items()):
        lines.append(
            f"  {pk}: n={v['count']} (lifetime {v['total_count']}) "
            f"rows/s={v['rows_per_s']} p50={v['p50_ms']}ms "
            f"p95={v['p95_ms']}ms p99={v['p99_ms']}ms "
            f"occ={v['mean_occupancy']} qwait={v['queue_wait_ms_mean']}ms"
            + (f" waste={v['padding_waste']}"
               if v.get("padding_waste") is not None else ""))
        if v.get("join_probe_rows"):
            lines.append(
                f"    join: build={v['join_build_rows']} "
                f"probe={v['join_probe_rows']} out={v['join_out_rows']} "
                f"selectivity={v['join_selectivity']}")
        if v.get("declines"):
            lines.append(f"    declines: {v['declines']}")
        if v.get("exemplar_traces"):
            lines.append(f"    exemplars: {', '.join(v['exemplar_traces'])}")
    routes = entry.get("routes")
    if routes:
        pairs = ", ".join(f"{k}={n}" for k, n in sorted(routes.items()))
        lines.append(f"  routes: {pairs}")
    return "\n".join(lines)


OBSERVATORY = Observatory()
