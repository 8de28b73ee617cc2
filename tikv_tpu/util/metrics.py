"""Metrics registry with Prometheus text exposition.

Re-expression of the reference's prometheus-static-metric usage (every module
has a metrics.rs; served at /metrics by the status server): counters, gauges,
and histograms with labels, rendered in the Prometheus text format.
"""

from __future__ import annotations

import threading
from bisect import bisect_left

_DEFAULT_BUCKETS = (0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 10)


class _Metric:
    def __init__(self, name: str, help_: str, kind: str):
        self.name = name
        self.help = help_
        self.kind = kind
        self._mu = threading.Lock()


class _Bound:
    """One label set of a counter or histogram with its key made once
    (``metric.labels(...)``): for sites that move the same series on every
    call."""

    __slots__ = ("_add", "_key")

    def __init__(self, add, key: tuple):
        self._add = add
        self._key = key

    def inc(self, value: float = 1) -> None:
        self._add(self._key, value)

    def observe(self, value: float) -> None:
        self._add(self._key, value)


class Counter(_Metric):
    def __init__(self, name, help_=""):
        super().__init__(name, help_, "counter")
        self._values: dict[tuple, float] = {}

    def inc(self, value: float = 1, **labels) -> None:
        self._add(tuple(sorted(labels.items())), value)

    def _add(self, key: tuple, value: float) -> None:
        with self._mu:
            self._values[key] = self._values.get(key, 0) + value

    def labels(self, **labels) -> _Bound:
        return _Bound(self._add, tuple(sorted(labels.items())))

    def get(self, **labels) -> float:
        return self._values.get(tuple(sorted(labels.items())), 0)

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        with self._mu:
            items = sorted(self._values.items())
        for key, v in items:
            lines.append(f"{self.name}{_fmt_labels(key)} {v}")
        if not items:
            lines.append(f"{self.name} 0")
        return "\n".join(lines)


class Gauge(Counter):
    def __init__(self, name, help_=""):
        super().__init__(name, help_)
        self.kind = "gauge"

    def set(self, value: float, **labels) -> None:
        key = tuple(sorted(labels.items()))
        with self._mu:
            self._values[key] = value


class Histogram(_Metric):
    def __init__(self, name, help_="", buckets=_DEFAULT_BUCKETS):
        super().__init__(name, help_, "histogram")
        self.buckets = tuple(buckets)
        self._counts: dict[tuple, list[int]] = {}
        self._sum: dict[tuple, float] = {}
        self._n: dict[tuple, int] = {}

    def observe(self, value: float, **labels) -> None:
        self._add(tuple(sorted(labels.items())), value)

    def _add(self, key: tuple, value: float) -> None:
        # the first bucket whose bound is at or above the value; past the
        # last finite bound it is the +Inf bucket
        i = bisect_left(self.buckets, value)
        with self._mu:
            counts = self._counts.get(key)
            if counts is None:
                counts = self._counts[key] = [0] * (len(self.buckets) + 1)
            counts[i] += 1
            self._sum[key] = self._sum.get(key, 0) + value
            self._n[key] = self._n.get(key, 0) + 1

    def labels(self, **labels) -> _Bound:
        return _Bound(self._add, tuple(sorted(labels.items())))

    def count(self, **labels) -> int:
        """Observation count for a label set (the _count series)."""
        with self._mu:
            return self._n.get(tuple(sorted(labels.items())), 0)

    def percentile(self, q: float, **labels) -> float:
        """Bucket-interpolated percentile for a label set (``q`` in [0, 1]):
        the same estimate PromQL's histogram_quantile computes, locally.
        Returns 0.0 for an empty histogram; observations past the last
        finite bucket clamp to that bucket's bound (the +Inf bucket has no
        upper edge to interpolate toward)."""
        key = tuple(sorted(labels.items()))
        with self._mu:
            counts = list(self._counts.get(key, ()))
            n = self._n.get(key, 0)
        return percentile_from_buckets(self.buckets, counts, n, q)

    def total(self, **labels) -> float:
        """Accumulated observed value for a label set (the _sum series)."""
        with self._mu:
            return self._sum.get(tuple(sorted(labels.items())), 0.0)

    def label_sets(self) -> list[dict]:
        """The label sets observed so far (debug summaries)."""
        with self._mu:
            return [dict(key) for key in self._n]

    def render(self) -> str:
        lines = [f"# HELP {self.name} {self.help}", f"# TYPE {self.name} {self.kind}"]
        with self._mu:
            snapshot = [
                (key, list(counts), self._sum[key], self._n[key])
                for key, counts in sorted(self._counts.items())
            ]
        for key, counts, _s, _n in snapshot:
            cum = 0
            for i, b in enumerate(self.buckets):
                cum += counts[i]
                lines.append(f'{self.name}_bucket{_fmt_labels(key, le=str(b))} {cum}')
            cum += counts[-1]
            lines.append(f'{self.name}_bucket{_fmt_labels(key, le="+Inf")} {cum}')
            lines.append(f"{self.name}_sum{_fmt_labels(key)} {_s}")
            lines.append(f"{self.name}_count{_fmt_labels(key)} {_n}")
        return "\n".join(lines)


def percentile_from_buckets(buckets, counts, n: int, q: float) -> float:
    """Shared bucket-interpolation core behind :meth:`Histogram.percentile`
    and the observatory's windowed p50/p95/p99 accessors
    (copr/observatory.py): ``buckets`` are the finite upper bounds,
    ``counts`` the per-bucket (non-cumulative) counts with the +Inf
    overflow last, ``n`` the total observation count."""
    if n <= 0 or not counts:
        return 0.0
    q = min(max(q, 0.0), 1.0)
    rank = q * n
    cum = 0.0
    lower = 0.0
    for i, b in enumerate(buckets):
        c = counts[i] if i < len(counts) else 0
        if cum + c >= rank and c > 0:
            frac = (rank - cum) / c
            return lower + (b - lower) * frac
        cum += c
        lower = b
    # rank lands in the +Inf bucket: clamp to the last finite bound
    return float(buckets[-1]) if buckets else 0.0


def _fmt_labels(key: tuple, **extra) -> str:
    items = list(key) + sorted(extra.items())
    if not items:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in items)
    return "{" + inner + "}"


class Registry:
    def __init__(self):
        self._mu = threading.Lock()
        self._metrics: dict[str, _Metric] = {}
        self._render_hooks: list = []

    def on_render(self, hook) -> None:
        """``hook()`` runs before every render: for values only a reader
        needs, computed when one reads instead of on every change."""
        with self._mu:
            self._render_hooks.append(hook)

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_create(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_create(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "", buckets=_DEFAULT_BUCKETS) -> Histogram:
        return self._get_or_create(name, lambda: Histogram(name, help_, buckets))

    def _get_or_create(self, name, factory):
        with self._mu:
            m = self._metrics.get(name)
            if m is None:
                m = factory()
                self._metrics[name] = m
            return m

    def render(self) -> str:
        with self._mu:
            hooks = list(self._render_hooks)
        for hook in hooks:
            hook()
        with self._mu:
            return "\n".join(m.render() for m in self._metrics.values()) + "\n"


REGISTRY = Registry()
