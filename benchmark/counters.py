"""The store's counters and histograms, read through the registry's own
Prometheus text: a snapshot before the window, one after, and the difference
of a series between them."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="((?:[^"\\]|\\.)*)"')


def snapshot() -> dict:
    """``{series: {((label, value), ...): number}}`` of every metric the
    process has registered; a histogram gives ``<name>_sum``, ``<name>_count``
    and ``<name>_bucket``."""
    from tikv_tpu.util.metrics import REGISTRY

    out: dict = {}
    for line in REGISTRY.render().splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if m is None:
            continue
        name, labels, value = m.groups()
        key = tuple(sorted(_LABEL.findall(labels or "")))
        try:
            out.setdefault(name, {})[key] = float(value)
        except ValueError:
            continue
    return out


def total(snap: dict, series: str, **labels) -> float:
    """Sum of ``series`` over every label set that carries ``labels``."""
    want = set(labels.items())
    return sum(v for key, v in snap.get(series, {}).items() if want <= set(key))


def moved(before: dict, after: dict, series: str, **labels) -> float:
    return total(after, series, **labels) - total(before, series, **labels)


class CompileCount:
    """Every program the process asked its backend for, and how many of them
    came out of the persistent cache: the ledger behind ``timed_jit`` sees
    only its own.  JAX times the whole of compile-or-fetch under its
    ``backend_compile_duration`` event, so that event counts both."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.programs = 0
        self.fetched = 0
        self.seconds = 0.0

    def listen(self) -> None:
        import jax.monitoring

        def on_duration(event: str, seconds: float, **_kw) -> None:
            if event == self.EVENTS[0]:
                self.programs += 1
                self.seconds += seconds
            elif event == self.EVENTS[1]:
                self.fetched += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
