"""From the profiler's trace to device numbers.

``load_events`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain lists; ``reduce`` turns those into busy time, time per XLA module, the
operations that took most time and the longest idle gaps.  The reduction works
on the plain lists, so a recorded trace kept as JSON checks it
(``benchmark/tests/``).

Programs are told apart by their XLA module names: the program has no
``named_scope`` yet.
"""

from __future__ import annotations

import glob
import os

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
TOP = 10
NAME_CHARS = 64     # an op's name is its whole HLO line


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    return found[-1]


def load_events(path: str) -> dict:
    """``{plane: {line: [[name, start_ns, duration_ns], ...]}}``; host lines
    keep only events of a millisecond or more, which is what an idle gap can
    be blamed on."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                dur = int(ev.duration_ns)
                if device or dur >= 1_000_000:
                    evs.append([ev.name[:NAME_CHARS], int(ev.start_ns), dur])
    return out


def device_planes(events: dict) -> list[str]:
    return sorted(p for p in events
                  if p.startswith("/device:") and
                  (OP_LINE in events[p] or MODULE_LINE in events[p]))


def _union(intervals) -> list[list[int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _short(name: str) -> str:
    """``jit_agg_step(123456)`` -> ``jit_agg_step``; ``%fusion.3 = ...`` ->
    ``fusion.3``."""
    name = name.split(" = ")[0].lstrip("%")
    return name.split("(")[0] if name.endswith(")") else name


def reduce(events: dict, window_s: float) -> dict | None:
    """None where no operation ran on a device."""
    planes = device_planes(events)
    per_plane_busy = []
    by_module: dict[str, float] = {}
    module_runs = 0
    by_op: dict[str, float] = {}
    gaps: list[tuple[int, int]] = []
    for p in planes:
        lines = events[p]
        ops = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        merged = _union((s, s + d) for _n, s, d in ops)
        per_plane_busy.append(sum(e - s for s, e in merged) / 1e9)
        if p == planes[0]:
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        mods = sorted(lines.get(MODULE_LINE) or [], key=lambda e: e[1])
        for name, _s, d in mods:
            by_module[_short(name)] = by_module.get(_short(name), 0.0) + d / 1e9
            module_runs += 1
        # an op belongs to the module that was running when it started
        mi = 0
        for name, s, d in sorted(lines.get(OP_LINE) or [], key=lambda e: e[1]):
            while mi + 1 < len(mods) and mods[mi + 1][1] <= s:
                mi += 1
            owner = (_short(mods[mi][0]) if mods and mods[mi][1] <= s
                     < mods[mi][1] + mods[mi][2] else "?")
            key = f"{owner}/{_short(name)}"
            by_op[key] = by_op.get(key, 0.0) + d / 1e9
    if not per_plane_busy or not any(per_plane_busy):
        return None
    return {
        "busy_s": sum(per_plane_busy) / len(per_plane_busy),
        "window_s": window_s,
        "devices": len(planes),
        "module_runs": module_runs,
        "by_module": by_module,
        "device_ops": _top(by_op),
        "idle_gaps": _blame(events, gaps),
    }


def _top(d: dict) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]


def _blame(events: dict, gaps) -> list:
    """The longest idle gaps of the first device, each named after the host
    event that covers most of it."""
    host = [(n, s, s + d) for p, lines in events.items()
            if not p.startswith("/device:")
            for evs in lines.values() for n, s, d in evs]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]:
        best, cover = "no host span", 0
        for n, hs, he in host:
            c = min(e, he) - max(s, hs)
            if c > cover:
                best, cover = n, c
        out.append([best, (e - s) / 1e9])
    return out
