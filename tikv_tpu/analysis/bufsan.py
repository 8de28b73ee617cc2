"""Buffer-exposure sanitizer — alias/lifetime checking for the zero-copy plane.

The chunk wire path ships column slabs as zero-copy memoryview parts
(``wire.dumps_parts`` → ``server.write_frame_parts`` → ``sendmsg``) while
write-through delta folds make new versions of cached columns
concurrently.  That is a *buffer lifetime* property no lock can express: a
buffer handed to the kernel (or pinned on a device, or held for a shadow
compare) must stay bit-stable until the hand-off completes.  This module is
the third pillar of ``tikv_tpu/analysis`` next to the lint and the
lock-order sanitizer: a bounded ledger of every buffer crossing an exposure
boundary, verified at release and at every mutation choke point.

Mechanics (docs/static_analysis.md has the design note):

* :func:`export` registers ``(id(buffer), blake2b(sample), site, stack)``
  when a buffer crosses an exposure boundary — ``wire.dumps_parts``
  passthrough parts, ``SelectResponse.encode_parts`` slabs,
  ``ColumnBlockCache.device_arrays`` pins, shadow-read snapshots.
* :func:`release` pops the entry at the matching release boundary (send
  completion in ``write_frame_parts``, pin drop, shadow-compare finish) and
  re-hashes the sample: a mismatch means the buffer mutated while exposed.
* :func:`note_mutation` is called from a mutation choke point with the
  arrays about to be written in place; any byte overlap with a live
  exposed buffer is reported immediately — BEFORE the torn bytes can reach
  a client.

Reports carry BOTH stacks (export + mutation/release), ride the lock
sanitizer's report channel under kind ``buffer-mutation-while-exposed``,
and the same ``TIKV_TPU_SANITIZE=1`` / ``sanitizer.force()`` switches
enable everything.  Disabled, every entry point returns after one cheap
check — the hot paths pay nothing beyond the call.

False-positive policy: chunk column slabs are immutable ``bytes`` copies
(``chunk_codec.encode_np_column`` joins), so legitimate serving never
trips the verify; device pins are excluded from :func:`note_mutation`
overlap checks (a pin is re-registered wherever its arrays are replaced:
``ColumnBlockCache.successor``).  A region image's fold writes into no
array a reader may hold — it makes a new version — so a pin whose sample
fails at drop means a host write behind the cache's back.
"""

from __future__ import annotations

import sys
import threading
from hashlib import blake2b

import numpy as np

from . import sanitizer as _san

REPORT_KIND = "buffer-mutation-while-exposed"

#: ledger bound: beyond this, the oldest entry is verified and evicted.
#: Entries hold a strong ref to their buffer (id() reuse after GC would
#: otherwise alias a dead entry onto a fresh buffer), so the bound also
#: caps how much memory sanitize mode can pin.
_MAX_LEDGER = 4096
_SAMPLE_BYTES = 64  # per probe point: head + middle + tail
_STACK_LIMIT = 20

_mu = threading.Lock()
_entries: list["_Entry"] = []  # FIFO for the bound
_by_key: dict[int, list["_Entry"]] = {}
_seen: set = set()  # report dedup, mirrors sanitizer._seen

_counter = None  # lazy: tikv_bufsan_total{event=export|release|violation}


def enabled() -> bool:
    """Shared switch with the lock-order sanitizer: ``TIKV_TPU_SANITIZE=1``
    or an enclosing ``sanitizer.force()``."""
    return _san.enabled()


def _count(event: str) -> None:
    global _counter
    if _counter is None:
        from ..util.metrics import REGISTRY

        _counter = REGISTRY.counter(
            "tikv_bufsan_total",
            "Buffer-exposure sanitizer events (export/release/violation)")
    _counter.inc(event=event)


def _stack(skip: int = 2) -> tuple[str, ...]:
    """Fast frame walk (no linecache I/O); frames inside this module are
    dropped so the exposure/mutation site tops the report."""
    try:
        f = sys._getframe(skip)
    except ValueError:
        return ()
    while f is not None and f.f_code.co_filename == __file__:
        f = f.f_back
    out = []
    while f is not None and len(out) < _STACK_LIMIT:
        co = f.f_code
        out.append(f"{co.co_filename}:{f.f_lineno} in {co.co_name}")
        f = f.f_back
    return tuple(out)


# ---------------------------------------------------------------------------
# buffer trees -> byte views
# ---------------------------------------------------------------------------

def _leaves(tree) -> list:
    """Flatten an exposure payload: nested lists/tuples/dicts and pin
    entries carrying their device arrays under a ``dev`` attribute (zone
    layouts) down to buffer-like leaves."""
    out: list = []
    stack = [tree]
    while stack:
        x = stack.pop()
        if x is None:
            continue
        if isinstance(x, (list, tuple)):
            stack.extend(x)
        elif isinstance(x, dict):
            stack.extend(x.values())
        elif not isinstance(x, (bytes, bytearray, memoryview, np.ndarray)) \
                and hasattr(x, "dev"):
            stack.append(x.dev)
        else:
            out.append(x)
    return out


def _as_u8(leaf) -> np.ndarray | None:
    """A flat uint8 view of the leaf's bytes.  numpy arrays view in place;
    bytes-likes wrap via the buffer protocol; device arrays pull to host
    (``np.asarray``) — a copy whose *hash* is still the truth, which is the
    sampling cost sanitize mode accepts.  ``None`` = nothing hashable."""
    try:
        if isinstance(leaf, (bytes, bytearray, memoryview)):
            a = np.frombuffer(leaf, dtype=np.uint8)
            return a if a.size else None
        a = np.asarray(leaf)
        if a.dtype == object or a.nbytes == 0:
            return None
        if not a.flags.c_contiguous:
            a = np.ascontiguousarray(a)
        return a.reshape(-1).view(np.uint8)
    except Exception:  # noqa: BLE001 — unhashable leaf: skip, don't break serving
        return None


def _span(u8: np.ndarray) -> tuple[int, int] | None:
    try:
        ptr = u8.__array_interface__["data"][0]
        return (ptr, ptr + u8.size)
    except Exception:  # noqa: BLE001
        return None


def _sample(u8s: list[np.ndarray]) -> bytes:
    """blake2b over head/middle/tail probes of each leaf plus its length —
    O(_SAMPLE_BYTES) per leaf regardless of slab size, so exporting a
    64 MiB column costs the same as a 4 KiB one."""
    h = blake2b(digest_size=16)
    for u8 in u8s:
        n = u8.size
        h.update(n.to_bytes(8, "little"))
        if n <= 3 * _SAMPLE_BYTES:
            h.update(u8.tobytes())
        else:
            h.update(u8[:_SAMPLE_BYTES].tobytes())
            mid = n // 2
            h.update(u8[mid:mid + _SAMPLE_BYTES].tobytes())
            h.update(u8[-_SAMPLE_BYTES:].tobytes())
    return h.digest()


def _key_of(buf) -> int:
    """Ledger key: the identity of the buffer's BASE object, so the bytes a
    slab was encoded into matches both its ``encode_parts`` registration
    and the memoryview ``dumps_parts`` wrapped around it."""
    if isinstance(buf, memoryview) and buf.obj is not None:
        return id(buf.obj)
    return id(buf)


class _Entry:
    __slots__ = ("key", "kind", "site", "leaves", "sample", "spans",
                 "stack", "thread", "buf", "violated")

    def __init__(self, key, kind, site, leaves, sample, spans, stack, buf):
        self.key = key
        self.kind = kind
        self.site = site
        self.leaves = leaves  # strong refs: re-hashed at verify time
        self.sample = sample
        self.spans = spans
        self.stack = stack
        self.thread = threading.current_thread().name
        self.buf = buf
        self.violated = False


def _violation(entry: _Entry, phase: str, site: str,
               stack: tuple[str, ...]) -> None:
    entry.violated = True
    dedup = (phase, entry.kind, entry.site, site,
             entry.stack[0] if entry.stack else "?",
             stack[0] if stack else "?")
    with _mu:
        if dedup in _seen:
            return
        _seen.add(dedup)
    _count("violation")
    _san._emit(_san.Report(
        REPORT_KIND,
        f"{entry.kind} buffer exported at {entry.site} "
        f"{'mutated while exposed' if phase == 'mutation' else 'changed between export and release'}"
        f" ({phase} at {site})",
        [(f"exposed at {entry.site} ({entry.kind}) by {entry.thread}", entry.stack),
         (f"{phase} at {site} by", stack)],
    ))


def _verify(entry: _Entry, phase: str, site: str) -> None:
    if entry.violated:
        return
    u8s = [u for u in (_as_u8(lf) for lf in entry.leaves) if u is not None]
    if _sample(u8s) != entry.sample:
        _violation(entry, phase, site, _stack(3))


# ---------------------------------------------------------------------------
# the boundary API
# ---------------------------------------------------------------------------

def export(kind: str, buf, site: str = "") -> None:
    """Register ``buf`` as exposed at ``site``.  Kinds in use: ``wire_part``
    (dumps_parts passthrough), ``encode_parts`` (response column slabs),
    ``device_pin`` (ColumnBlockCache pins), ``shadow_read`` (integrity
    snapshot compares).  No-op when the sanitizer is off."""
    if not _san.enabled():
        return
    leaves = _leaves(buf)
    u8s, spans = [], []
    for lf in leaves:
        u8 = _as_u8(lf)
        if u8 is None:
            continue
        u8s.append(u8)
        sp = _span(u8)
        if sp is not None:
            spans.append(sp)
    entry = _Entry(_key_of(buf), kind, site, leaves, _sample(u8s), spans,
                   _stack(2), buf)
    evicted = []
    with _mu:
        _entries.append(entry)
        _by_key.setdefault(entry.key, []).append(entry)
        while len(_entries) > _MAX_LEDGER:
            old = _entries.pop(0)
            peers = _by_key.get(old.key)
            if peers is not None:
                try:
                    peers.remove(old)
                except ValueError:
                    pass
                if not peers:
                    _by_key.pop(old.key, None)
            evicted.append(old)
    _count("export")
    for old in evicted:
        # evict-with-verify: a leaked exposure (a part list that never
        # reached the frame writer) still gets its mutation check here
        _verify(old, "release", "bufsan.evict")
        _count("release")


def release(buf, site: str = "") -> int:
    """Verify and drop every ledger entry for ``buf``; returns how many
    were released.  Quiet for unregistered buffers (frame headers, small
    parts)."""
    if not _san.enabled():
        return 0
    key = _key_of(buf)
    with _mu:
        popped = _by_key.pop(key, None)
        if not popped:
            return 0
        for e in popped:
            try:
                _entries.remove(e)
            except ValueError:
                pass
    for e in popped:
        _verify(e, "release", site)
        _count("release")
    return len(popped)


def release_parts(parts, site: str = "") -> None:
    """Release every buffer of a frame's part list at send completion."""
    if not _san.enabled():
        return
    for p in parts:
        release(p, site)


def note_mutation(bufs, site: str = "") -> None:
    """Mutation choke point: ``bufs`` are about to take in-place writes.
    Any byte overlap with a live exposed buffer (device pins excepted —
    they are re-registered wherever their arrays are replaced) is a
    violation, reported with the export stack AND this mutation stack."""
    if not _san.enabled():
        return
    with _mu:
        candidates = [e for e in _entries
                      if e.kind != "device_pin" and not e.violated and e.spans]
    if not candidates:
        return
    spans = []
    for b in bufs:
        u8 = _as_u8(b)
        if u8 is None:
            continue
        sp = _span(u8)
        if sp is not None:
            spans.append(sp)
    if not spans:
        return
    stack = None
    for e in candidates:
        if any(lo < ehi and elo < hi
               for (lo, hi) in spans for (elo, ehi) in e.spans):
            if stack is None:
                stack = _stack(2)
            _violation(e, "mutation", site, stack)


def verify_all(site: str = "") -> None:
    """Re-hash every live entry without releasing (structural repack
    boundary + test/gate hook)."""
    if not _san.enabled():
        return
    with _mu:
        snap = list(_entries)
    for e in snap:
        _verify(e, "release", site)


# ---------------------------------------------------------------------------
# introspection + test plumbing
# ---------------------------------------------------------------------------

def reports() -> list:
    """This sanitizer's findings (they ride the lock sanitizer's channel)."""
    return _san.reports(REPORT_KIND)


def ledger_size() -> int:
    with _mu:
        return len(_entries)


def exposed_kinds() -> dict[str, int]:
    with _mu:
        out: dict[str, int] = {}
        for e in _entries:
            out[e.kind] = out.get(e.kind, 0) + 1
        return out


def clear() -> None:
    """Drop the ledger and report dedup (test isolation; reports themselves
    clear via ``sanitizer.clear_reports``)."""
    with _mu:
        _entries.clear()
        _by_key.clear()
        _seen.clear()


def snapshot_state():
    """Pair with :func:`restore_state` — same contract as the lock
    sanitizer's, so seeded strike tests don't erase what a session-wide
    gate is accumulating."""
    with _mu:
        return (list(_entries), {k: list(v) for k, v in _by_key.items()},
                set(_seen))


def restore_state(state) -> None:
    entries, by_key, seen = state
    with _mu:
        _entries[:] = entries
        _by_key.clear()
        _by_key.update({k: list(v) for k, v in by_key.items()})
        _seen.clear()
        _seen.update(seen)
