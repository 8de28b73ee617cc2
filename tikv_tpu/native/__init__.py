"""Native engines, built from source on first use (``g++``, plain C ABI)."""

from __future__ import annotations

import os
import subprocess

# guard.h: the one code every guarded entry point returns once its engine is
# closed; entry points that return an unsigned 64-bit number say it as the
# type's maximum
CLOSED = -9
U64_CLOSED = 2**64 - 1


class EngineClosed(RuntimeError):
    """A call through a native engine, or through a snapshot or cursor taken
    from it, after ``close()``.  The native side turned it away (guard.h):
    nothing was read and nothing was written."""


def refused(engine: str, what: str) -> EngineClosed:
    """Count one call refused after close, by engine (``kv`` | ``raftlog``);
    the exception for the caller to raise.  A store that stops cleanly joins
    its threads before it closes its engines, so this reads 0."""
    from ..util.metrics import REGISTRY

    REGISTRY.counter(
        "tikv_engine_closed_call_total",
        "Native calls refused because the engine was closed, by engine",
    ).inc(engine=engine)
    return EngineClosed(f"{what}: the {engine} engine is closed")


def ensure_built(so: str, *srcs: str) -> None:
    """Compile ``srcs[0]`` into ``so`` when the shared object is missing or
    predates ANY of its sources (the .cc plus shared headers).

    Several processes may get here at once (test workers, the stores of a
    local cluster), and a thread lock does not reach across them: each
    compiles to a name of its own beside the target and renames it into
    place, so no process ever loads a half-written file."""
    if os.path.exists(so):
        newest = max(
            (os.path.getmtime(p) for p in srcs if os.path.exists(p)), default=0
        )
        if os.path.getmtime(so) >= newest:
            return
    head, tail = os.path.split(so)
    tmp = os.path.join(head, f".{os.getpid()}.{tail}")
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, srcs[0]],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, so)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
