"""Native engines, built from source on first use (``g++``, plain C ABI)."""

from __future__ import annotations

import os
import subprocess


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
