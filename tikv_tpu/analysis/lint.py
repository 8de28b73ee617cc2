"""AST-based project linter for the concurrent + device hot paths.

Rules (docs/static_analysis.md has the full catalog and waiver syntax):

``lock-blocking-call``
    A blocking operation — ``engine.write`` / ``engine.snapshot`` round
    trips, device sync (``block_until_ready``), ``time.sleep``, socket I/O,
    event waits on foreign objects, ``scan_delta`` — executed while a
    cache/scheduler/latch lock is held (directly, or transitively through
    same-class/same-module calls).
``jit-nocache``
    ``jax.jit(...)`` called inside a function body with no visible caching
    idiom: every call re-traces and re-compiles — the dominant hidden cost
    on tensor runtimes ("Query Processing on Tensor Computation Runtimes").
``jit-static-args``
    ``static_argnums``/``static_argnames`` passed a non-literal value —
    value-varying or unhashable statics silently recompile per call.
``jit-host-sync``
    ``.item()`` / ``float(param)`` / ``int(param)`` / ``bool(param)``
    inside a jitted function: a trace-time host sync or value-dependent
    branch point.
``jit-shape-branch``
    ``if``/``while`` on a parameter's ``.shape``/``len()`` inside a jitted
    function: the branch specializes at trace time — each new shape
    recompiles silently.
``metric-drift-dashboard``
    A metric referenced by the Grafana dashboards / alert rules that no
    ``REGISTRY.counter/gauge/histogram`` call defines.
``metric-drift-code``
    A REGISTRY-defined metric never referenced by any dashboard or alert
    rule (dead telemetry — either chart it or waive it).
``failpoint-drift-test``
    A test configures (``cfg``) a failpoint name that no ``fail_point``
    site defines (neither in source nor locally in the test file).
``failpoint-drift-source``
    A ``fail_point`` site never exercised by any test.
``raw-lock-direct``
    A sanitizer-wired module creating ``threading.Lock/RLock/Condition``
    directly instead of through ``analysis.sanitizer.make_*`` — the lock
    would silently escape order tracking.
``buffer-inplace-export``
    An in-place numpy mutation (``x[...] = v``, ``+=``, ``np.copyto``,
    ``.sort()``/``.fill()``) on a name that flows into
    ``wire.dumps_parts`` / ``bufsan.export`` in the same function (directly
    or through a same-module call) — the zero-copy wire path holds that
    buffer until the send completes, so a later in-place write corrupts
    frames already handed to the kernel.
``buffer-export-unregistered``
    An exposure-boundary function (``dumps_parts``, ``write_frame_parts``,
    ``encode_parts``, the device-pin cache entry points) that doesn't route
    through ``analysis.bufsan`` export/release — the buffer would cross the
    zero-copy boundary invisible to the runtime sanitizer.
``view-escape``
    A public method returning a ``memoryview`` or slice of a cache-resident
    buffer attribute without ``.copy()``/``.tobytes()``/``.toreadonly()``
    or bufsan export registration: the caller holds an aliasing view into
    state a later fold mutates in place.

Waivers: ``# lint: allow(rule-name[, rule2]) -- reason`` on the flagged
line or the line directly above it.  Every waiver should carry a reason.

Limits (by design): the blocking-call analysis links ``self.method()`` and
bare same-module calls only — cross-object calls are invisible unless they
match a blocking pattern themselves; the runtime sanitizer
(``analysis/sanitizer.py``) covers that half dynamically.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path

# --------------------------------------------------------------------------
# config
# --------------------------------------------------------------------------

# attribute names that smell like a mutex when assigned threading primitives
_LOCK_NAME_RE = re.compile(
    r"(^|_)(mu|mutex|lock|lk|cv|cond|conds|cvs|latch|latches)\d*$"
)
_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "make_lock", "make_rlock",
                   "make_condition"}

# attr-chain suffixes that are blocking by themselves
_BLOCKING_CHAIN_SUFFIXES = (
    ("engine", "write"),
    ("engine", "snapshot"),
)
_BLOCKING_ATTRS = {"block_until_ready"}
_SOCKET_ATTRS = {"accept", "connect", "recv", "recvfrom", "recv_into",
                 "sendall", "makefile", "create_connection"}
# project-specific expensive scans treated as blocking
_BLOCKING_NAMES = {"scan_delta"}

# modules that MUST create locks through analysis.sanitizer (tentpole wiring)
_SANITIZER_WIRED = {
    "tikv_tpu/storage/txn/latches.py",
    "tikv_tpu/storage/txn/scheduler.py",
    "tikv_tpu/storage/concurrency_manager.py",
    "tikv_tpu/copr/breaker.py",
    "tikv_tpu/copr/cache.py",
    "tikv_tpu/copr/dag.py",
    "tikv_tpu/copr/endpoint.py",
    "tikv_tpu/copr/jax_join.py",
    "tikv_tpu/copr/costmodel.py",
    "tikv_tpu/copr/encoding.py",
    "tikv_tpu/copr/integrity.py",
    "tikv_tpu/copr/observatory.py",
    "tikv_tpu/copr/overload.py",
    "tikv_tpu/copr/region_cache.py",
    "tikv_tpu/copr/scheduler.py",
    "tikv_tpu/raft/store.py",
    "tikv_tpu/raft/batch_system.py",
    "tikv_tpu/raft/fsm_system.py",
    "tikv_tpu/sidecar/resolved_ts.py",
    "tikv_tpu/server/read_plane.py",
    "tikv_tpu/server/wire.py",
    "tikv_tpu/util/chaos.py",
    "tikv_tpu/util/retry.py",
    "tikv_tpu/util/trace.py",
    "tikv_tpu/util/worker.py",
}

# files whose functions count as "device code" for the jit rules
_DEVICE_FILES = ("copr/jax_eval.py", "copr/jax_zone.py", "parallel/mesh.py")

# exposure-boundary functions that MUST route through analysis.bufsan
# (export at the boundary, release at the completion point) — the runtime
# half of the zero-copy contract (docs/static_analysis.md §bufsan)
_BUFSAN_BOUNDARY = {
    "tikv_tpu/server/wire.py": ("dumps_parts",),
    "tikv_tpu/server/server.py": ("write_frame_parts",),
    "tikv_tpu/copr/dag.py": ("encode_parts",),
    "tikv_tpu/copr/cache.py": ("device_arrays", "drop_device", "successor"),
}
_BUFSAN_MODULES = ("bufsan", "_bufsan")
_BUFSAN_CALLS = {"export", "release", "release_parts", "note_mutation",
                 "verify_all"}
# in-place ndarray methods for the buffer-inplace-export rule (``.clear()``
# etc. would drown the rule in dict/list noise)
_INPLACE_METHODS = {"sort", "fill", "partition", "byteswap"}
# attribute names that smell like a shared buffer for the view-escape rule
_BUF_NAME_RE = re.compile(
    r"(^|_)(data|buf|buffer|raw|bytes|payload|arr|array|nulls|packed|slab|"
    r"frame|view|blob|chunk)s?\d*$"
)

_METRIC_REF_RE = re.compile(r"\btikv_[a-z0-9_]+")
_HISTO_SUFFIXES = ("_bucket", "_sum", "_count")

RULES = {
    "lock-blocking-call": "blocking call while holding a lock",
    "jit-nocache": "uncached jax.jit in a function body (recompiles per call)",
    "jit-static-args": "non-literal static_argnums/static_argnames",
    "jit-host-sync": "host sync / value branch inside a jitted function",
    "jit-shape-branch": "shape-dependent branch inside a jitted function",
    "metric-drift-dashboard": "dashboard references an undefined metric",
    "metric-drift-code": "metric defined in code but on no dashboard",
    "failpoint-drift-test": "test configures an unknown failpoint",
    "failpoint-drift-source": "failpoint site never exercised by tests",
    "raw-lock-direct": "wired module bypasses analysis.sanitizer lock factories",
    "buffer-inplace-export": "in-place mutation of a buffer that flows to the "
                             "zero-copy wire boundary",
    "buffer-export-unregistered": "exposure-boundary function bypasses "
                                  "analysis.bufsan export/release",
    "view-escape": "public method returns an aliasing view of a "
                   "cache-resident buffer",
}


@dataclass
class Finding:
    path: str
    line: int
    rule: str
    message: str
    waived: bool = False

    def format(self) -> str:
        w = " (waived)" if self.waived else ""
        return f"{self.path}:{self.line}: [{self.rule}]{w} {self.message}"


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _attr_chain(node: ast.AST) -> list[str]:
    """``self.store.engine.write`` -> ["self","store","engine","write"]."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    elif isinstance(node, ast.Subscript):
        inner = _attr_chain(node.value)
        parts.append(inner[0] if inner else "?")
    else:
        parts.append("?")
    return list(reversed(parts))


def _expr_key(node: ast.AST) -> str:
    """Stable text for a with-target / call-base comparison."""
    return ".".join(_attr_chain(node))


def _is_lock_expr(node: ast.AST, known_locks: set[str]) -> bool:
    """Does this with-target look like a mutex?  Known (assigned from a lock
    factory in this file) or name-pattern matched; subscripts of lock-named
    containers (``self._cvs[i]``) count."""
    if isinstance(node, ast.Subscript):
        return _is_lock_expr(node.value, known_locks)
    if isinstance(node, ast.Call):  # with foo.acquire_timeout(...): etc
        return False
    chain = _attr_chain(node)
    if not chain:
        return False
    last = chain[-1]
    key = ".".join(chain)
    return key in known_locks or last in known_locks or bool(_LOCK_NAME_RE.search(last))


def _waivers_for(src_lines: list[str]) -> dict[int, set[str]]:
    """line -> waived rule names.  A waiver covers its own line (inline
    form) and the next CODE line — intervening comment-only lines (the
    reason text) don't break the reach."""
    out: dict[int, set[str]] = {}
    rx = re.compile(r"#\s*lint:\s*allow\(([^)]*)\)")
    for i, line in enumerate(src_lines, start=1):
        m = rx.search(line)
        if not m:
            continue
        rules = {r.strip() for r in m.group(1).split(",") if r.strip()}
        out.setdefault(i, set()).update(rules)
        if not line.strip().startswith("#"):
            continue  # inline form covers ONLY its own line
        j = i + 1
        while j <= len(src_lines) and src_lines[j - 1].strip().startswith("#"):
            j += 1
        if j <= len(src_lines):
            out.setdefault(j, set()).update(rules)
    return out


def _apply_waivers(findings: list[Finding], waivers: dict[int, set[str]]) -> None:
    for f in findings:
        rules = waivers.get(f.line)
        if rules and (f.rule in rules or "*" in rules):
            f.waived = True


# --------------------------------------------------------------------------
# per-file analysis
# --------------------------------------------------------------------------

@dataclass
class _FuncInfo:
    qualname: str
    node: ast.AST
    cls: str | None
    # (lineno, description) of direct blocking calls in this function
    direct: list[tuple[int, str]] = field(default_factory=list)
    # local callees: ("self", name) for self.method, ("bare", name) for f()
    calls: set[tuple[str, str]] = field(default_factory=set)
    blocking: tuple[str, ...] | None = None  # chain of the reached blocker


class _FileLint(ast.NodeVisitor):
    """Single-module pass: lock inventory, function table, jit sites."""

    def __init__(self, path: str, tree: ast.Module, relpath: str):
        self.path = path
        self.relpath = relpath
        self.tree = tree
        self.findings: list[Finding] = []
        self.known_locks: set[str] = set()
        self.funcs: dict[str, _FuncInfo] = {}
        self._cls_stack: list[str] = []
        self._fn_stack: list[_FuncInfo] = []
        # fail_point()/REGISTRY sites for the project passes
        self.failpoint_sites: list[tuple[str, int]] = []
        self.failpoint_cfgs: list[tuple[str, int]] = []
        self.metric_defs: list[tuple[str, int]] = []

    # -- inventory ----------------------------------------------------------

    def _note_lock_assign(self, target: ast.AST, value: ast.AST) -> None:
        if not isinstance(value, ast.Call):
            return
        fn = value.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None)
        if name in _LOCK_FACTORIES:
            chain = _attr_chain(target)
            if chain:
                self.known_locks.add(chain[-1])
                self.known_locks.add(".".join(chain))

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._note_lock_assign(t, node.value)
            # list-of-locks: self._cvs = [make_condition(...) for ...]
            if isinstance(node.value, (ast.ListComp, ast.List)):
                elts = (node.value.elts if isinstance(node.value, ast.List)
                        else [node.value.elt])
                for e in elts:
                    self._note_lock_assign(t, e)
        self.generic_visit(node)

    # -- structure ----------------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._cls_stack.append(node.name)
        self.generic_visit(node)
        self._cls_stack.pop()

    def _visit_func(self, node) -> None:
        cls = self._cls_stack[-1] if self._cls_stack else None
        qual = f"{cls}.{node.name}" if cls else node.name
        info = _FuncInfo(qual, node, cls)
        # nested defs shadow outer entries only if names collide; last wins
        self.funcs[qual] = info
        self._fn_stack.append(info)
        self.generic_visit(node)
        self._fn_stack.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- calls --------------------------------------------------------------

    def _blocking_desc(self, call: ast.Call) -> str | None:
        fn = call.func
        chain = _attr_chain(fn)
        if not chain:
            return None
        last = chain[-1]
        key = ".".join(chain)
        if last in _BLOCKING_ATTRS:
            return f"{key}() [device sync]"
        for a, b in _BLOCKING_CHAIN_SUFFIXES:
            if len(chain) >= 2 and chain[-2] == a and last == b:
                return f"{key}() [engine round trip]"
        if key in ("time.sleep", "sleep"):
            return f"{key}() [sleep]"
        if last in _SOCKET_ATTRS and chain[0] != "?":
            # str.join-style false positives have no resolvable base
            return f"{key}() [socket I/O]"
        if last == "wait" and len(chain) >= 2:
            return f"{key}() [wait]"
        if last == "join" and any("thread" in p.lower() for p in chain[:-1]):
            return f"{key}() [thread join]"
        if last in _BLOCKING_NAMES and len(chain) == 1:
            return f"{key}() [mvcc scan]"
        return None

    def visit_Call(self, node: ast.Call) -> None:
        fn = node.func
        chain = _attr_chain(fn)
        if self._fn_stack:
            info = self._fn_stack[-1]
            desc = self._blocking_desc(node)
            if desc is not None:
                info.direct.append((node.lineno, desc))
            if chain:
                if len(chain) == 2 and chain[0] == "self":
                    info.calls.add(("self", chain[1]))
                elif len(chain) == 1:
                    info.calls.add(("bare", chain[0]))
        # project-pass inventory
        if chain:
            last = chain[-1]
            args = node.args
            if last == "fail_point" and args and isinstance(args[0], ast.Constant) \
                    and isinstance(args[0].value, str):
                self.failpoint_sites.append((args[0].value, node.lineno))
            if last == "cfg" and (len(chain) == 1 or "failpoint" in chain[-2].lower()
                                  or chain[-2] in ("fp", "fail")):
                if args and isinstance(args[0], ast.Constant) and isinstance(args[0].value, str):
                    self.failpoint_cfgs.append((args[0].value, node.lineno))
            if last in ("counter", "gauge", "histogram") and len(chain) >= 2 \
                    and "registry" in chain[-2].lower():
                if args and isinstance(args[0], ast.Constant) and isinstance(args[0].value, str):
                    self.metric_defs.append((args[0].value, node.lineno))
            # raw-lock-direct (wired modules only)
            if self.relpath in _SANITIZER_WIRED and len(chain) == 2 \
                    and chain[0] == "threading" and last in ("Lock", "RLock", "Condition"):
                self.findings.append(Finding(
                    self.path, node.lineno, "raw-lock-direct",
                    f"threading.{last}() in a sanitizer-wired module — use "
                    f"analysis.sanitizer.make_{last.lower().replace('rlock','rlock')} "
                    f"so the lock joins order tracking",
                ))
        self.generic_visit(node)

    # -- jit rules ----------------------------------------------------------

    def check_jit(self) -> None:
        if not self.relpath.startswith("tikv_tpu/"):
            return
        for info in self.funcs.values():
            body_src_has_cache = self._cacheish(info.node)
            jitted_local_fns: list[str] = []
            for call in ast.walk(info.node):
                if not isinstance(call, ast.Call):
                    continue
                chain = _attr_chain(call.func)
                if chain[-1:] != ["jit"] or (len(chain) > 1 and chain[-2] != "jax"):
                    continue
                if call.args and isinstance(call.args[0], ast.Name):
                    jitted_local_fns.append(call.args[0].id)
                for kw in call.keywords:
                    if kw.arg in ("static_argnums", "static_argnames") \
                            and not self._literal(kw.value):
                        self.findings.append(Finding(
                            self.path, call.lineno, "jit-static-args",
                            f"{kw.arg} is not a literal — a value-varying or "
                            f"unhashable static recompiles (or fails) per call",
                        ))
                if not body_src_has_cache:
                    self.findings.append(Finding(
                        self.path, call.lineno, "jit-nocache",
                        f"jax.jit inside {info.qualname}() with no caching "
                        f"idiom in sight — every invocation re-traces and "
                        f"re-compiles",
                    ))
            # rules inside the jitted local functions
            for fname in jitted_local_fns:
                target = None
                for n in ast.walk(info.node):
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                            and n.name == fname:
                        target = n
                        break
                if target is None:
                    continue
                params = {a.arg for a in target.args.args}
                self._check_jitted_body(target, params)

    def _check_jitted_body(self, fn, params: set[str]) -> None:
        for n in ast.walk(fn):
            if isinstance(n, (ast.If, ast.While)):
                for sub in ast.walk(n.test):
                    if isinstance(sub, ast.Attribute) and sub.attr == "shape":
                        base = _attr_chain(sub.value)
                        if base and base[0] in params:
                            self.findings.append(Finding(
                                self.path, n.lineno, "jit-shape-branch",
                                f"branch on {'.'.join(base)}.shape inside "
                                f"jitted {fn.name}() — specializes at trace "
                                f"time, every new shape recompiles silently",
                            ))
                    if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name) \
                            and sub.func.id == "len" and sub.args \
                            and isinstance(sub.args[0], ast.Name) \
                            and sub.args[0].id in params:
                        self.findings.append(Finding(
                            self.path, n.lineno, "jit-shape-branch",
                            f"branch on len({sub.args[0].id}) inside jitted "
                            f"{fn.name}()",
                        ))
            if isinstance(n, ast.Call):
                chain = _attr_chain(n.func)
                if chain[-1:] == ["item"] and len(chain) >= 2:
                    self.findings.append(Finding(
                        self.path, n.lineno, "jit-host-sync",
                        f"{'.'.join(chain)}() inside jitted {fn.name}() — "
                        f"forces a host sync / concretization at trace time",
                    ))
                if isinstance(n.func, ast.Name) and n.func.id in ("float", "int", "bool") \
                        and n.args and isinstance(n.args[0], ast.Name) \
                        and n.args[0].id in params:
                    self.findings.append(Finding(
                        self.path, n.lineno, "jit-host-sync",
                        f"{n.func.id}({n.args[0].id}) on a traced parameter "
                        f"inside jitted {fn.name}()",
                    ))

    @staticmethod
    def _literal(node: ast.AST) -> bool:
        try:
            ast.literal_eval(node)
            return True
        except (ValueError, SyntaxError):
            return False

    def _cacheish(self, fn) -> bool:
        try:
            src = ast.unparse(fn)
        except Exception:  # noqa: BLE001
            return True  # can't inspect: benefit of the doubt
        low = src.lower()
        return any(tok in low for tok in ("cache", "memo", "_fns", "lru"))

    # -- bufsan rules -------------------------------------------------------

    @staticmethod
    def _bufsan_call_name(call: ast.Call) -> str | None:
        """``bufsan.export`` / ``_bufsan.release_parts`` etc., else None."""
        chain = _attr_chain(call.func)
        if (len(chain) >= 2 and chain[-2] in _BUFSAN_MODULES
                and chain[-1] in _BUFSAN_CALLS):
            return chain[-1]
        return None

    def _bufsan_reach(self) -> set[str]:
        """Qualnames that touch analysis.bufsan, directly or through a
        same-class/same-module call (same fixpoint shape as blocking)."""
        reach = {
            q for q, info in self.funcs.items()
            if any(isinstance(n, ast.Call) and self._bufsan_call_name(n)
                   for n in ast.walk(info.node))
        }
        changed = True
        while changed:
            changed = False
            for q, info in self.funcs.items():
                if q in reach:
                    continue
                for kind, name in info.calls:
                    callee = self._resolve(info, kind, name)
                    if callee is not None and callee.qualname in reach:
                        reach.add(q)
                        changed = True
                        break
        return reach

    @staticmethod
    def _sink_args(call: ast.Call) -> list[ast.AST]:
        """Buffer-valued arguments of a direct export sink: the payload of
        ``dumps_parts(obj)`` or ``bufsan.export(kind, buf, ...)``."""
        chain = _attr_chain(call.func)
        if chain[-1:] == ["dumps_parts"] and call.args:
            return [call.args[0]]
        if (len(chain) >= 2 and chain[-2] in _BUFSAN_MODULES
                and chain[-1] == "export" and len(call.args) >= 2):
            return [call.args[1]]
        return []

    @staticmethod
    def _buf_key(node: ast.AST) -> str | None:
        """Taint key for a buffer expression: a dotted name chain, or the
        chain inside a trivial wrapper (``memoryview(x)``)."""
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "memoryview" and node.args):
            node = node.args[0]
        if not isinstance(node, (ast.Name, ast.Attribute)):
            return None
        chain = _attr_chain(node)
        if not chain or chain == ["self"] or chain[0] == "?":
            return None
        return ".".join(chain)

    def check_bufsan(self) -> None:
        if not self.relpath.startswith("tikv_tpu/"):
            return
        reach = self._bufsan_reach()
        self._check_export_unregistered(reach)
        self._check_inplace_export()
        self._check_view_escape(reach)

    def _check_export_unregistered(self, reach: set[str]) -> None:
        for fname in _BUFSAN_BOUNDARY.get(self.relpath, ()):
            for q, info in self.funcs.items():
                if q != fname and not q.endswith(f".{fname}"):
                    continue
                if q in reach:
                    continue
                self.findings.append(Finding(
                    self.path, info.node.lineno, "buffer-export-unregistered",
                    f"{q}() is an exposure boundary but never routes through "
                    f"analysis.bufsan export/release — buffers cross the "
                    f"zero-copy plane invisible to the sanitizer",
                ))

    def _check_inplace_export(self) -> None:
        # param indices each local function exports (fixpoint over direct
        # sinks, so taint follows ``f(buf)`` into f's own dumps_parts call)
        exported_params: dict[str, set[int]] = {q: set() for q in self.funcs}
        changed = True
        while changed:
            changed = False
            for q, info in self.funcs.items():
                params = [a.arg for a in info.node.args.args]
                keys = self._exported_keys(info, exported_params)
                for i, p in enumerate(params):
                    if p in keys and i not in exported_params[q]:
                        exported_params[q].add(i)
                        changed = True
        for info in self.funcs.values():
            exported = self._exported_keys(info, exported_params)
            if not exported:
                continue
            self._scan_mutations(info, exported)

    def _exported_keys(self, info: _FuncInfo,
                       exported_params: dict[str, set[int]]) -> dict[str, int]:
        """key -> line of the earliest export of that name inside ``info``:
        direct sink args plus positional args handed to local callees at
        positions those callees export."""
        out: dict[str, int] = {}

        def note(node: ast.AST, line: int) -> None:
            key = self._buf_key(node)
            if key is not None and (key not in out or line < out[key]):
                out[key] = line

        for call in ast.walk(info.node):
            if not isinstance(call, ast.Call):
                continue
            for arg in self._sink_args(call):
                note(arg, call.lineno)
            chain = _attr_chain(call.func)
            callee, bound = None, 0
            if len(chain) == 2 and chain[0] == "self":
                callee = self._resolve(info, "self", chain[1])
                bound = 1  # callee's args.args leads with self
            elif len(chain) == 1:
                callee = self._resolve(info, "bare", chain[0])
            if callee is not None:
                for i in exported_params.get(callee.qualname, ()):
                    j = i - bound
                    if 0 <= j < len(call.args):
                        note(call.args[j], call.lineno)
        return out

    def _scan_mutations(self, info: _FuncInfo, exported: dict[str, int]) -> None:
        """Flag in-place writes that land AFTER a name was exported — the
        window where the wire/pin layer may still hold the buffer."""
        def flag(line: int, key: str, what: str) -> None:
            exp_line = exported.get(key)
            if exp_line is None or line <= exp_line:
                return  # untainted, or fill-before-export (safe ordering)
            self.findings.append(Finding(
                self.path, line, "buffer-inplace-export",
                f"{what} after {key} flowed to the zero-copy export on line "
                f"{exp_line} — the wire/pin layer may still hold this "
                f"buffer; copy before export or defer the write",
            ))

        for n in ast.walk(info.node):
            if isinstance(n, ast.Assign):
                for t in n.targets:
                    if isinstance(t, ast.Subscript):
                        key = self._buf_key(t.value)
                        if key:
                            flag(n.lineno, key, f"{key}[...] = assignment")
            elif isinstance(n, ast.AugAssign):
                t = n.target
                base = t.value if isinstance(t, ast.Subscript) else t
                key = self._buf_key(base)
                if key:
                    flag(n.lineno, key, f"augmented assignment to {key}")
            elif isinstance(n, ast.Call):
                chain = _attr_chain(n.func)
                if chain[-1:] == ["copyto"] and n.args:
                    key = self._buf_key(n.args[0])
                    if key:
                        flag(n.lineno, key, f"np.copyto into {key}")
                elif (len(chain) >= 2 and chain[-1] in _INPLACE_METHODS):
                    key = ".".join(chain[:-1])
                    if chain[0] not in ("?",):
                        flag(n.lineno, key, f"in-place .{chain[-1]}() on {key}")

    def _check_view_escape(self, reach: set[str]) -> None:
        for q, info in self.funcs.items():
            name = q.rsplit(".", 1)[-1]
            if info.cls is None or name.startswith("_"):
                continue
            if q in reach:
                continue  # exposure is registered with bufsan
            for n in ast.walk(info.node):
                if not isinstance(n, ast.Return) or n.value is None:
                    continue
                v = n.value
                what = None
                if (isinstance(v, ast.Call) and isinstance(v.func, ast.Name)
                        and v.func.id == "memoryview" and v.args):
                    base = _attr_chain(v.args[0])
                    if base and base[0] == "self":
                        what = f"memoryview({'.'.join(base)})"
                elif isinstance(v, ast.Subscript) and isinstance(v.slice, ast.Slice):
                    base = _attr_chain(v.value)
                    if (base and base[0] == "self"
                            and any(_BUF_NAME_RE.search(p) for p in base[1:])):
                        what = f"slice of {'.'.join(base)}"
                if what is not None:
                    self.findings.append(Finding(
                        self.path, n.lineno, "view-escape",
                        f"{q}() returns {what} — an aliasing view of "
                        f"cache-resident state; .copy()/.tobytes() it, return "
                        f".toreadonly(), or register the exposure with "
                        f"bufsan.export",
                    ))

    # -- blocking-under-lock ------------------------------------------------

    def propagate_blocking(self) -> None:
        """Fixpoint: a function is blocking if it blocks directly or calls a
        local/same-class function that does.  ``blocking`` stores the chain
        for the report."""
        for info in self.funcs.values():
            if info.direct:
                info.blocking = (info.direct[0][1],)
        changed = True
        while changed:
            changed = False
            for info in self.funcs.values():
                if info.blocking is not None:
                    continue
                for kind, name in info.calls:
                    callee = self._resolve(info, kind, name)
                    if callee is not None and callee.blocking is not None:
                        info.blocking = (f"{callee.qualname}()",) + callee.blocking
                        changed = True
                        break

    def _resolve(self, caller: _FuncInfo, kind: str, name: str) -> _FuncInfo | None:
        if kind == "self" and caller.cls is not None:
            return self.funcs.get(f"{caller.cls}.{name}")
        if kind == "bare":
            return self.funcs.get(name)
        return None

    def check_with_regions(self) -> None:
        for info in self.funcs.values():
            for w in ast.walk(info.node):
                if not isinstance(w, ast.With):
                    continue
                held = [item.context_expr for item in w.items
                        if _is_lock_expr(item.context_expr, self.known_locks)]
                if not held:
                    continue
                held_keys = {_expr_key(h) for h in held}
                for stmt in w.body:
                    for call in ast.walk(stmt):
                        if not isinstance(call, ast.Call):
                            continue
                        self._check_call_under_lock(info, call, held_keys)

    def _check_call_under_lock(self, info: _FuncInfo, call: ast.Call,
                               held_keys: set[str]) -> None:
        desc = self._blocking_desc(call)
        chain = _attr_chain(call.func)
        locks = ", ".join(sorted(held_keys))
        if desc is not None:
            if "[wait]" in desc:
                base = ".".join(chain[:-1])
                if base in held_keys:
                    return  # normal condition wait on the held lock
            self.findings.append(Finding(
                self.path, call.lineno, "lock-blocking-call",
                f"{desc} while holding {locks}",
            ))
            return
        # transitive: self.foo()/bare foo() reaching a blocker
        callee = None
        if len(chain) == 2 and chain[0] == "self":
            callee = self._resolve(info, "self", chain[1])
        elif len(chain) == 1:
            callee = self._resolve(info, "bare", chain[0])
        if callee is not None and callee.blocking is not None:
            via = " -> ".join(callee.blocking)
            self.findings.append(Finding(
                self.path, call.lineno, "lock-blocking-call",
                f"{callee.qualname}() reaches {via} while holding {locks}",
            ))


# --------------------------------------------------------------------------
# project passes
# --------------------------------------------------------------------------

def _metric_drift(code_files: list[_FileLint], root: Path) -> list[Finding]:
    findings: list[Finding] = []
    defined: dict[str, tuple[str, int]] = {}
    for fl in code_files:
        if not fl.relpath.startswith("tikv_tpu/"):
            continue
        for name, line in fl.metric_defs:
            defined.setdefault(name, (fl.path, line))
    metrics_dir = root / "metrics"
    if not metrics_dir.is_dir():
        return findings
    refs: dict[str, tuple[str, int]] = {}
    for p in sorted(metrics_dir.rglob("*")):
        if p.suffix not in (".json", ".yml", ".yaml"):
            continue
        for i, line in enumerate(p.read_text().splitlines(), start=1):
            # only PromQL carriers — dashboard titles/uids also match the
            # name regex but reference nothing
            if not ("expr" in line or "query" in line):
                continue
            for m in _METRIC_REF_RE.finditer(line):
                refs.setdefault(m.group(0), (str(p), i))

    def base_of(ref: str) -> str:
        for suf in _HISTO_SUFFIXES:
            if ref.endswith(suf) and ref[: -len(suf)] in defined:
                return ref[: -len(suf)]
        return ref

    for ref, (path, line) in sorted(refs.items()):
        if base_of(ref) not in defined:
            findings.append(Finding(
                path, line, "metric-drift-dashboard",
                f"{ref} referenced here but defined by no REGISTRY call",
            ))
    ref_blob = set(refs)
    for name, (path, line) in sorted(defined.items()):
        used = name in ref_blob or any(name + s in ref_blob for s in _HISTO_SUFFIXES)
        if not used:
            findings.append(Finding(
                path, line, "metric-drift-code",
                f"metric {name} is exported but appears on no dashboard or "
                f"alert rule",
            ))
    return findings


def _failpoint_drift(code_files: list[_FileLint]) -> list[Finding]:
    findings: list[Finding] = []
    source_sites: dict[str, tuple[str, int]] = {}
    local_sites: dict[str, set[str]] = {}  # per test file
    cfgs: list[tuple[str, str, int]] = []
    for fl in code_files:
        if fl.relpath.startswith("tikv_tpu/"):
            for name, line in fl.failpoint_sites:
                source_sites.setdefault(name, (fl.path, line))
        else:
            for name, _line in fl.failpoint_sites:
                local_sites.setdefault(fl.path, set()).add(name)
            for name, line in fl.failpoint_cfgs:
                cfgs.append((name, fl.path, line))
    cfg_names = {n for n, _p, _l in cfgs}
    for name, path, line in cfgs:
        if name in source_sites or name in local_sites.get(path, ()):
            continue
        findings.append(Finding(
            path, line, "failpoint-drift-test",
            f"failpoint {name!r} configured here but no fail_point site "
            f"defines it (renamed or removed in a refactor?)",
        ))
    # the doc example in util/failpoint.py's docstring is code, not a site
    for name, (path, line) in sorted(source_sites.items()):
        if name == "name" and path.endswith("util/failpoint.py"):
            continue
        if name not in cfg_names:
            findings.append(Finding(
                path, line, "failpoint-drift-source",
                f"fail_point({name!r}) is never configured by any test — "
                f"dead injection site or missing coverage",
            ))
    return findings


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

def _collect_py(paths: list[str], root: Path) -> list[Path]:
    out: list[Path] = []
    for p in paths:
        pp = (root / p) if not Path(p).is_absolute() else Path(p)
        if pp.is_dir():
            out.extend(sorted(pp.rglob("*.py")))
        elif pp.suffix == ".py":
            out.append(pp)
    return out


def run(paths: list[str], root: Path | None = None,
        drift: bool = True) -> tuple[list[Finding], list[Finding]]:
    """Lint ``paths``; returns (active, waived) findings."""
    root = root or _repo_root()
    files = _collect_py(paths, root)
    file_lints: list[_FileLint] = []
    findings: list[Finding] = []
    waiver_maps: dict[str, dict[int, set[str]]] = {}
    for path in files:
        try:
            src = path.read_text()
            tree = ast.parse(src)
        except (SyntaxError, UnicodeDecodeError) as e:
            findings.append(Finding(str(path), getattr(e, "lineno", 1) or 1,
                                    "parse-error", str(e)))
            continue
        try:
            rel = str(path.resolve().relative_to(root))
        except ValueError:
            rel = str(path)
        fl = _FileLint(str(path), tree, rel)
        fl.visit(tree)
        fl.propagate_blocking()
        fl.check_with_regions()
        fl.check_jit()
        fl.check_bufsan()
        file_lints.append(fl)
        waiver_maps[str(path)] = _waivers_for(src.splitlines())
        # nested lock withs walk the same call once per enclosing region —
        # one finding per (line, rule) is enough
        seen: set[tuple[int, str]] = set()
        for f in fl.findings:
            if (f.line, f.rule) not in seen:
                seen.add((f.line, f.rule))
                findings.append(f)
    if drift:
        findings.extend(_metric_drift(file_lints, root))
        findings.extend(_failpoint_drift(file_lints))
    # waivers; findings in files we didn't parse (the metrics/ JSONs) have
    # no in-line waiver channel and stay active
    for f in findings:
        wmap = waiver_maps.get(f.path)
        if wmap:
            _apply_waivers([f], wmap)
    active = [f for f in findings if not f.waived]
    waived = [f for f in findings if f.waived]
    return active, waived


def _repo_root() -> Path:
    # tikv_tpu/analysis/lint.py -> repo root two levels above the package
    return Path(__file__).resolve().parents[2]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tikv-tpu-lint",
        description="Project linter: concurrency + device recompile hazards, "
                    "metric and failpoint drift.",
    )
    ap.add_argument("paths", nargs="*", default=["tikv_tpu", "tests"])
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--show-waived", action="store_true",
                    help="also print waived findings")
    ap.add_argument("--no-drift", action="store_true",
                    help="skip the project-wide metric/failpoint drift passes")
    args = ap.parse_args(argv)
    if args.list_rules:
        for name, desc in RULES.items():
            print(f"{name:26s} {desc}")
        return 0
    active, waived = run(args.paths or ["tikv_tpu", "tests"],
                         drift=not args.no_drift)
    for f in active:
        print(f.format())
    if args.show_waived:
        for f in waived:
            print(f.format())
    print(f"lint: {len(active)} finding(s), {len(waived)} waived", file=sys.stderr)
    return 1 if active else 0


if __name__ == "__main__":
    sys.exit(main())
