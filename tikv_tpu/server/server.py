"""TCP server: the node's RPC front door.

Re-expression of ``src/server/server.rs`` + the ``batch_commands`` stream
(service/kv.rs:891): one socket per client, length-prefixed frames, each frame
``[req_id, method, request]`` (wire codec) answered out of order —
multiplexed like batch_commands.  A thread-pool executes handlers so slow
commands don't block the socket reader.
"""

from __future__ import annotations

import contextlib
import inspect
import os
import queue
import socket
import struct
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from ..analysis import bufsan as _bufsan
from ..util import error_code, trace
from ..util.metrics import REGISTRY
from ..util.worker import TaskPriority, UnifiedReadPool
from . import wire
from .service import KvService

# the reference's grpc request metrics (tikv_grpc_msg_* in metrics.rs):
# per-method counts + latency over the framed-TCP transport
GRPC_MSG_TOTAL = REGISTRY.counter(
    "tikv_grpc_msg_total", "RPCs served, by method")
GRPC_MSG_DURATION = REGISTRY.histogram(
    "tikv_grpc_msg_duration_seconds", "RPC handling latency, by method")
GRPC_MSG_FAIL = REGISTRY.counter(
    "tikv_grpc_msg_fail_total", "RPCs that returned an error, by method")
# per-stage wire-path breakdown (docs/wire_path.md): where a served frame's
# time goes — decode (frame bytes -> request value), route (read/handler
# pool queue wait), execute (service dispatch), encode (response value ->
# socket).  THE profiling surface for the decode->endpoint->encode gap;
# summarized by the debug_wire_stages RPC and read by the benchmark
# (`wire_codec_ms_per_task`, PERF.md section 3).
WIRE_STAGE = REGISTRY.histogram(
    "tikv_wire_stage_seconds",
    "Wire-path time per served frame, by stage",
    buckets=(1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1, 5),
)

error_code.register_builtin()

_LEN = struct.Struct(">I")
_MAX_FRAME = 64 << 20

# read-path RPCs go through the unified read pool (src/read_pool.rs routes
# point gets / scans / coprocessor there); writes keep the plain executor so
# a saturated analytical workload can't starve the write path's threads
# max unacked streamed frames in flight per stream (gRPC window analog);
# both sides hold at most this many frames regardless of consumer speed
STREAM_WINDOW = 8
# a stream whose consumer sends no ack (and no cancel) for this long is
# dropped so it cannot pin a read-pool worker indefinitely
STREAM_IDLE_TIMEOUT = 300.0

_READ_METHODS = (
    "kv_get", "kv_batch_get", "kv_scan", "kv_scan_lock",
    "raw_get", "raw_batch_get", "raw_scan", "raw_batch_scan", "raw_get_key_ttl",
    "coprocessor", "coprocessor_stream", "coprocessor_batch", "raw_coprocessor",
    "mvcc_get_by_key", "mvcc_get_by_start_ts",
)


def _read_exact(sock: socket.socket, n: int) -> bytes | None:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            return None
        buf += chunk
    return buf


def read_frame(sock: socket.socket) -> bytes | None:
    hdr = _read_exact(sock, 4)
    if hdr is None:
        return None
    (n,) = _LEN.unpack(hdr)
    if n > _MAX_FRAME:
        raise ValueError("frame too large")
    return _read_exact(sock, n)


def write_frame(sock: socket.socket, payload: bytes) -> None:
    sock.sendall(_LEN.pack(len(payload)) + payload)


try:
    #: the kernel rejects a sendmsg with more iovecs than this (EMSGSIZE) —
    #: a many-payload response (batch coprocessor) must gather in slices
    _IOV_MAX = os.sysconf("SC_IOV_MAX")
except (AttributeError, OSError, ValueError):
    _IOV_MAX = 1024


def write_frame_parts(sock: socket.socket, parts: list) -> None:
    """One frame from a ``wire.dumps_parts`` buffer list: gather-write via
    ``sendmsg`` so a large response payload (coprocessor chunk data) goes
    header + passthrough buffers straight to the kernel — no single-buffer
    concatenation copy.  TLS sockets (no sendmsg) fall back to a join.

    This is the RELEASE boundary of the zero-copy exposure window: once the
    send completes (or the socket dies), the passthrough buffers are no
    longer aliased by the kernel, and bufsan verifies each one's sample
    against its ``dumps_parts`` registration."""
    try:
        bufs = [memoryview(_LEN.pack(sum(len(p) for p in parts)))]
        bufs += [p if isinstance(p, memoryview) else memoryview(p) for p in parts]
        sendmsg = getattr(sock, "sendmsg", None)
        if sendmsg is None:
            sock.sendall(b"".join(bufs))
            return
        try:
            sent = sendmsg(bufs[:_IOV_MAX])
        except (NotImplementedError, OSError) as e:
            if isinstance(e, OSError):
                raise
            sock.sendall(b"".join(bufs))  # ssl.SSLSocket raises NotImplementedError
            return
        # a partial gather write is legal: advance through the buffer list
        while True:
            while bufs and sent >= len(bufs[0]):
                sent -= len(bufs[0])
                bufs.pop(0)
            if not bufs:
                return
            if sent:
                bufs[0] = bufs[0][sent:]
            sent = sendmsg(bufs[:_IOV_MAX])
    finally:
        _bufsan.release_parts(parts, site="server.write_frame_parts")


class Server:
    def __init__(
        self,
        service: KvService,
        host: str = "127.0.0.1",
        port: int = 0,
        workers: int = 8,
        security=None,
        read_pool_workers: int | None = None,  # ReadPoolConfig.unified_max_threads
        inbound=None,  # util.inbound.InboundReads shared with the read scheduler
    ):
        self.service = service
        self.inbound = inbound
        self.security = security
        self._ssl_ctx = security.server_context() if security is not None else None
        self._sock = socket.create_server((host, port))
        self.addr = self._sock.getsockname()
        self._pool = ThreadPoolExecutor(max_workers=workers)
        # created lazily on the first read-method dispatch: PD / raft-only
        # servers never pay for read-pool threads
        self._read_pool: UnifiedReadPool | None = None
        self._read_pool_workers = read_pool_workers or workers
        self._read_pool_mu = threading.Lock()
        self._stop = threading.Event()
        self._accept_thread: threading.Thread | None = None
        self._pb_gateway_inst = None
        self._pb_gateway_mu = threading.Lock()

    def _pb_gateway(self):
        with self._pb_gateway_mu:
            if self._pb_gateway_inst is None:
                from .pb_gateway import PbGateway

                self._pb_gateway_inst = PbGateway(self.service)
            return self._pb_gateway_inst

    def _trace_root(self, method: str, request, t_dec: float, t_dec_end: float):
        """The request's root span, spanning decode→encode (docs/tracing.md):
        joins the trace the request context carries (forwarded hops and
        client-held traces propagate over the wire as plain context keys) or
        head-samples a fresh one.  The frame-decode stage — measured before
        any span could exist — lands as an explicitly-timed child."""
        ctx = None
        if isinstance(request, dict):
            c = request.get("context")
            if isinstance(c, dict) and c.get("trace_id"):
                ctx = c
        if ctx is None and not trace.enabled():
            return trace.NOOP
        root = trace.start_trace(
            f"rpc.{method}", ctx=ctx, start=t_dec, method=method,
            store=getattr(getattr(self.service, "read_plane", None),
                          "store_id", None) or "")
        if root:
            root.record("wire.decode", t_dec, t_dec_end, stage=True)
        return root

    @property
    def read_pool(self) -> UnifiedReadPool:
        with self._read_pool_mu:
            if self._read_pool is None:
                if self._stop.is_set():
                    # a frame racing shutdown must not birth an unstoppable pool
                    raise RuntimeError("server is stopped")
                self._read_pool = UnifiedReadPool(
                    workers=self._read_pool_workers, name="unified-read-pool"
                )
            return self._read_pool

    def start(self) -> None:
        self._accept_thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._accept_thread.start()

    def _accept_loop(self) -> None:
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except TimeoutError:
                continue
            except OSError:
                return
            threading.Thread(target=self._handshake_and_serve, args=(conn,), daemon=True).start()

    def _handshake_and_serve(self, conn: socket.socket) -> None:
        if self._ssl_ctx is not None:
            try:
                conn = self._ssl_ctx.wrap_socket(conn, server_side=True)
                self.security.check_common_name(conn)
            except Exception:  # noqa: BLE001 — failed handshake, drop the peer
                conn.close()
                return
        self._serve_conn(conn)

    def _serve_conn(self, conn: socket.socket) -> None:
        send_mu = threading.Lock()
        # per-stream flow-control credits (the gRPC window role): a stream's
        # writer may have at most STREAM_WINDOW unacked frames in flight;
        # the client acks as its consumer drains, so memory is O(window)
        # on BOTH sides no matter how slow the consumer is
        stream_credits: dict[int, threading.Semaphore] = {}
        stream_cancelled: set[int] = set()
        conn_dead = threading.Event()
        try:
            while not self._stop.is_set():
                frame = read_frame(conn)
                if frame is None:
                    return
                t_dec = time.perf_counter()
                req_id, method, request = wire.loads(frame)
                t_dec_end = time.perf_counter()
                WIRE_STAGE.observe(t_dec_end - t_dec, stage="decode")

                if method == "_stream_ack":
                    sem = stream_credits.get(request.get("id"))
                    if sem is not None:
                        for _ in range(int(request.get("n", 1))):
                            sem.release()
                    continue
                if method == "_stream_cancel":
                    sid = request.get("id")
                    # record the cancel even when the stream's writer has
                    # not registered yet (request still queued in the pool):
                    # the writer checks this set right after registering
                    stream_cancelled.add(sid)
                    sem = stream_credits.get(sid)
                    if sem is not None:
                        sem.release()  # wake the parked writer to notice
                    continue

                if req_id == 0:
                    # oneway frame (peer raft traffic): no response, and run
                    # INLINE so frames keep the connection's FIFO order —
                    # snapshot chunks and raft messages must not be reordered
                    # by pool scheduling (the reference's peer stream is
                    # likewise ordered per connection)
                    try:
                        self.service.dispatch(method, request)
                    except Exception:  # noqa: BLE001 — lossy channel
                        pass
                    continue

                t_submit = time.perf_counter()
                # request-root span (docs/tracing.md): joins the trace the
                # context carries (forwarded hops, client-initiated traces)
                # or head-samples a fresh one; the wire stages land as child
                # spans mirroring the WIRE_STAGE histogram.  One branch and
                # no allocation when tracing is off and no ctx carries a
                # trace id.
                root = self._trace_root(method, request, t_dec, t_dec_end)
                is_read = method.removeprefix("pb/") in _READ_METHODS
                # a read is on its way to the read scheduler from here until
                # the scheduler parks it or its handler ends (util/inbound.py)
                counted = is_read and self.inbound is not None

                def run(req_id=req_id, method=method, request=request,
                        t_submit=t_submit, root=root, t_dec_end=t_dec_end,
                        counted=counted):
                    t0 = time.perf_counter()
                    # route = pool queue wait: submission to handler start
                    WIRE_STAGE.observe(t0 - t_submit, stage="route")
                    if root:
                        # the span tiles the root exactly: decode-end to
                        # handler start is ALL routing overhead (trace/
                        # closure bookkeeping + pool queue wait), so the
                        # stage spans account for the whole request
                        root.record("wire.route", t_dec_end, t0, stage=True)
                    # the handler pays the inbound count down as it returns
                    # or raises, unless the scheduler took it at its queue
                    owes = (self.inbound.handling() if counted
                            else contextlib.nullcontext())
                    try:
                        with owes, root.active(), trace.span("wire.execute"):
                            if method.startswith("pb/"):
                                # kvproto mode: request/response are protobuf
                                # bytes (pb_gateway), framing unchanged
                                resp = self._pb_gateway().handle(method[3:], request)
                            else:
                                resp = self.service.dispatch(method, request)
                    except Exception as e:  # noqa: BLE001 — wire boundary
                        resp = {"error": {"other": repr(e), "code": error_code.code_of(e)}}
                    GRPC_MSG_TOTAL.inc(method=method)
                    t_done = time.perf_counter()
                    GRPC_MSG_DURATION.observe(t_done - t0, method=method)
                    WIRE_STAGE.observe(t_done - t0, stage="execute")
                    if isinstance(resp, dict) and resp.get("error"):
                        GRPC_MSG_FAIL.inc(method=method)
                    if inspect.isgenerator(resp) and root:
                        # streaming responses finish the root HERE: the
                        # per-frame credit loop below has early-return paths
                        # (consumer gone/cancelled) that must not leak an
                        # open trace record
                        root.tag(streaming=True)
                        root.finish()
                        root = trace.NOOP
                    if inspect.isgenerator(resp):
                        # server-streaming response (endpoint.rs:508): one
                        # wire frame per yielded item, same req_id, closed by
                        # a stream_end frame.  send_mu is taken PER FRAME so
                        # a long stream interleaves with other responses on
                        # the connection; the credit window caps in-flight
                        # frames so neither side buffers more than O(window).
                        sem = threading.Semaphore(STREAM_WINDOW)
                        stream_credits[req_id] = sem
                        final = {"stream_end": True}
                        try:
                            if req_id in stream_cancelled:
                                return  # cancelled before we even started
                            for item in resp:
                                # bounded park: a consumer that neither acks
                                # nor cancels must not pin this pool worker
                                # forever (STREAM_IDLE_TIMEOUT)
                                stalled = 0.0
                                while not sem.acquire(timeout=1.0):
                                    stalled += 1.0
                                    if (conn_dead.is_set() or self._stop.is_set()
                                            or stalled >= STREAM_IDLE_TIMEOUT):
                                        return  # consumer gone; drop stream
                                if req_id in stream_cancelled:
                                    return  # consumer abandoned the stream
                                parts = wire.dumps_parts([req_id, {"stream": item}])
                                with send_mu:
                                    # lint: allow(lock-blocking-call) -- send_mu
                                    # guards exactly this socket: frames from
                                    # concurrent handlers must not interleave
                                    write_frame_parts(conn, parts)
                        except OSError:
                            return  # client went away mid-stream
                        except Exception as e:  # noqa: BLE001 — wire boundary
                            final["error"] = {"other": repr(e),
                                              "code": error_code.code_of(e)}
                        finally:
                            stream_credits.pop(req_id, None)
                            stream_cancelled.discard(req_id)
                        resp = final
                    # single-buffer response assembly: dumps_parts emits the
                    # response's large bytes payloads (coprocessor chunk
                    # data) as passthrough buffers and the frame writer
                    # gather-writes them — no re-encoding copy of the data
                    t_enc = time.perf_counter()
                    # response assembly, then the frame write with its wait
                    # for the socket's turn: two stages that tile the root
                    # from execute-end on (see wire.route)
                    with root.active():
                        with trace.stage("wire.encode"):
                            parts = wire.dumps_parts([req_id, resp])
                        with trace.stage("wire.send"), send_mu:
                            try:
                                # lint: allow(lock-blocking-call) -- per-socket
                                # frame serialization (same as the stream path)
                                write_frame_parts(conn, parts)
                            except OSError:
                                pass
                    t_enc_end = time.perf_counter()
                    WIRE_STAGE.observe(t_enc_end - t_enc, stage="encode")
                    root.finish(end=t_enc_end)

                if is_read:
                    ctx, group = {}, id(conn)
                    prio_hint = None
                    if isinstance(request, dict):
                        c = request.get("context")
                        ctx = c if isinstance(c, dict) else {}
                        # group by caller txn (start_ts); falls back per-conn
                        group = ctx.get("resource_group") or request.get("start_ts") or id(conn)
                    elif isinstance(request, bytes):
                        # pb mode: peek at Context (task_id, priority) without
                        # a full request decode
                        from .pb_gateway import sched_hints

                        g, prio_hint = sched_hints(request)
                        group = g or id(conn)
                    prio = (
                        TaskPriority.HIGH
                        if ctx.get("priority") == "high" or prio_hint == "high"
                        else TaskPriority.NORMAL
                    )
                    if counted:
                        self.inbound.arrived()
                    try:
                        self.read_pool.submit(run, group=group, priority=prio)
                    except RuntimeError:  # pool/server stopped mid-shutdown
                        if counted:
                            self.inbound.left()
                        root.finish()
                        return
                else:
                    try:
                        self._pool.submit(run)
                    except RuntimeError:  # executor shut down mid-frame
                        root.finish()
                        return
        except (ConnectionError, ValueError, OSError):
            pass
        finally:
            conn_dead.set()  # wake any stream writer parked on credits
            conn.close()

    def stop(self) -> None:
        self._stop.set()
        self._sock.close()
        self._pool.shutdown(wait=False)
        with self._read_pool_mu:
            if self._read_pool is not None:
                self._read_pool.stop()


_STREAM_DEAD = object()  # sentinel: connection died under an open stream


class Client:
    """Blocking client with request multiplexing (ReqBatcher flavor)."""

    def __init__(self, host: str, port: int, security=None):
        self._sock = socket.create_connection((host, port))
        if security is not None and security.enabled:
            self._sock = security.client_context().wrap_socket(self._sock)
        self._dead = False
        self._mu = threading.Lock()
        # writes serialize separately from bookkeeping: concurrent callers
        # interleaving sendall bytes mid-frame would desync the server
        self._send_mu = threading.Lock()
        self._next_id = 0
        self._pending: dict[int, threading.Event] = {}
        self._results: dict[int, object] = {}
        # server-streaming calls: req_id -> bounded frame queue; the reader
        # pushes each same-id frame, the consumer iterates (call_stream)
        self._streams: dict[int, queue.Queue] = {}
        self._reader = threading.Thread(target=self._read_loop, daemon=True)
        self._reader.start()

    def _read_loop(self) -> None:
        try:
            while True:
                frame = read_frame(self._sock)
                if frame is None:
                    return
                req_id, resp = wire.loads(frame)
                with self._mu:
                    q = self._streams.get(req_id)
                    if q is not None:
                        if isinstance(resp, dict) and resp.get("stream_end"):
                            del self._streams[req_id]
                        q.put(resp)
                        continue
                    ev = self._pending.pop(req_id, None)
                    if ev is None:
                        continue  # late frame for a cancelled/timed-out call
                    self._results[req_id] = resp
                ev.set()
        except (ConnectionError, OSError, ValueError):
            with self._mu:
                self._dead = True
                for ev in self._pending.values():
                    ev.set()
                self._pending.clear()
                for q in self._streams.values():
                    q.put(_STREAM_DEAD)
                self._streams.clear()

    def call(self, method: str, request: dict, timeout: float = 30.0):
        with self._mu:
            if self._dead:
                raise ConnectionError("connection is closed")
            self._next_id += 1
            req_id = self._next_id
            ev = threading.Event()
            self._pending[req_id] = ev
        with self._send_mu:
            # lint: allow(lock-blocking-call) -- _send_mu exists to serialize
            # frames on this client's one socket
            write_frame(self._sock, wire.dumps([req_id, method, request]))
        if not ev.wait(timeout):
            with self._mu:
                # deregister so a late response is dropped, not leaked
                self._pending.pop(req_id, None)
                self._results.pop(req_id, None)
            raise TimeoutError(f"{method} timed out")
        with self._mu:
            if req_id not in self._results:
                raise ConnectionError(f"connection lost during {method}")
            return self._results.pop(req_id)

    def call_stream(self, method: str, request: dict, timeout: float = 30.0):
        """Server-streaming call: returns an iterator yielding each streamed
        item as the server produces it (kv.rs coprocessor_stream:574).  The
        request is sent EAGERLY (before the first next()); in-flight frames
        are capped by the server-side credit window, and the final
        stream_end frame may carry a mid-stream execution error, raised on
        the consumer."""
        with self._mu:
            if self._dead:
                raise ConnectionError("connection is closed")
            self._next_id += 1
            req_id = self._next_id
            q: queue.Queue = queue.Queue()
            self._streams[req_id] = q
        with self._send_mu:
            # lint: allow(lock-blocking-call) -- per-socket frame serialization
            write_frame(self._sock, wire.dumps([req_id, method, request]))
        return self._stream_iter(method, req_id, q, timeout)

    def _stream_iter(self, method: str, req_id: int, q: "queue.Queue", timeout: float):
        finished = False
        try:
            while True:
                try:
                    resp = q.get(timeout=timeout)
                except queue.Empty:
                    raise TimeoutError(f"{method} stream timed out") from None
                if resp is _STREAM_DEAD:
                    finished = True
                    raise ConnectionError(f"connection lost during {method}")
                if isinstance(resp, dict) and resp.get("stream_end"):
                    finished = True
                    if resp.get("error"):
                        raise RuntimeError(f"{method} failed mid-stream: {resp['error']}")
                    return
                if isinstance(resp, dict) and "stream" in resp:
                    yield resp["stream"]
                    # consumer drained one frame: grant the server one
                    # credit (oneway ack — no response expected)
                    try:
                        with self._send_mu:
                            # lint: allow(lock-blocking-call) -- per-socket
                            # frame serialization
                            write_frame(self._sock, wire.dumps(
                                [0, "_stream_ack", {"id": req_id, "n": 1}]))
                    except OSError:
                        finished = True
                        raise ConnectionError(f"connection lost during {method}")
                else:
                    # unary shape: pre-stream validation error (or a non-
                    # streaming server) — no stream_end will follow, so the
                    # registration must be dropped here, not by _read_loop
                    finished = True
                    with self._mu:
                        self._streams.pop(req_id, None)
                    if isinstance(resp, dict) and resp.get("error"):
                        raise RuntimeError(f"{method} failed: {resp['error']}")
                    yield resp
                    return
        finally:
            if not finished:
                # consumer abandoned the stream early: tell the server so
                # its writer doesn't stay parked waiting for credits
                with self._mu:
                    self._streams.pop(req_id, None)
                try:
                    with self._send_mu:
                        # lint: allow(lock-blocking-call) -- per-socket frame
                        # serialization
                        write_frame(self._sock, wire.dumps(
                            [0, "_stream_cancel", {"id": req_id}]))
                except OSError:
                    pass

    def close(self) -> None:
        self._sock.close()
