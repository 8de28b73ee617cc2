"""Wire-path tests (ISSUE 8, docs/wire_path.md).

Covers the four layers of the unfrozen cluster wire path:

* the server wire codec: property-based round-trips (deep/large values),
  ``dumps_parts`` zero-copy byte-identity, memoryview-based decode, and the
  gather frame writer;
* the vectorized datum/chunk encoders vs the per-row scalar encoders —
  across every datum type, null patterns, dictionary encodings, chunk
  framing splits, and BOTH row formats (rowv1/rowv2);
* socket-level coalesced serving: concurrent connections through the read
  scheduler's continuous lanes must produce byte-identical responses to
  serial per-request serving, with the stage histogram + coalesce counter
  populated;
* device-owner forwarding: the one-hop, loop-guarded, breaker-protected
  route to the store owning the warm region image.
"""

from __future__ import annotations

import random
import socket
import threading

import numpy as np
import pytest

from tikv_tpu.copr import datum as datum_mod, datum_vec
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.chunk_codec import ChunkColumn, decode_column
from tikv_tpu.copr.dag import (
    Aggregation,
    DagRequest,
    ResponseEncoder,
    Selection,
    TableScan,
)
from tikv_tpu.copr.dag_wire import dag_to_wire
from tikv_tpu.copr.datatypes import (
    Chunk,
    Column,
    ColumnInfo,
    EvalType,
    FieldType,
    enum_column,
    set_column,
)
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rpn import call as rpn_call, col, const_int
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.copr.rowv2 import encode_row_v2
from tikv_tpu.server import wire
from tikv_tpu.server.read_plane import ReadPlane
from tikv_tpu.server.server import (
    Client,
    Server,
    read_frame,
    write_frame_parts,
)
from tikv_tpu.server.service import KvService
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.storage import Storage
from tikv_tpu.util import codec
from tikv_tpu.util.metrics import REGISTRY

from copr_fixtures import TABLE_ID
from fixtures import put_committed

# ---------------------------------------------------------------------------
# server wire codec
# ---------------------------------------------------------------------------


def _random_value(rng: random.Random, depth: int = 0):
    t = rng.randrange(9 if depth < 4 else 6)
    if t == 0:
        return None
    if t == 1:
        return rng.choice([True, False])
    if t == 2:
        return rng.randrange(-(2**63), 2**63)
    if t == 3:
        return rng.random() * 10**rng.randrange(-5, 6)
    if t == 4:
        n = rng.choice([0, 1, 7, 100, 5000])
        return bytes(rng.randrange(256) for _ in range(n))
    if t == 5:
        return "".join(chr(rng.randrange(32, 0x2FF)) for _ in range(rng.randrange(0, 40)))
    if t == 6:
        return [_random_value(rng, depth + 1) for _ in range(rng.randrange(0, 6))]
    if t == 7:
        return tuple(_random_value(rng, depth + 1) for _ in range(rng.randrange(0, 4)))
    return {
        _random_value(rng, 5): _random_value(rng, depth + 1)
        for _ in range(rng.randrange(0, 5))
    }


def _materialize(v):
    if isinstance(v, memoryview):
        return bytes(v)
    if isinstance(v, list):
        return [_materialize(x) for x in v]
    if isinstance(v, tuple):
        return tuple(_materialize(x) for x in v)
    if isinstance(v, dict):
        return {_materialize(k): _materialize(x) for k, x in v.items()}
    return v


def test_wire_roundtrip_property():
    """Property-based round-trip incl. deep nesting and large payloads:
    dumps == concat(dumps_parts), loads inverts, bytes_view decodes to the
    same values (views materialized), memoryview/bytearray inputs accepted."""
    rng = random.Random(1234)
    for i in range(200):
        v = _random_value(rng)
        b = wire.dumps(v)
        parts = wire.dumps_parts(v)
        assert b == b"".join(bytes(p) for p in parts), f"case {i}"
        assert wire.loads(b) == v, f"case {i}"
        assert wire.loads(memoryview(b)) == v, f"case {i}"
        assert wire.loads(bytearray(b)) == v, f"case {i}"
        assert _materialize(wire.loads(b, bytes_view=True)) == v, f"case {i}"


def test_wire_deep_and_trailing_guards():
    deep = None
    for _ in range(40):
        deep = [deep]
    with pytest.raises(ValueError):
        wire.dumps(deep)
    ok = 1
    for _ in range(32):
        ok = [ok]
    assert wire.loads(wire.dumps(ok)) == ok
    with pytest.raises(ValueError):
        wire.loads(wire.dumps(1) + b"\x00")


def test_wire_parts_large_payload_is_not_copied():
    big = bytes(range(256)) * 64  # 16 KiB >= PASSTHROUGH_MIN
    parts = wire.dumps_parts({"data": big, "n": 1})
    views = [p for p in parts if isinstance(p, memoryview)]
    assert views and any(v.obj is big for v in views), \
        "large payload must pass through as a view of the caller's buffer"
    small = b"x" * 16
    parts_small = wire.dumps_parts({"data": small})
    assert not any(isinstance(p, memoryview) and p.obj is small
                   for p in parts_small)


def test_wire_bytes_view_zero_copy_decode():
    big = b"z" * (wire.PASSTHROUGH_MIN + 1)
    frame = wire.dumps({"data": big, "k": b"small"})
    v = wire.loads(frame, bytes_view=True)
    assert isinstance(v["data"], memoryview) and bytes(v["data"]) == big
    assert isinstance(v["k"], bytes)  # small payloads stay plain bytes


def test_write_frame_parts_gather_matches_plain_frame():
    value = [7, "resp", {"data": bytes(range(256)) * 40, "ok": True}]
    a, b = socket.socketpair()
    try:
        write_frame_parts(a, wire.dumps_parts(value))
        got = read_frame(b)
    finally:
        a.close()
        b.close()
    assert got == wire.dumps(value)
    assert wire.loads(got) == value


# ---------------------------------------------------------------------------
# vectorized datum / chunk encoders
# ---------------------------------------------------------------------------


def _scalar_rows(cols, rows) -> bytes:
    out = bytearray()
    for r in rows:
        out += codec.encode_var_u64(len(cols))
        for c in cols:
            flag, value = c.datum_at(int(r))
            datum_mod.encode_datum(out, flag, value)
    return bytes(out)


def _mixed_columns(n: int, rng: np.random.Generator) -> list[Column]:
    mk = lambda p, f: [None if rng.random() < p else f() for _ in range(n)]
    cols = [
        Column.from_values(EvalType.INT,
                           mk(0.1, lambda: int(rng.integers(-(2**63), 2**63 - 1)))),
        Column.from_values(EvalType.REAL, mk(0.1, lambda: float(rng.normal() * 1e18))),
        Column.from_values(EvalType.DECIMAL,
                           mk(0.1, lambda: int(rng.integers(-(10**12), 10**12))), frac=4),
        Column.from_values(EvalType.BYTES,
                           mk(0.1, lambda: bytes(rng.integers(0, 256, rng.integers(0, 40)).astype(np.uint8)))),
        Column.from_values(EvalType.DURATION,
                           mk(0.1, lambda: int(rng.integers(-(10**15), 10**15)))),
        Column.from_values(EvalType.DATETIME,
                           mk(0.1, lambda: int(rng.integers(0, 2**63 - 1)))),
        enum_column([int(rng.integers(0, 4)) for _ in range(n)], (b"a", b"bb", b"ccc")),
        set_column([int(rng.integers(0, 8)) for _ in range(n)], (b"x", b"y", b"z")),
        Column(EvalType.BYTES, rng.integers(0, 3, n), np.zeros(n, bool),
               dictionary=np.array([b"alpha", b"beta", b"gamma"], dtype=object)),
        Column.from_values(EvalType.INT, [None] * n),
        Column.from_values(EvalType.INT,
                           ([0, -1, 1, -(2**63), 2**63 - 1] * (n // 5 + 1))[:n]),
    ]
    return cols


def test_vectorized_rows_byte_identical_all_types():
    rng = np.random.default_rng(7)
    n = 500
    cols = _mixed_columns(n, rng)
    rows = np.arange(n)
    buf, ends = datum_vec.encode_chunk_rows(cols, rows)
    want = _scalar_rows(cols, rows)
    assert buf == want
    assert int(ends[-1]) == len(want)
    # a logical-row selection (executor mask semantics)
    sel = np.sort(rng.choice(n, 117, replace=False))
    assert datum_vec.encode_chunk_rows(cols, sel)[0] == _scalar_rows(cols, sel)
    # empty selection
    b0, e0 = datum_vec.encode_chunk_rows(cols, np.empty(0, np.int64))
    assert b0 == b"" and len(e0) == 0


def test_varint_batch_identity():
    rng = np.random.default_rng(11)
    vals = np.concatenate([
        rng.integers(0, 2**63 - 1, 200, dtype=np.int64).view(np.uint64),
        np.array([0, 1, 127, 128, 2**32, 2**63, 2**64 - 1], np.uint64),
    ])
    data, lens = codec.encode_var_u64_batch(vals)
    want = b"".join(codec.encode_var_u64(int(v)) for v in vals)
    assert data.tobytes() == want
    assert [len(codec.encode_var_u64(int(v))) for v in vals] == lens.tolist()
    ivals = np.array([0, -1, 1, -(2**63), 2**63 - 1, -123456789], np.int64)
    idata, _ = codec.encode_var_i64_batch(ivals)
    assert idata.tobytes() == b"".join(codec.encode_var_i64(int(v)) for v in ivals)


@pytest.mark.parametrize("chunk_rows", [1, 7, 100, 1024])
def test_response_encoder_framing_identical(chunk_rows, monkeypatch):
    rng = np.random.default_rng(3)
    n = 300
    cols = _mixed_columns(n, rng)

    def run(vec: bool):
        monkeypatch.setattr(datum_vec, "VEC_MIN_ROWS", 1 if vec else 10**9)
        enc = ResponseEncoder(chunk_rows)
        for lo, hi in ((0, 33), (33, 34), (34, n)):
            enc.add_chunk(Chunk(cols, np.arange(lo, hi)), None)
        return enc.finish()

    assert run(True) == run(False)


def test_response_encoder_output_offsets(monkeypatch):
    rng = np.random.default_rng(5)
    cols = _mixed_columns(64, rng)
    chunk = Chunk(cols, np.arange(64))

    def run(vec: bool):
        monkeypatch.setattr(datum_vec, "VEC_MIN_ROWS", 1 if vec else 10**9)
        enc = ResponseEncoder(50)
        enc.add_chunk(chunk, [2, 0, 5])
        return enc.finish()

    assert run(True) == run(False)


def test_chunk_column_extend_identity():
    for ft, values in [
        (FieldType.int64(), [1, None, -5, 2**40, None] * 20),
        (FieldType.double(), [1.5, None, -2.25, 1e300] * 25),
    ]:
        a, b = ChunkColumn(ft), ChunkColumn(ft)
        for v in values:
            a.append(v)
        b.extend(values)
        assert a.encode() == b.encode()
        # decode round-trips through the vectorized offsets reader
        dec, consumed = decode_column(a.encode(), 0, ft)
        assert consumed == len(a.encode())
        assert dec.rows == len(values)


# ---------------------------------------------------------------------------
# rowv1 / rowv2 serving byte-identity
# ---------------------------------------------------------------------------

_WIDE_COLUMNS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.varchar()),
    ColumnInfo(3, FieldType.int64()),
    ColumnInfo(4, FieldType.decimal_type(2)),
]


def _wide_rows(n: int):
    rng = np.random.default_rng(9)
    rows = []
    for i in range(n):
        name = None if rng.random() < 0.1 else bytes(f"item-{i % 37}", "ascii")
        cnt = None if rng.random() < 0.1 else int(rng.integers(-1000, 1000))
        price = None if rng.random() < 0.1 else int(rng.integers(0, 10**6))
        rows.append((i, name, cnt, price))
    return rows


def _engine_for(rows, v2: bool):
    eng = BTreeEngine()
    non_handle = _WIDE_COLUMNS[1:]
    for rid, name, cnt, price in rows:
        vals = [name, cnt, price]
        raw = (encode_row_v2(non_handle, vals) if v2
               else encode_row(non_handle, vals))
        put_committed(eng, record_key(TABLE_ID, rid), raw, 90, 100)
    return eng


@pytest.mark.parametrize("v2", [False, True], ids=["rowv1", "rowv2"])
def test_scan_serving_vectorized_identity_both_row_formats(v2, monkeypatch):
    rows = _wide_rows(200)
    ep = Endpoint(LocalEngine(_engine_for(rows, v2)), enable_device=False)
    lo = record_key(TABLE_ID, 0)
    hi = record_key(TABLE_ID, len(rows) + 1)
    req = lambda: CoprRequest(103, DagRequest(executors=[
        TableScan(TABLE_ID, _WIDE_COLUMNS)]), [(lo, hi)], 150)
    monkeypatch.setattr(datum_vec, "VEC_MIN_ROWS", 10**9)
    scalar = ep.handle_request(req()).data
    monkeypatch.setattr(datum_vec, "VEC_MIN_ROWS", 1)
    vectorized = ep.handle_request(req()).data
    assert scalar == vectorized


def test_rowv1_and_rowv2_serve_identical_bytes():
    rows = _wide_rows(150)
    dag = lambda: DagRequest(executors=[TableScan(TABLE_ID, _WIDE_COLUMNS)])
    lo, hi = record_key(TABLE_ID, 0), record_key(TABLE_ID, len(rows) + 1)
    outs = []
    for v2 in (False, True):
        ep = Endpoint(LocalEngine(_engine_for(rows, v2)), enable_device=False)
        outs.append(ep.handle_request(
            CoprRequest(103, dag(), [(lo, hi)], 150)).data)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# socket-level coalesced serving
# ---------------------------------------------------------------------------


def _numeric_engine(regions: int, rows_per: int):
    rng = np.random.default_rng(21)
    eng = BTreeEngine()
    non_handle = _WIDE_COLUMNS[1:]
    oracle = []
    for i in range(regions * rows_per):
        vals = [b"n%d" % (i % 13), int(rng.integers(0, 100)),
                int(rng.integers(0, 100000))]
        oracle.append(vals)
        put_committed(eng, record_key(TABLE_ID, i),
                      encode_row(non_handle, vals), 90, 100)
    return eng


def _agg_dag(cut: int) -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, _WIDE_COLUMNS),
        Selection([rpn_call("lt", col(2), const_int(cut))]),
        Aggregation([], [AggDescriptor("sum", col(2)),
                         AggDescriptor("count", None)]),
    ])


def _wire_reqs(regions: int, rows_per: int, clients: int):
    out = []
    for cut in (50, 80):
        for r in range(regions):
            lo = record_key(TABLE_ID, r * rows_per)
            hi = record_key(TABLE_ID, (r + 1) * rows_per)
            for _ in range(clients):
                out.append({
                    "dag": dag_to_wire(_agg_dag(cut)),
                    "ranges": [[lo, hi]],
                    "start_ts": 150,
                    "context": {"region_id": r + 1, "region_epoch": (1, 1),
                                "apply_index": 7},
                })
    return out


def _serve_concurrent(addr, reqs, n_conns: int):
    conns = [Client(*addr) for _ in range(n_conns)]
    results: list = [None] * len(reqs)
    errs: list = []

    def worker(ci):
        try:
            for i in range(ci, len(reqs), n_conns):
                results[i] = conns[ci].call("coprocessor", reqs[i], timeout=120.0)
        except Exception as exc:  # noqa: BLE001
            errs.append(exc)

    ts = [threading.Thread(target=worker, args=(ci,)) for ci in range(n_conns)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for c in conns:
        c.close()
    assert not errs, errs
    for r in results:
        assert isinstance(r, dict) and not r.get("error"), r
    return [r["data"] for r in results]


def test_socket_coalesced_matches_serial():
    """Multi-connection coalesced serving is byte-identical to per-request
    serving, requests ride dispatcher batches, and every wire stage lands in
    the histogram + the debug_wire_stages RPC."""
    regions, rows_per, clients = 4, 800, 2
    eng = _numeric_engine(regions, rows_per)
    reqs = _wire_reqs(regions, rows_per, clients)

    def run(continuous: bool):
        ep = Endpoint(LocalEngine(eng), enable_device=continuous,
                      block_rows=1 << 10)
        svc = KvService(Storage(engine=LocalEngine(eng)), ep)
        srv = Server(svc)
        srv.start()
        if continuous:
            ep.scheduler.start()
        try:
            _serve_concurrent(srv.addr, reqs, 4)  # warm + compile
            datas = _serve_concurrent(srv.addr, reqs, 4)
            stages = None
            if continuous:
                c = Client(*srv.addr)
                stages = c.call("debug_wire_stages", {})["stages"]
                c.close()
            return datas, stages
        finally:
            ep.scheduler.stop()
            srv.stop()

    coalesce = REGISTRY.counter("tikv_wire_coalesce_total", "")
    before = coalesce.get(outcome="batched")
    coal, stages = run(True)
    assert coalesce.get(outcome="batched") > before, \
        "no request was served out of a coalesced batch"
    serial, _ = run(False)
    assert coal == serial
    for stage in ("decode", "route", "execute", "encode"):
        assert stages.get(stage, {}).get("count", 0) > 0, (stage, stages)


# ---------------------------------------------------------------------------
# device-owner forwarding
# ---------------------------------------------------------------------------


def _owner_counter():
    return REGISTRY.counter("tikv_copr_owner_forward_total", "")


def test_forward_device_owner_one_hop_context():
    calls = []

    def send(store_id, method, req, timeout):
        calls.append((store_id, method, req))
        return {"data": b"OWNED", "from_device": True}

    rp = ReadPlane(send=send)
    rp.set_device_owners({7: 3})
    assert rp.device_owner_of(7) == 3
    before = _owner_counter().get(outcome="ok")
    r = rp.forward_device_owner(
        "coprocessor", {"ranges": [], "start_ts": 5,
                        "context": {"region_id": 7}}, 3)
    assert r == {"data": b"OWNED", "from_device": True}
    assert _owner_counter().get(outcome="ok") == before + 1
    sid, method, freq = calls[0]
    assert sid == 3 and method == "coprocessor"
    # the hop is loop-guarded and may serve on a non-leader owner
    assert freq["context"]["forwarded"] is True
    assert freq["context"]["stale_fallback"] is True


def test_forward_device_owner_remote_error_and_breaker():
    def send_err(store_id, method, req, timeout):
        return {"error": {"not_leader": {"region_id": 7}}}

    rp = ReadPlane(send=send_err)
    before = _owner_counter().get(outcome="remote_region_error")
    assert rp.forward_device_owner("coprocessor", {"context": {}}, 3) is None
    assert _owner_counter().get(outcome="remote_region_error") == before + 1

    def send_boom(store_id, method, req, timeout):
        raise ConnectionError("down")

    rp2 = ReadPlane(send=send_boom)
    assert rp2.forward_device_owner("coprocessor", {"context": {}}, 3) is None
    # consecutive failures trip the per-store breaker
    for _ in range(3):
        rp2.forward_device_owner("coprocessor", {"context": {}}, 3)
    b = _owner_counter().get(outcome="breaker_open")
    assert rp2.forward_device_owner("coprocessor", {"context": {}}, 3) is None
    assert _owner_counter().get(outcome="breaker_open") >= b


def test_owner_forward_service_gating():
    eng = _numeric_engine(1, 64)
    served = []

    def send(store_id, method, req, timeout):
        served.append(store_id)
        return {"data": b"REMOTE", "from_device": True}

    rp = ReadPlane(send=send)
    rp.store_id = 2
    rp.set_device_owners({1: 5})
    ep = Endpoint(LocalEngine(eng), enable_device=False)
    svc = KvService(Storage(engine=LocalEngine(eng)), ep, read_plane=rp)
    agg = dag_to_wire(_agg_dag(50))
    lo, hi = record_key(TABLE_ID, 0), record_key(TABLE_ID, 65)
    base = {"dag": agg, "ranges": [[lo, hi]], "start_ts": 150}

    # owner elsewhere + eligible plan -> forwarded
    r = svc.coprocessor(dict(base, context={"region_id": 1}))
    assert r == {"data": b"REMOTE", "from_device": True} and served == [5]

    # loop guard: a forwarded request NEVER re-forwards
    r = svc.coprocessor(dict(base, context={"region_id": 1, "forwarded": True}))
    assert r.get("data") != b"REMOTE" and served == [5]

    # owner is self -> local serving
    rp.set_device_owners({1: 2})
    svc.coprocessor(dict(base, context={"region_id": 1}))
    assert served == [5]

    # ineligible plan (pure scan) -> local serving
    rp.set_device_owners({1: 5})
    scan = dag_to_wire(DagRequest(executors=[TableScan(TABLE_ID, _WIDE_COLUMNS)]))
    svc.coprocessor({"dag": scan, "ranges": [[lo, hi]], "start_ts": 150,
                     "context": {"region_id": 1}})
    assert served == [5]

    # warm local device image -> local serving even with a remote owner
    ep2 = Endpoint(LocalEngine(eng), enable_device=True)
    svc2 = KvService(Storage(engine=LocalEngine(eng)), ep2, read_plane=rp)
    warm = dict(base, context={"region_id": 1, "region_epoch": (1, 1),
                               "apply_index": 7})
    svc2.coprocessor(warm)  # builds the local image
    if ep2.region_cache.has_warm_region(1):
        svc2.coprocessor(warm)
        assert served == [5]


def test_owner_forward_end_to_end_socket():
    """Store A (CPU-only) forwards a device-eligible DAG to warm owner B
    over a real socket; bytes match B's direct serving."""
    eng = _numeric_engine(1, 512)
    ep_b = Endpoint(LocalEngine(eng), enable_device=True, block_rows=1 << 10)
    svc_b = KvService(Storage(engine=LocalEngine(eng)), ep_b)
    srv_b = Server(svc_b)
    srv_b.start()
    try:
        req = {
            "dag": dag_to_wire(_agg_dag(60)),
            "ranges": [[record_key(TABLE_ID, 0), record_key(TABLE_ID, 513)]],
            "start_ts": 150,
            "context": {"region_id": 1, "region_epoch": (1, 1),
                        "apply_index": 7},
        }
        cb = Client(*srv_b.addr)
        direct = cb.call("coprocessor", req, timeout=120.0)
        cb.close()
        assert not direct.get("error")

        rp = ReadPlane(resolver=lambda sid: srv_b.addr if sid == 9 else None,
                       forward_timeout=120.0)
        rp.store_id = 2
        rp.set_device_owners({1: 9})
        ep_a = Endpoint(LocalEngine(eng), enable_device=False)
        svc_a = KvService(Storage(engine=LocalEngine(eng)), ep_a,
                          read_plane=rp)
        srv_a = Server(svc_a)
        srv_a.start()
        try:
            before = _owner_counter().get(outcome="ok")
            ca = Client(*srv_a.addr)
            via_a = ca.call("coprocessor", req, timeout=120.0)
            ca.close()
            assert not via_a.get("error")
            assert via_a["data"] == direct["data"]
            assert _owner_counter().get(outcome="ok") == before + 1
        finally:
            srv_a.stop()
            rp.close()
    finally:
        srv_b.stop()


# ---------------------------------------------------------------------------
# the inbound count, from the socket to the scheduler's lanes (ISSUE 30)
# ---------------------------------------------------------------------------

_LINGER = 0.5


def _dispatch_passes() -> tuple[float, float]:
    """(passes, riders) of the read scheduler's dispatcher, every release."""
    c = REGISTRY.counter("tikv_coprocessor_sched_dispatch_total")
    h = REGISTRY.histogram("tikv_coprocessor_sched_dispatch_riders",
                           buckets=(1, 2, 4, 8, 16, 32, 64))
    whys = ("drained", "deadline", "full", "stop")
    return (sum(c.get(why=w) for w in whys), sum(h.total(why=w) for w in whys))


@pytest.fixture(scope="module")
def lingering_store():
    """A store's wiring at test size: a ``Server`` and a read scheduler that
    lingers half a second, sharing one inbound count; two warm regions."""
    from tikv_tpu.copr.scheduler import SchedulerConfig
    from tikv_tpu.util.inbound import InboundReads

    regions, rows_per = 2, 800
    eng = _numeric_engine(regions, rows_per)
    ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=1 << 10,
                  sched_config=SchedulerConfig(max_wait_s=_LINGER))
    inbound = InboundReads()
    ep.scheduler.watch_inbound(inbound)
    svc = KvService(Storage(engine=LocalEngine(eng)), ep)
    srv = Server(svc, inbound=inbound)
    srv.start()
    ep.scheduler.start()
    reqs = _wire_reqs(regions, rows_per, 1)[:regions]  # one plan, each region
    # images and programs: each task alone, then both in one batch
    c = Client(*srv.addr)
    for r in reqs:
        assert not c.call("coprocessor", r, timeout=120.0).get("error")
    c.close()
    ep.handle_batch([svc._parse_copr_request(r) for r in reqs])
    try:
        yield srv, inbound, reqs
    finally:
        ep.scheduler.stop()
        srv.stop()


def _frame(req_id: int, req: dict) -> bytes:
    body = wire.dumps([req_id, "coprocessor", req])
    return len(body).to_bytes(4, "big") + body


def test_two_frames_back_to_back_ride_one_pass(lingering_store):
    """A session's query: both regions' tasks written to the socket at once.
    The second frame is counted before the first reaches the scheduler, so
    the first waits for it, and its arrival (not the half-second linger)
    releases ONE pass of two riders."""
    import time

    srv, inbound, reqs = lingering_store
    before = _dispatch_passes()
    sock = socket.create_connection(srv.addr)
    try:
        t0 = time.perf_counter()
        sock.sendall(_frame(1, reqs[0]) + _frame(2, reqs[1]))
        answers = dict(wire.loads(read_frame(sock)) for _ in range(2))
        took = time.perf_counter() - t0
    finally:
        sock.close()
    assert sorted(answers) == [1, 2]
    assert not any(a.get("error") for a in answers.values()), answers
    assert took < _LINGER / 2, f"waited out the linger: {took:.3f} s"
    passes, riders = (a - b for a, b in zip(_dispatch_passes(), before))
    assert (passes, riders) == (1, 2)
    assert inbound.pending() == 0 and inbound.low == 0


@pytest.mark.parametrize("gap_ms", [1, 2, 20])
def test_two_frames_a_moment_apart_ride_one_pass(lingering_store, gap_ms):
    """A client that writes a query's tasks one after the other: when the
    first is parked nothing else is inbound and nothing the store observes
    says that a second will be SENT.  A rider alone of its plan therefore
    keeps its linger, and the second's arrival, not the count, releases ONE
    pass of two: such a client pays what it always paid, never two lone
    serves."""
    import time

    srv, inbound, reqs = lingering_store
    before = _dispatch_passes()
    sock = socket.create_connection(srv.addr)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    try:
        t0 = time.perf_counter()
        sock.sendall(_frame(1, reqs[0]))
        time.sleep(gap_ms / 1000)
        sock.sendall(_frame(2, reqs[1]))
        answers = dict(wire.loads(read_frame(sock)) for _ in range(2))
        took = time.perf_counter() - t0
    finally:
        sock.close()
    assert sorted(answers) == [1, 2]
    assert not any(a.get("error") for a in answers.values()), answers
    assert took < _LINGER / 2, f"waited out the linger: {took:.3f} s"
    passes, riders = (a - b for a, b in zip(_dispatch_passes(), before))
    assert (passes, riders) == (1, 2)
    assert inbound.pending() == 0 and inbound.low == 0


def test_sixteen_connections_still_coalesce(lingering_store):
    """Under load somebody is nearly always on the way, so the linger does
    what it is for: passes carry more than a lone session's two tasks."""
    srv, inbound, reqs = lingering_store
    before = _dispatch_passes()
    datas = _serve_concurrent(srv.addr, reqs * 32, 16)
    assert len(set(datas[0::2])) == 1 and len(set(datas[1::2])) == 1
    passes, riders = (a - b for a, b in zip(_dispatch_passes(), before))
    assert riders == 64
    assert riders / passes > 2, f"{riders} riders in {passes} passes"
    assert inbound.pending() == 0 and inbound.low == 0


def test_connection_dropped_between_its_frames_owes_nothing(lingering_store):
    """One whole frame and the head of a second, then the peer is gone: the
    whole frame is served and counted down, the torn one never counted."""
    srv, inbound, reqs = lingering_store
    before = _dispatch_passes()
    sock = socket.create_connection(srv.addr)
    sock.sendall(_frame(1, reqs[0]) + _frame(2, reqs[1])[:9])
    rid, answer = wire.loads(read_frame(sock))
    sock.close()
    assert rid == 1 and not answer.get("error")
    # the server's thread for that connection ends on the torn frame
    c = Client(*srv.addr)
    assert not c.call("coprocessor", reqs[1], timeout=30.0).get("error")
    c.close()
    passes, riders = (a - b for a, b in zip(_dispatch_passes(), before))
    assert (passes, riders) == (2, 2)
    assert inbound.pending() == 0 and inbound.low == 0
