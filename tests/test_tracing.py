"""End-to-end distributed tracing plane (ISSUE 11, docs/tracing.md).

* span-tree mechanics: nesting, explicit pool handoff, cross-thread finish,
  head sampling + tail promotion, the rate-0 no-op fast path;
* wire propagation: one trace from the client frame through forwarded hops,
  with decode/route/execute/encode stage spans accounting for >=90% of the
  root;
* THE acceptance scenario: a coprocessor request to the WRONG store
  (device-owner hop) yields ONE trace with wire, ladder, queue, and device
  spans across two stores;
* chaos: a seeded Nemesis leader isolation mid-traffic yields ONE trace
  whose spans cover >=2 stores (forward rung + retry joined, never a fresh
  trace per hop);
* fan-in: every coalesced rider links to the shared device-dispatch span;
* write path: slow-log parity with latch/propose/apply phases + trace ids,
  and the raft propose->apply span finished by the apply callback;
* log<->trace correlation through util.logger + diagnostics.search_log;
* stages (ISSUE 26): leaf spans with wall and CPU totals, mirrored into the
  device profiler's trace, never nested, and a request's attributed time.
"""

import logging
import threading
import time

import pytest

from copr_fixtures import TABLE_ID as PRODUCT_TABLE  # noqa: F401 (path setup)
from tikv_tpu.copr.dag import (
    AggDescriptor,
    Aggregation,
    DagRequest,
    Selection,
    TableScan,
)
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rpn import call as rpn_call, col, const_int
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.pd.client import MockPd
from tikv_tpu.raft.cluster import Cluster
from tikv_tpu.raft.raftkv import RaftKv
from tikv_tpu.server.read_plane import ReadPlane
from tikv_tpu.server.server import Client, Server
from tikv_tpu.server.service import KvService
from tikv_tpu.sidecar.resolved_ts import ResolvedTsEndpoint
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_WRITE
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.storage import Storage
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util import trace
from tikv_tpu.util.chaos import Nemesis

FIRST_REGION_ID = 1
TABLE_ID = 81

COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.int64()),
    ColumnInfo(3, FieldType.int64()),
]


@pytest.fixture(autouse=True)
def _tracer_isolation():
    old_rate = trace.sample_rate()
    old_slow = trace.slow_threshold()
    trace.TRACER.reset()
    trace.set_sample_rate(1.0)
    trace.set_slow_threshold(0.3)
    yield
    trace.set_sample_rate(old_rate)
    trace.set_slow_threshold(old_slow)
    trace.TRACER.reset()


def _engine(n: int) -> BTreeEngine:
    eng = BTreeEngine()
    items = []
    for i in range(n):
        rk = record_key(TABLE_ID, i)
        val = encode_row(COLS[1:], [i % 50, i])
        items.append((Key.from_raw(rk).append_ts(20).encoded,
                      Write(WriteType.PUT, 10, short_value=val).to_bytes()))
    eng.bulk_load(CF_WRITE, items)
    return eng


def _agg_dag(cut: int) -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([rpn_call("lt", col(1), const_int(cut))]),
        Aggregation([], [AggDescriptor("sum", col(2)),
                         AggDescriptor("count", None)]),
    ])


def _wait_for(pred, timeout=5.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {msg}")


def _spans_named(t: dict, name: str) -> list:
    return [s for s in t["spans"] if s["name"] == name]


def _stage_names(t: dict) -> set:
    return {s["name"] for s in t["spans"] if s.get("stage")}


# ---------------------------------------------------------------------------
# span-tree mechanics
# ---------------------------------------------------------------------------

def test_span_nesting_ids_and_ring_commit():
    with trace.start_trace("root", kind="test") as root:
        tid = root.rec.trace_id
        with trace.span("child") as c1:
            assert c1.parent_id == root.span_id
            with trace.span("grandchild") as c2:
                assert c2.parent_id == c1.span_id
    t = trace.TRACER.get(tid)
    assert t is not None and t["sampled"] and not t["promoted"]
    names = [s["name"] for s in t["spans"]]
    assert names.count("root") == 1
    assert set(names) == {"root", "child", "grandchild"}
    # parentage is reconstructible (the timeline renders a tree)
    text = trace.timeline(t)
    assert "root" in text and "    " in text


def test_explicit_handoff_and_cross_thread_finish():
    with trace.start_trace("root") as root:
        tid = root.rec.trace_id
        ctx = trace.current_context()
        assert ctx["trace_id"] == tid and ctx["sampled"]

        done = threading.Event()

        def worker():
            # pool-boundary handoff: attach, then nest
            with trace.attach(ctx):
                with trace.span("worker.step"):
                    pass
            done.set()

        th = threading.Thread(target=worker)
        th.start()
        done.wait(5)
        th.join(5)
        # cross-thread finish of a begin() handle (the raft-callback shape)
        h = trace.begin("late.handle")
        fin = threading.Thread(target=h.finish)
        fin.start()
        fin.join(5)
        # dispatcher-side remote span lands in this trace without touching
        # the worker's current stack
        trace.remote_span(ctx, "remote.step", start=0.0, end=0.001, k="v")
    t = trace.TRACER.get(tid)
    names = {s["name"] for s in t["spans"]}
    assert {"worker.step", "late.handle", "remote.step"} <= names
    ws = _spans_named(t, "worker.step")[0]
    assert ws["parent_id"] == ctx["span_id"]


def test_sampling_off_is_noop_and_costs_nothing():
    trace.set_sample_rate(0.0)
    assert not trace.enabled()
    sp = trace.start_trace("x")
    assert sp is trace.NOOP and not sp
    with trace.span("y") as s:
        assert s is trace.NOOP
    assert trace.current_trace_id() is None
    snap = trace.snapshot()
    assert snap["recent"] == [] and snap["slow"] == [] and snap["live"] == 0


def test_head_drop_and_tail_promotion():
    class _FixedRng:
        def random(self):
            return 0.99  # always above the rate: head says DROP

    trace.TRACER._rng = _FixedRng()
    trace.set_sample_rate(0.5)
    # fast trace: head-dropped, not slow -> vanishes
    with trace.start_trace("fast") as sp:
        tid_fast = sp.rec.trace_id
        assert not sp.rec.sampled
    assert trace.TRACER.get(tid_fast) is None
    # slow trace: head-dropped but crosses the threshold -> PROMOTED
    trace.set_slow_threshold(0.0)
    with trace.start_trace("slow") as sp:
        tid_slow = sp.rec.trace_id
        with trace.span("inner"):
            pass
    t = trace.TRACER.get(tid_slow)
    assert t is not None and t["promoted"] and t["slow"] and not t["sampled"]
    assert {"slow", "inner"} <= {s["name"] for s in t["spans"]}
    snap = trace.snapshot()
    assert any(x["trace_id"] == tid_slow for x in snap["slow"])
    assert not any(x["trace_id"] == tid_slow for x in snap["recent"])


def test_promoted_trace_keeps_cross_thread_spans():
    """Tail promotion exists to keep the phases where an UNSAMPLED slow
    request actually spent its time — attach/remote_span must record into
    head-dropped live traces (regression: they used to gate on sampled,
    leaving promoted traces without their worker-side spans)."""
    class _FixedRng:
        def random(self):
            return 0.99  # head says DROP

    trace.TRACER._rng = _FixedRng()
    trace.set_sample_rate(0.5)
    trace.set_slow_threshold(0.0)  # everything promotes
    with trace.start_trace("slow.write") as root:
        assert not root.rec.sampled
        tid = root.rec.trace_id
        ctx = trace.current_context()
        assert ctx["sampled"] is False

        def worker():
            with trace.attach(ctx):
                with trace.span("txn.process_write"):
                    pass

        th = threading.Thread(target=worker)
        th.start()
        th.join(5)
        trace.remote_span(ctx, "sched.batched", start=0.0, end=0.001)
    t = trace.TRACER.get(tid)
    assert t is not None and t["promoted"]
    names = {s["name"] for s in t["spans"]}
    assert {"txn.process_write", "sched.batched"} <= names, names


def test_span_cap_truncates_not_balloons():
    with trace.start_trace("root") as root:
        tid = root.rec.trace_id
        for _ in range(trace.MAX_SPANS + 40):
            with trace.span("s"):
                pass
    t = trace.TRACER.get(tid)
    assert len(t["spans"]) <= trace.MAX_SPANS
    assert t["truncated"] >= 40


# ---------------------------------------------------------------------------
# wire propagation over real sockets
# ---------------------------------------------------------------------------

def test_rpc_stage_spans_cover_root():
    storage = Storage()
    svc = KvService(storage, Endpoint(storage.engine))
    srv = Server(svc)
    srv.start()
    c = Client(*srv.addr)
    try:
        c.call("kv_get", {"key": b"x", "version": 10, "context": {}})
    finally:
        c.close()
        srv.stop()
    _wait_for(lambda: trace.snapshot()["recent"], msg="rpc trace commit")
    t = trace.snapshot()["recent"][-1]
    root = [s for s in t["spans"]
            if s["parent_id"] is None and s["name"] == "rpc.kv_get"]
    assert root, "rpc root span missing"
    kids = [s for s in t["spans"] if s["parent_id"] == root[0]["span_id"]]
    stages = {s["name"] for s in kids}
    assert {"wire.decode", "wire.route", "wire.execute",
            "wire.encode"} <= stages
    covered = sum(s["duration_ms"] for s in kids)
    total = root[0]["duration_ms"]
    # the stages tile the root; on a sub-millisecond request a scheduler
    # hiccup between two lock acquisitions can exceed 10% of the total, so
    # accept either the ratio or a small absolute gap
    assert covered >= 0.9 * total or total - covered <= 1.5, \
        f"stage spans cover only {covered:.3f} of {total:.3f}ms"


def test_acceptance_owner_forward_one_trace_wire_ladder_queue_device():
    """THE acceptance scenario: a device-eligible DAG sent to the WRONG
    store hops to the device owner; ONE trace carries wire, ladder, queue,
    and device spans across both stores, and the root's direct children
    account for >=90% of it."""
    eng = _engine(1200)
    # store 2: device owner, continuous scheduler (queue lanes)
    ep_b = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256)
    rp_b = ReadPlane()
    rp_b.store_id = 2
    svc_b = KvService(Storage(engine=LocalEngine(eng)), ep_b, read_plane=rp_b)
    srv_b = Server(svc_b)
    srv_b.start()
    ep_b.scheduler.start()
    # store 1: no device; PD named store 2 the warm owner of region 1
    rp_a = ReadPlane(resolver=lambda sid: srv_b.addr if sid == 2 else None)
    rp_a.store_id = 1
    rp_a.set_device_owners({FIRST_REGION_ID: 2})
    ep_a = Endpoint(LocalEngine(eng), enable_device=False)
    svc_a = KvService(Storage(engine=LocalEngine(eng)), ep_a, read_plane=rp_a)
    srv_a = Server(svc_a)
    srv_a.start()

    from tikv_tpu.copr.dag_wire import dag_to_wire

    lo, hi = record_key(TABLE_ID, 0), record_key(TABLE_ID, 1200)
    req = {"dag": dag_to_wire(_agg_dag(30)), "ranges": [[lo, hi]],
           "start_ts": 100,
           "context": {"region_id": FIRST_REGION_ID,
                       "region_epoch": (1, 1), "apply_index": 7}}
    c = Client(*srv_a.addr)
    try:
        r = c.call("coprocessor", req, timeout=120.0)
        assert not r.get("error") and r["from_device"], r
    finally:
        c.close()
        srv_a.stop()
        ep_b.scheduler.stop()
        srv_b.stop()
        rp_a.close()

    def traced():
        return [t for t in trace.snapshot(limit=50)["recent"]
                if _spans_named(t, "ladder.owner_forward")]

    _wait_for(lambda: traced(), msg="owner-forward trace commit")
    ts = traced()
    assert len(ts) == 1, "the hop must JOIN the trace, not mint a new one"
    t = ts[0]
    names = [s["name"] for s in t["spans"]]
    # wire spans from BOTH stores in the one trace
    assert names.count("rpc.coprocessor") == 2
    stores = {s["tags"].get("store") for s in t["spans"]
              if s["name"] == "rpc.coprocessor"}
    assert stores == {1, 2}, f"expected both stores' rpc spans, got {stores}"
    # ladder + queue + device spans
    fwd = _spans_named(t, "ladder.owner_forward")[0]
    assert fwd["tags"]["outcome"] == "ok" and fwd["tags"]["target_store"] == 2
    assert _spans_named(t, "sched.queue"), "queue-lane span missing"
    assert _spans_named(t, "device.run"), "device span missing"
    assert _spans_named(t, "copr.handle")[0]["tags"]["from_device"] is True
    # >=90% of the root accounted by its direct children
    root = [s for s in t["spans"] if s["parent_id"] is None
            and s["name"] == "rpc.coprocessor"]
    assert len(root) == 1
    kids = [s for s in t["spans"] if s["parent_id"] == root[0]["span_id"]]
    cov = sum(s["duration_ms"] for s in kids) / root[0]["duration_ms"]
    assert cov >= 0.9, f"child spans cover only {cov:.0%} of the root"


# ---------------------------------------------------------------------------
# chaos: trace propagation through a seeded leader isolation
# ---------------------------------------------------------------------------

def _commit_kv(pd, storage, ctx, key, value):
    from tikv_tpu.storage.txn.commands import Commit, Prewrite
    from tikv_tpu.storage.txn_types import Mutation

    ts = pd.get_tso()
    storage.sched_txn_command(
        Prewrite([Mutation.put(Key.from_raw(key), value)], key, ts), ctx)
    cts = pd.get_tso()
    storage.sched_txn_command(Commit([Key.from_raw(key)], ts, cts), ctx)
    return cts


def test_chaos_leader_isolation_one_trace_spans_two_stores():
    """Seeded Nemesis isolates the leader mid-traffic: the client keeps ONE
    trace open across its retries — the pre-isolation forwarded read joins
    the leader's spans, the mid-isolation retry degrades to a follower
    stale serve — and every hop's spans land in that one trace (never a
    fresh trace per hop)."""
    pd = MockPd()
    c = Cluster(3, pd=pd)
    c.run()
    rts = ResolvedTsEndpoint(pd)
    for s in c.stores.values():
        rts.attach_store(s)
    leader = c.wait_leader(FIRST_REGION_ID)
    leader_sid = leader.store.store_id
    storage = Storage(engine=c.raftkv(leader_sid))
    _commit_kv(pd, storage, {"region_id": FIRST_REGION_ID}, b"rk", b"rv")
    w = rts.advance_all()[FIRST_REGION_ID]

    isolated: set = set()
    svcs: dict = {}

    def rpc_send(sid, method, req, timeout):
        # the injected wire: a partitioned store is unreachable, a healthy
        # one serves through the same trace-joining RPC shape server.py uses
        if sid in isolated:
            raise ConnectionError(f"store {sid} partitioned")
        return call_store(sid, method, req)

    def call_store(sid, method, req):
        root = trace.start_trace(f"rpc.{method}",
                                 ctx=(req.get("context") or None),
                                 method=method, store=sid)
        try:
            with root.active():
                return svcs[sid].dispatch(method, req)
        finally:
            root.finish()

    for sid, st in c.stores.items():
        plane = ReadPlane(store=st, resolved_ts=rts, send=rpc_send)
        kv = RaftKv(st, pump=c.process, resolved_ts=rts)
        svcs[sid] = KvService(Storage(engine=kv), raft_router=st,
                              resolved_ts=rts, read_plane=plane)

    fol = next(s for s in c.stores if s != leader_sid)
    nem = Nemesis(c, seed=20260804)
    client_root = trace.start_trace("client.read", store="client")
    tid = client_root.rec.trace_id
    try:
        with client_root.active():
            ctx = {"region_id": FIRST_REGION_ID, "stale_fallback": True}
            trace.inject(ctx)
            # pre-isolation: fresh read on the follower forwards one hop
            r = call_store(fol, "kv_get",
                           {"key": b"rk", "version": w, "context": dict(ctx)})
            assert r.get("error") is None and r["value"] == b"rv", r
            # mid-traffic leader isolation (seeded, deterministic)
            isolated.add(leader_sid)
            nem.isolate(leader_sid)
            for _ in range(5):
                c.tick()
            # the retry re-injects the SAME trace: forward fails, the
            # ladder degrades to a follower stale serve at the watermark
            r = call_store(fol, "kv_get",
                           {"key": b"rk", "version": w, "context": dict(ctx)})
            assert r.get("error") is None and r["value"] == b"rv", r
    finally:
        client_root.finish()
        isolated.clear()
        nem.heal()
        nem.close()

    t = trace.TRACER.get(tid)
    assert t is not None, "client trace never committed"
    # ONE trace, spans from >=2 stores
    stores = {s["tags"].get("store") for s in t["spans"]
              if "store" in s["tags"]} - {"client"}
    assert len(stores) >= 2, f"trace covers only stores {stores}"
    assert leader_sid in stores and fol in stores
    # forward rung (pre-isolation, served) + stale rung (mid-isolation)
    fwd = _spans_named(t, "ladder.forward")
    assert any(s["tags"].get("outcome") == "ok" for s in fwd)
    stale = _spans_named(t, "ladder.stale_serve")
    assert any(s["tags"].get("outcome") == "served" for s in stale)
    # never a fresh trace per hop: every rpc span of the exercise is HERE
    assert len(_spans_named(t, "rpc.kv_get")) >= 3  # 2 client calls + 1 hop
    others = [x for x in trace.snapshot(limit=50)["recent"]
              if x["trace_id"] != tid and _spans_named(x, "rpc.kv_get")]
    assert not others, "a hop minted its own trace instead of joining"


# ---------------------------------------------------------------------------
# fan-in: coalesced riders link to the shared dispatch span
# ---------------------------------------------------------------------------

def test_batch_fanin_links_every_rider():
    eng = _engine(2400)
    dev = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256)
    rows_per = 600

    def region_req(r, ts=101):
        lo = record_key(TABLE_ID, r * rows_per)
        hi = record_key(TABLE_ID, (r + 1) * rows_per)
        return CoprRequest(103, _agg_dag(40), [(lo, hi)], ts,
                           context={"region_id": r + 1,
                                    "region_epoch": (1, 1), "apply_index": 7})

    # warm: fill the region images + compile outside the traced window; the
    # riders read above the images' timestamp, so each hit walks CF_LOCK
    dev.handle_batch([region_req(r, ts=100) for r in range(4)])
    dev.scheduler.start()
    try:
        barrier = threading.Barrier(4)
        tids: list = [None] * 4
        errs: list = []

        def worker(i):
            try:
                root = trace.start_trace(f"client.{i}", store=f"client{i}")
                tids[i] = root.rec.trace_id
                with root.active():
                    barrier.wait(5)
                    dev.scheduler.execute(region_req(i))
                root.finish()
            except Exception as exc:  # noqa: BLE001
                errs.append(exc)

        ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(30)
        assert not errs, errs
    finally:
        dev.scheduler.stop()

    recent = trace.snapshot(limit=50)["recent"]
    dispatches = [t for t in recent
                  if _spans_named(t, "sched.device_dispatch")]
    assert dispatches, "no shared device-dispatch trace recorded"
    # riders that were actually served out of a shared batch
    linked = 0
    for tid in tids:
        t = trace.TRACER.get(tid)
        assert t is not None
        queue = _spans_named(t, "sched.queue")
        assert queue, "rider lost its queue-lane span"
        if queue[0]["tags"].get("outcome") != "batched":
            continue  # underfull/direct riders carry no link — honest
        linked += 1
        ref = queue[0]["tags"]["batched_into"]
        batched = _spans_named(t, "sched.batched")
        assert batched and batched[0]["tags"]["batched_into"] == ref
        # the link resolves to a real dispatch trace naming this rider
        dtid, dsid = ref.split(":")
        dt = next((x for x in dispatches if x["trace_id"] == dtid), None)
        assert dt is not None, "batched_into names an unknown dispatch trace"
        dsp = _spans_named(dt, "sched.device_dispatch")[0]
        assert dsp["span_id"] == dsid
        assert tid in dsp["tags"]["participants"]
    assert linked >= 2, "expected at least one shared batch among 4 riders"
    # device spans nest under the dispatch trace (launch + pull)
    dt = next(x for x in dispatches
              if _spans_named(x, "sched.device_dispatch")[0]["tags"]
              .get("outcome") == "ok")
    assert _spans_named(dt, "device.launch") and _spans_named(dt, "device.pull")
    # the stages of a warm batched task are reachable from every rider's
    # trace: its slot's in its own tree, the dispatch's through the link
    for tid in tids:
        t = trace.TRACER.get(tid)
        queue = _spans_named(t, "sched.queue")[0]
        if queue["tags"].get("outcome") != "batched":
            continue
        linked_t = next(x for x in dispatches if x["trace_id"]
                        == queue["tags"]["batched_into"].split(":")[0])
        reachable = _stage_names(t) | _stage_names(linked_t)
        assert {"sched.wait", "sched.handoff", "cache.lookup",
                "cache.lock_check", "device.launch", "device.pull",
                "device.finalize", "copr.encode"} <= reachable, reachable
        # the dispatch's stages are the rider's time too
        assert t["shared_ms"] > 0 and t["attributed_ms"] > t["shared_ms"]


# ---------------------------------------------------------------------------
# write path: slow-log parity + propose->apply span
# ---------------------------------------------------------------------------

def test_txn_slow_log_records_phases_and_trace_id():
    storage = Storage()
    storage.scheduler.slow_log.threshold_s = 0.0  # record every command
    with trace.start_trace("client.write") as root:
        tid = root.rec.trace_id
        _commit_kv(MockPd(), storage, None, b"wk", b"wv")
    entries = storage.scheduler.slow_log.tail(10)
    tags = [e["tag"] for e in entries]
    assert "txn Prewrite" in tags and "txn Commit" in tags
    for e in entries:
        assert e["trace_id"] == tid
        for k in ("latch_wait_ms", "process_ms", "propose_apply_ms",
                  "total_ms", "group_size", "status"):
            assert k in e, f"{k} missing from write slow-log entry"
        assert e["status"] == "done"
    # the worker-side spans landed in the submitting request's trace
    t = trace.TRACER.get(tid)
    names = {s["name"] for s in t["spans"]}
    assert {"txn.latch_wait", "txn.process_write"} <= names


def test_raft_propose_apply_span_finishes_via_callback():
    pd = MockPd()
    c = Cluster(1, pd=pd)
    c.run()
    try:
        leader = c.wait_leader(FIRST_REGION_ID)
        storage = Storage(engine=c.raftkv(leader.store.store_id))
        with trace.start_trace("client.write") as root:
            tid = root.rec.trace_id
            _commit_kv(pd, storage, {"region_id": FIRST_REGION_ID},
                       b"rk2", b"rv2")
    finally:
        pass  # in-memory Cluster needs no teardown (no threads of its own)
    t = trace.TRACER.get(tid)
    spans = _spans_named(t, "raft.propose_apply")
    assert spans, "propose->apply span missing from the write trace"
    for s in spans:
        assert s["duration_ms"] >= 0 and "error" not in s["tags"]
        assert s["tags"]["region"] == FIRST_REGION_ID


def test_copr_slow_log_gains_trace_ids():
    eng = _engine(600)
    ep = Endpoint(LocalEngine(eng), enable_device=False)
    ep.slow_log.threshold_s = 0.0
    lo, hi = record_key(TABLE_ID, 0), record_key(TABLE_ID, 600)
    with trace.start_trace("client.copr") as root:
        tid = root.rec.trace_id
        ep.handle_request(CoprRequest(103, _agg_dag(25), [(lo, hi)], 100,
                                      context={"region_id": 1}))
    entry = ep.slow_log.tail(1)[0]
    assert entry["trace_id"] == tid


# ---------------------------------------------------------------------------
# log<->trace correlation
# ---------------------------------------------------------------------------

def test_logger_attaches_trace_id_and_search_log_pivots(tmp_path):
    from tikv_tpu.server.diagnostics import Diagnostics
    from tikv_tpu.util.logger import _Formatter, get_logger

    log_path = tmp_path / "store.log"
    handler = logging.FileHandler(log_path)
    handler.setFormatter(_Formatter())
    pylog = logging.getLogger("tikv_tpu.tracetest")
    pylog.addHandler(handler)
    pylog.setLevel(logging.INFO)
    try:
        log = get_logger("tracetest")
        with trace.start_trace("client.op") as root:
            tid = root.rec.trace_id
            log.info("applying delta", region=7)
        log.info("outside any span", region=8)
    finally:
        handler.close()
        pylog.removeHandler(handler)
    text = log_path.read_text()
    assert f"[trace_id={tid}]" in text
    # exactly the in-span line carries the id; search_log pivots on it
    hits = Diagnostics(log_path=str(log_path)).search_log(patterns=[tid])
    assert len(hits) == 1 and "applying delta" in hits[0]["message"]
    assert "region=7" in hits[0]["message"]


# ---------------------------------------------------------------------------
# ops surfaces: RPC, HTTP, online config
# ---------------------------------------------------------------------------

def test_debug_traces_rpc_and_status_route_and_online_rate():
    import json
    import urllib.request

    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.util.config import ConfigController, TikvConfig, TraceConfig

    storage = Storage()
    svc = KvService(storage, Endpoint(storage.engine))
    srv = Server(svc)
    srv.start()
    cl = Client(*srv.addr)
    try:
        cl.call("kv_get", {"key": b"k", "version": 5, "context": {}})
        _wait_for(lambda: trace.snapshot()["recent"], msg="trace commit")
        # RPC: list then show
        snap = cl.call("debug_traces", {"limit": 5})
        assert snap["sample_rate"] == 1.0 and snap["recent"]
        tid = snap["recent"][-1]["trace_id"]
        one = cl.call("debug_traces", {"trace_id": tid})
        assert one["trace"]["trace_id"] == tid
        assert "rpc.kv_get" in one["timeline"]
        missing = cl.call("debug_traces", {"trace_id": "nope"})
        assert missing.get("error")
    finally:
        cl.close()
        srv.stop()

    # HTTP: timeline text, JSON form, one-trace form + the online rate knob
    controller = ConfigController(TikvConfig(
        trace=TraceConfig(sample_rate=trace.sample_rate(),
                          slow_threshold_s=trace.slow_threshold())))
    controller.register(
        "trace",
        lambda changed: (
            trace.set_sample_rate(changed["sample_rate"])
            if "sample_rate" in changed else None,
            trace.set_slow_threshold(changed["slow_threshold_s"])
            if "slow_threshold_s" in changed else None,
        ),
    )
    ss = StatusServer(controller=controller)
    ss.start()
    base = f"http://{ss.addr[0]}:{ss.addr[1]}"
    try:
        text = urllib.request.urlopen(base + "/debug/traces").read().decode()
        assert "sample_rate=1.0" in text and "rpc.kv_get" in text
        j = json.loads(urllib.request.urlopen(
            base + "/debug/traces?format=json&limit=3").read())
        assert j["recent"] and j["sample_rate"] == 1.0
        one = urllib.request.urlopen(
            base + f"/debug/traces?trace_id={tid}").read().decode()
        assert "rpc.kv_get" in one
        # the ctl.py `trace set-sample-rate` path: POST /config trace.*
        req = urllib.request.Request(
            base + "/config",
            data=json.dumps({"trace.sample_rate": 0.25}).encode(),
            method="POST")
        diff = json.loads(urllib.request.urlopen(req).read())
        assert diff == {"trace": {"sample_rate": 0.25}}
        assert trace.sample_rate() == 0.25
        # validation rejects a bad rate and changes nothing
        req = urllib.request.Request(
            base + "/config",
            data=json.dumps({"trace.sample_rate": 7}).encode(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(req)
        assert trace.sample_rate() == 0.25
    finally:
        ss.stop()


def test_trace_metrics_series_move():
    from tikv_tpu.util.metrics import REGISTRY

    c = REGISTRY.counter("tikv_trace_total")
    before = c.get(outcome="sampled")
    with trace.start_trace("m"):
        pass
    assert c.get(outcome="sampled") == before + 1
    # the rings' sizes are computed when the registry renders
    assert 'tikv_trace_ring_traces{ring="recent"} 1' in REGISTRY.render()
    g = REGISTRY.gauge("tikv_trace_ring_traces")
    assert g.get(ring="recent") >= 1


# ---------------------------------------------------------------------------
# stages: leaf spans with totals and a mirror (ISSUE 26)
# ---------------------------------------------------------------------------

STAGE_SERIES = ("tikv_trace_stage_seconds", "tikv_trace_stage_cpu_seconds_total",
                "tikv_trace_request_seconds_total",
                "tikv_trace_request_attributed_seconds_total")


def _stage_lines() -> list:
    from tikv_tpu.util.metrics import REGISTRY

    return [ln for ln in REGISTRY.render().splitlines()
            if ln.startswith(STAGE_SERIES)]


class _RecordingMirror:
    """Stands in for jax.profiler.TraceAnnotation: notes what is entered."""

    def __init__(self):
        self.entered: list = []
        self.open: dict = {}  # thread id -> names open there

    def __call__(self, name):
        outer = self

        class _Ann:
            def __enter__(self):
                stack = outer.open.setdefault(threading.get_ident(), [])
                assert not stack, f"{name} mirrored inside {stack}"
                stack.append(name)
                outer.entered.append(name)

            def __exit__(self, *exc):
                outer.open[threading.get_ident()].remove(name)

        return _Ann()


@pytest.fixture
def mirror():
    old = trace.TRACER._mirror
    m = _RecordingMirror()
    trace.set_mirror(m)
    yield m
    trace.set_mirror(old)


class _Served:
    """A device endpoint behind a socket server over two warm regions; a
    round sends both regions' tasks together, as a TiDB session does."""

    ROWS = 60000  # enough work a task that the stages, not their seams, are the time

    def __init__(self):
        from tikv_tpu.copr.dag_wire import dag_to_wire

        eng = _engine(2 * self.ROWS)
        self.ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=16384)
        svc = KvService(Storage(engine=LocalEngine(eng)), self.ep)
        self.srv = Server(svc)
        self.srv.start()
        self.ep.scheduler.start()
        self.clients = [Client(*self.srv.addr) for _ in range(2)]
        self.dag = dag_to_wire(_agg_dag(40))
        self.ts = 100
        for _ in range(3):  # fill both images, compile both rungs' programs
            self.round()

    def request(self, r: int, ts: int) -> dict:
        lo = record_key(TABLE_ID, r * self.ROWS)
        hi = record_key(TABLE_ID, (r + 1) * self.ROWS)
        return {"dag": self.dag, "ranges": [[lo, hi]], "start_ts": ts,
                "context": {"region_id": r + 1, "region_epoch": (1, 1),
                            "apply_index": 7}}

    def round(self) -> list:
        """One query: a fresh timestamp, one task a region, sent together."""
        self.ts += 1
        barrier = threading.Barrier(2)
        out: list = [None, None]

        def task(i):
            barrier.wait(5)
            out[i] = self.clients[i].call(
                "coprocessor", self.request(i, self.ts), timeout=120.0)

        ts = [threading.Thread(target=task, args=(i,)) for i in range(2)]
        for th in ts:
            th.start()
        for th in ts:
            th.join(120)
        assert all(r is not None and not r.get("error") and r["from_device"]
                   for r in out), out
        return out

    def batched_round(self) -> list:
        """A round whose two tasks rode one batch; returns their traces."""
        for _ in range(20):
            trace.TRACER.reset()
            self.round()
            _wait_for(lambda: len(_rpc_traces()) == 2, msg="both rpc traces")
            ts = _rpc_traces()
            if all(_spans_named(t, "sched.batched") for t in ts):
                return ts
        raise AssertionError("the two tasks never rode one batch")

    def close(self):
        for c in self.clients:
            c.close()
        self.ep.scheduler.stop()
        self.srv.stop()


def _rpc_traces() -> list:
    return [t for t in trace.snapshot(limit=50)["recent"]
            if _spans_named(t, "rpc.coprocessor")]


@pytest.fixture(scope="module")
def served():
    old = trace.sample_rate()
    trace.set_sample_rate(1.0)
    s = _Served()
    yield s
    s.close()
    trace.set_sample_rate(old)


def test_stage_nests_moves_totals_and_is_noop_when_off():
    from tikv_tpu.util.metrics import REGISTRY

    wall = REGISTRY.histogram("tikv_trace_stage_seconds")
    cpu = REGISTRY.counter("tikv_trace_stage_cpu_seconds_total")
    n0, s0 = wall.count(stage="t.outer"), wall.total(stage="t.outer")
    with trace.start_trace("root") as root:
        tid = root.rec.trace_id
        with trace.span("container") as c:
            with trace.stage("t.outer", k=1) as st:
                x = sum(i * i for i in range(20000))  # CPU inside the stage
                st.tag(outcome="hit")
    assert x
    t = trace.TRACER.get(tid)
    sp = _spans_named(t, "t.outer")
    assert len(sp) == 1 and sp[0]["stage"] is True
    assert sp[0]["parent_id"] == c.span_id
    assert sp[0]["tags"] == {"k": 1, "outcome": "hit"}
    assert "stage" not in _spans_named(t, "container")[0]
    assert wall.count(stage="t.outer") == n0 + 1
    dt = wall.total(stage="t.outer") - s0
    assert abs(dt - sp[0]["duration_ms"] / 1e3) < 1e-4
    assert 0 < cpu.get(stage="t.outer") <= dt * 1.5 + 1e-3
    assert abs(t["attributed_ms"] - sp[0]["duration_ms"]) < 0.01
    # with no current span: totals still move, no span to hold
    with trace.stage("t.outer"):
        pass
    assert wall.count(stage="t.outer") == n0 + 2
    # off: the shared no-op on one branch, and nothing moves
    trace.set_sample_rate(0.0)
    before = _stage_lines()
    st = trace.stage("t.outer")
    assert st is trace.NOOP
    with st:
        pass
    assert _stage_lines() == before


def test_stage_inside_stage_suspends_the_outer_one(mirror):
    with trace.start_trace("root") as root:
        tid = root.rec.trace_id
        with trace.stage("t.lookup") as outer:
            time.sleep(0.002)
            with trace.stage("t.lock_check"):
                time.sleep(0.004)
            outer.tag(outcome="hit")
    t = trace.TRACER.get(tid)
    segs = _spans_named(t, "t.lookup")
    inner = _spans_named(t, "t.lock_check")
    assert len(segs) == 2 and len(inner) == 1
    # siblings that tile: no stage is another's parent, none overlaps
    assert {s["parent_id"] for s in segs + inner} == {root.span_id}
    assert segs[1]["tags"]["outcome"] == "hit"
    assert sum(s["duration_ms"] for s in segs) < inner[0]["duration_ms"]
    assert outer.seconds * 1e3 == pytest.approx(
        sum(s["duration_ms"] for s in segs), abs=0.01)
    # the mirror never saw one annotation inside another (it asserts)
    assert mirror.entered == ["t.lookup", "t.lock_check", "t.lookup"]


def test_recorded_stage_and_shared_attribution():
    with trace.start_trace("rpc.x", method="x") as rider:
        ctx = trace.current_context()
        t0 = time.perf_counter()
        rider.record("wire.route", t0 - 0.003, t0, stage=True)
        trace.remote_span(ctx, "sched.wait", start=t0 - 0.002, end=t0,
                          stage=True, lane="normal")

        def dispatcher():
            # stages closed under shared() count for the waiting rider
            # whichever trace they land in, but once
            with trace.shared([ctx, ctx, None]):
                with trace.stage("t.dispatch"):
                    time.sleep(0.003)
                with trace.attach(ctx), trace.stage("t.slot"):
                    time.sleep(0.002)

        th = threading.Thread(target=dispatcher)
        th.start()
        th.join(5)
    t = trace.TRACER.get(rider.rec.trace_id)
    assert _stage_names(t) == {"wire.route", "sched.wait", "t.slot"}
    assert t["shared_ms"] == pytest.approx(3.0, abs=2.0) and t["shared_ms"] >= 3.0
    own = sum(s["duration_ms"] for s in t["spans"] if s.get("stage"))
    assert t["attributed_ms"] == pytest.approx(own + t["shared_ms"], abs=0.01)
    from tikv_tpu.util.metrics import REGISTRY

    assert REGISTRY.counter(
        "tikv_trace_request_attributed_seconds_total").get(method="x") > 0


def test_batch_stage_has_no_span_and_a_handed_back_rider_stops_sharing():
    """A batch stage is every rider's shared time and no rider's span, also
    where it resumes inside an attached trace; a rider handed back to its
    own thread (unshare) gets none of the batch's later stages."""
    from tikv_tpu.util.metrics import REGISTRY

    wall = REGISTRY.histogram("tikv_trace_stage_seconds")
    riders = [trace.start_trace("rpc.x", method="x") for _ in range(2)]
    ctxs = [r.context for r in riders]
    batch = []  # the batch stage's wall total before the unshare, and after

    def dispatcher():
        w0 = wall.total(stage="t.batch")
        with trace.shared(ctxs):
            with trace.batch_stage("t.batch"):
                time.sleep(0.002)
                with trace.attach(ctxs[0]):
                    with trace.stage("t.slot"):
                        time.sleep(0.002)
                    time.sleep(0.002)  # the batch stage, resumed
            batch.append(wall.total(stage="t.batch") - w0)
            trace.unshare(ctxs[1])
            with trace.batch_stage("t.batch"):
                time.sleep(0.002)
            batch.append(wall.total(stage="t.batch") - w0)

    th = threading.Thread(target=dispatcher)
    th.start()
    th.join(5)
    assert not th.is_alive()
    for r in riders:
        r.finish()
    first, second = (trace.TRACER.get(r.rec.trace_id) for r in riders)
    assert not _spans_named(first, "t.batch")
    assert not _spans_named(second, "t.batch")
    slot = _spans_named(first, "t.slot")
    assert len(slot) == 1 and not _spans_named(second, "t.slot")
    # the first rider waited for every batch segment (its own slot is its
    # span); the second for the segments before it was handed back and for
    # the other rider's slot
    assert first["shared_ms"] == pytest.approx(batch[1] * 1e3, abs=0.002)
    assert second["shared_ms"] == pytest.approx(
        batch[0] * 1e3 + slot[0]["duration_ms"], abs=0.002)
    assert batch[1] - batch[0] >= 0.002


def test_a_suspended_stage_and_the_inner_one_meet_at_one_instant():
    """No time falls between a stage and the one that suspends it, nor
    between that one's end and the outer stage's next segment: a clock that
    counts both as activities never falls back to its base between them."""
    clock = trace.WaitClock(3, {"t.outer": 1, "t.inner": 2}.get, 0)
    trace.bind_clock(clock)
    try:
        with trace.stage("t.outer"):
            before = clock.read(time.perf_counter())
            with trace.stage("t.inner"):
                time.sleep(0.001)
            after = clock.read(time.perf_counter())
    finally:
        trace.unbind_clock()
    assert after[0] == before[0]
    assert after[2] - before[2] >= 0.001


def test_served_request_mirrors_stages_not_containers(served, mirror):
    ts = served.batched_round()
    names = set(mirror.entered)
    assert {"cache.lookup", "cache.lock_check", "device.launch",
            "device.pull", "device.finalize", "copr.encode", "wire.encode",
            "wire.send"} <= names, names
    containers = {"wire.execute", "copr.handle", "sched.queue",
                  "sched.device_dispatch", "sched.batched", "device.run",
                  "sched.wait", "wire.route", "wire.decode"}
    assert not names & containers and not any(
        n.startswith("rpc.") for n in names), names
    # over the served request no stage is a stage's child, and the stages
    # one thread ran do not overlap (the mirror asserted the latter live)
    for t in ts + [x for x in trace.snapshot(limit=50)["recent"]
                   if _spans_named(x, "sched.device_dispatch")]:
        stage_ids = {s["span_id"] for s in t["spans"] if s.get("stage")}
        assert stage_ids
        assert not any(s["parent_id"] in stage_ids for s in t["spans"])


def test_served_batch_attribution_covers_execute(served):
    """On the CPU backend a warm batched task's stages account for at least
    90% of its ``wire.execute`` span, and of the whole request; on a task of
    a few milliseconds the seams between some thirty stages can exceed a
    tenth, so a small absolute remainder passes too (as in
    test_rpc_stage_spans_cover_root).  A thread descheduled between two
    stages only ever lowers the share, so the best of a few rounds is what
    the instrumentation covers."""
    worst = None
    for _ in range(8):
        gaps = []  # (share unattributed, ms unattributed)
        for t in served.batched_round():
            execute = _spans_named(t, "wire.execute")[0]
            lo = execute["start"]
            hi = lo + execute["duration_ms"] / 1e3
            own = sum(s["duration_ms"] for s in t["spans"] if s.get("stage")
                      and lo - 1e-4 <= s["start"] <= hi)
            for part, whole in ((own + t["shared_ms"], execute["duration_ms"]),
                                # the tracer's own sum, over the whole request
                                (t["attributed_ms"], t["duration_ms"])):
                gaps.append((1.0 - part / whole, whole - part))
        worst = max(gaps)
        if worst[0] <= 0.1 or worst[1] <= 1.0:
            return
    raise AssertionError((worst, trace.timeline(t)))


def test_warm_hit_stage_span_budget(served):
    for t in served.batched_round():
        n = sum(1 for s in t["spans"] if s.get("stage"))
        assert 8 <= n <= 24, trace.timeline(t)
        assert not t["truncated"]


def test_rate_zero_served_request_moves_no_stage_series(served):
    trace.set_sample_rate(0.0)
    before = _stage_lines()
    served.round()
    assert _stage_lines() == before
    assert trace.snapshot()["live"] == 0


def test_profiler_trace_names_the_stages(served, tmp_path):
    """What benchmark/trace.py blames idle gaps on: with a profiler session
    on, the host plane of the .xplane.pb holds the program's stages."""
    import glob

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        served.round()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    names = {ev.name
             for plane in ProfileData.from_file(found[-1]).planes
             if not plane.name.startswith("/device:")
             for line in plane.lines for ev in line.events}
    assert {"cache.lock_check", "cache.lookup", "device.launch",
            "device.pull"} <= names, sorted(names)[:60]
    assert "wire.execute" not in names and "sched.queue" not in names


def test_stage_cost_is_bounded():
    """100,000 stages with no profiler session: a stage is a span, two clock
    reads, a thread_time pair, two registry updates and the mirror's enter
    and exit.  Held as a ratio against an empty ``with`` block, so that a
    slow host moves both sides (measured here: 6-8 us against 0.05-0.35 us;
    the limit of 10 us holds on an idle core)."""
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with trace.NOOP:
            pass
    empty = (time.perf_counter() - t0) / n
    with trace.start_trace("root"):
        with trace.span("container"):
            t0 = time.perf_counter()
            for _ in range(n):
                with trace.stage("t.cost"):
                    pass
            each = (time.perf_counter() - t0) / n
    assert each < 250 * empty, (each, empty)


def test_trace_module_imports_without_jax():
    """Followers are to run JAX-free (ROADMAP queue 2, A8): the tracing
    plane, mirror and all, must not pull jax in; jax_eval installs the
    mirror from its side."""
    import subprocess
    import sys

    code = ("import sys; import tikv_tpu.util.trace as t; "
            "assert 'jax' not in sys.modules, 'trace imported jax'; "
            "assert t.TRACER._mirror is None; "
            "import tikv_tpu.copr.jax_eval; "
            "assert t.TRACER._mirror is sys.modules['jax'].profiler.TraceAnnotation")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
