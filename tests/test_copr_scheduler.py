"""Unified coprocessor read scheduler (copr/scheduler.py): cross-region
continuous batching, mixed-eligibility handle_batch, admission control,
and the fused-batch metrics contract.

Every batched response must be byte-identical to the per-request CPU
pipeline — the scheduler only ever removes dispatches, never changes bytes.
"""

import contextlib
import threading
import time

import numpy as np
import pytest

from tikv_tpu.copr import jax_eval
from tikv_tpu.copr import observatory as obs
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.dag import Aggregation, DagRequest, Limit, Selection, TableScan
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.plan_shape import plan_signature, split
from tikv_tpu.copr.scheduler import SchedulerConfig
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_WRITE
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util.inbound import InboundReads
from tikv_tpu.util.metrics import REGISTRY

from copr_fixtures import PRODUCT_COLUMNS, TABLE_ID as PRODUCT_TABLE, product_engine
from tikv_tpu.copr.table import record_range

TABLE_ID = 77

COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.int64()),
    ColumnInfo(3, FieldType.varchar()),
    ColumnInfo(4, FieldType.decimal_type(2)),
]


def _engine(n: int, seed: int = 0) -> BTreeEngine:
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 100, n)
    price = rng.integers(100, 100000, n)
    names = (b"x", b"y", b"z")
    eng = BTreeEngine()
    items = []
    for i in range(n):
        rk = record_key(TABLE_ID, i)
        val = encode_row(COLS[1:], [int(a[i]), names[i % 3], int(price[i])])
        items.append((Key.from_raw(rk).append_ts(20).encoded,
                      Write(WriteType.PUT, 10, short_value=val).to_bytes()))
    eng.bulk_load(CF_WRITE, items)
    return eng


def _sum_dag(cut: int) -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("lt", col(1), const_int(cut))]),
        Aggregation([], [AggDescriptor("sum", col(3)),
                         AggDescriptor("count", None)]),
    ])


def _group_dag() -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Aggregation([col(2)], [AggDescriptor("sum", col(1)),
                               AggDescriptor("count", None)]),
    ])


def _scan_dag() -> DagRequest:
    return DagRequest(executors=[TableScan(TABLE_ID, COLS), Limit(10)])


def _region_req(region: int, rows_per: int, dag: DagRequest,
                priority: str | None = None, apply_index: int = 7) -> CoprRequest:
    lo = record_key(TABLE_ID, region * rows_per)
    hi = record_key(TABLE_ID, (region + 1) * rows_per)
    ctx = {"region_id": region + 1, "region_epoch": (1, 1),
           "apply_index": apply_index}
    if priority is not None:
        ctx["priority"] = priority
    return CoprRequest(103, dag, [(lo, hi)], 100, context=ctx)


ROWS_PER = 600
N_REGIONS = 4


@pytest.fixture(scope="module")
def engines():
    eng = _engine(ROWS_PER * N_REGIONS, seed=5)
    dev = Endpoint(LocalEngine(eng), enable_device=True, block_rows=1024)
    cpu = Endpoint(LocalEngine(eng), enable_device=False)
    return dev, cpu


def test_plan_signature_groups_same_plans():
    assert plan_signature(_sum_dag(50)) == plan_signature(_sum_dag(50))
    assert plan_signature(_sum_dag(50)) != plan_signature(_sum_dag(51))
    assert plan_signature(_sum_dag(50)) != plan_signature(_group_dag())


def test_plan_signature_normalizes_wire_sigs():
    """A tipb ScalarFuncSig spelling and its kernel name key identically
    (sig_map is the single source of truth for the fold)."""
    a = DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("LtInt", col(1), const_int(9))]),
        Aggregation([], [AggDescriptor("count", None)]),
    ])
    b = DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("lt", col(1), const_int(9))]),
        Aggregation([], [AggDescriptor("count", None)]),
    ])
    assert plan_signature(a) == plan_signature(b)


def test_xregion_batch_byte_identical(engines):
    """Same plan across regions collapses into one cross-region program;
    responses match the CPU pipeline byte for byte (group order included)."""
    dev, cpu = engines
    dags = [lambda: _sum_dag(50), lambda: _sum_dag(80), _group_dag]
    reqs = [_region_req(r, ROWS_PER, d()) for d in dags for r in range(N_REGIONS)]
    # warm (fills region images + compiles)
    dev.handle_batch([_region_req(r, ROWS_PER, d())
                      for d in dags for r in range(N_REGIONS)])
    before = REGISTRY.counter("tikv_coprocessor_sched_batches_total", "").get(
        kind="xregion")
    got = dev.handle_batch(reqs)
    after = REGISTRY.counter("tikv_coprocessor_sched_batches_total", "").get(
        kind="xregion")
    assert after >= before + 3  # one cross-region batch per signature
    assert all(r.from_device for r in got)
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, req.dag, req.ranges, req.start_ts, dict(req.context)))
        assert resp.data == want.data
    # scheduler metadata rides the response
    assert any(r.metrics.get("sched_batch") == "xregion" for r in got)
    occ = [r.metrics.get("batch_occupancy") for r in got
           if r.metrics.get("sched_batch") == "xregion"]
    assert occ and all(o >= 2 for o in occ)


def _seed_profiles(sid: str, xregion_s: float, zone_s: float) -> None:
    for _ in range(8):
        obs.OBSERVATORY.record_serve(sid, "xregion", xregion_s, rows=ROWS_PER)
        obs.OBSERVATORY.record_serve(sid, "zone", zone_s, rows=ROWS_PER)


def _xregion_carries_a_compile(sid: str) -> None:
    """The state that flipped runs: ``xregion`` cheaper by p50, with a 20 s
    entry in the compile ledger that ``zone`` has not."""
    _seed_profiles(sid, 0.002, 0.009)
    obs.OBSERVATORY.record_compile("jax_eval.xregion", "xregion", 20.0, sig=sid)


def _xregion_aged_out(sid: str) -> None:
    """The state a ``zone`` run stayed in: every retained window of the
    ``xregion`` profile has rolled past (windowed count 0, lifetime count
    kept) and only ``zone`` is warm."""
    _seed_profiles(sid, 0.012, 0.003)
    for (path, _enc), prof in obs.OBSERVATORY._sigs[sid].paths.items():
        if path == "xregion":
            prof.windows = [type(prof.windows[0])(time.monotonic())]


@pytest.mark.parametrize("profiles", [
    pytest.param(lambda sid: None, id="cold"),
    pytest.param(lambda sid: _seed_profiles(sid, 0.012, 0.003),
                 id="xregion_dearer_by_p50"),
    pytest.param(_xregion_carries_a_compile, id="xregion_carries_a_compile"),
    pytest.param(_xregion_aged_out, id="xregion_aged_out"),
])
def test_same_plan_riders_are_one_xregion_group_whatever_was_measured(
        engines, monkeypatch, profiles):
    """The rule of ``_group``: two riders of one plan over two region views
    are ONE xregion group.  No state of the observatory's profiles takes
    the pair apart, with a live router whose probes are held at 0 (the
    benchmark's configurations): each of these states could, or did, send
    the pair to per-request serving while the scheduler weighed the batch
    against a synthetic ``direct`` path."""
    from tikv_tpu.copr.costmodel import CostRouter, RouterConfig

    dev, cpu = engines
    router = CostRouter(enabled=True, config=RouterConfig(
        seed=3, epsilon=0.0, cold_probe_rate=0.0))
    ep = Endpoint(dev.engine, enable_device=True, block_rows=1024,
                  cost_router=router)
    pair = lambda: [_region_req(r, ROWS_PER, _sum_dag(61)) for r in range(2)]
    ep.handle_batch(pair())  # images and the program
    groups = []
    group = ep.scheduler._group

    def spy(items):
        groups.append(group(items))
        return groups[-1]

    monkeypatch.setattr(ep.scheduler, "_group", spy)
    obs.OBSERVATORY.reset()
    profiles(obs.dag_sig(_sum_dag(61))[0])
    try:
        got = ep.handle_batch(pair())
    finally:
        obs.OBSERVATORY.reset()
    [(exec_groups, rest)] = groups
    assert rest == []
    [(kind, g_sig, slots)] = exec_groups
    # a group's key is the plan's whole identity: shape and literals
    assert (kind, g_sig, len(slots)) == ("xregion", split(_sum_dag(61)), 2)
    for req, resp in zip(pair(), got):
        assert resp.from_device
        assert resp.metrics.get("sched_batch") == "xregion"
        assert resp.data == cpu.handle_request(req).data
    # the label that named the other side of the weighing is gone from the
    # router's series, whichever test ran before this one in the process
    routed = REGISTRY.counter("tikv_coprocessor_cost_route_total", "").render()
    assert 'path="direct"' not in routed


def test_xregion_dedupes_identical_requests(engines):
    """Identical hot requests from many clients share one execution slot."""
    dev, cpu = engines
    reqs = [_region_req(r, ROWS_PER, _sum_dag(42))
            for r in range(N_REGIONS) for _ in range(3)]
    got = dev.handle_batch(reqs)
    want = {r: cpu.handle_request(_region_req(r, ROWS_PER, _sum_dag(42))).data
            for r in range(N_REGIONS)}
    for req, resp in zip(reqs, got):
        assert resp.data == want[req.context["region_id"] - 1]
    # 12 requests, but the batch occupancy counts the 12 (shared slots serve
    # every rider), all from one device dispatch
    assert all(r.from_device for r in got)


def test_one_shape_is_one_program_and_each_literal_its_own_slot(engines, monkeypatch):
    """The two keys (docs/copr_scheduler.md).  Identical requests still share
    one slot's response bytes; requests of one shape and different literals
    never do: they are separate groups, separate launches of ONE evaluator's
    program, each answered for its own literal."""
    dev, cpu = engines
    a, b = lambda: _sum_dag(41), lambda: _sum_dag(73)
    assert split(a())[0] == split(b())[0] and split(a()) != split(b())
    assert plan_signature(a()) != plan_signature(b())
    pair = [_region_req(r, ROWS_PER, a()) for r in range(2)]
    dev.handle_batch(pair)  # images, the evaluator and its program
    shapes = dict(dev._evaluators)
    groups, group = [], dev.scheduler._group

    def spy(items):
        groups.append(group(items))
        return groups[-1]

    monkeypatch.setattr(dev.scheduler, "_group", spy)
    launched = []
    launch = jax_eval.launch_xregion_cached

    def count(ev, caches, params=()):
        launched.append((ev, params))
        return launch(ev, caches, params)

    monkeypatch.setattr(jax_eval, "launch_xregion_cached", count)
    # A on region 0 twice (one slot), A on region 1, B on both regions
    reqs = [_region_req(0, ROWS_PER, a()), _region_req(0, ROWS_PER, a()),
            _region_req(1, ROWS_PER, a()),
            _region_req(0, ROWS_PER, b()), _region_req(1, ROWS_PER, b())]
    got = dev.handle_batch(reqs)
    [(exec_groups, rest)] = groups
    assert rest == []
    by_sig = {sig: slots for kind, sig, slots in exec_groups}
    assert set(by_sig) == {split(a()), split(b())}
    assert sorted(len(s.items) for s in by_sig[split(a())]) == [1, 2]
    assert sorted(len(s.items) for s in by_sig[split(b())]) == [1, 1]
    # two launches of one evaluator, each with its group's literal
    assert len(launched) == 2 and launched[0][0] is launched[1][0]
    assert {p for _ev, p in launched} == {(41,), (73,)}
    assert dev._evaluators == shapes and split(a())[0] in shapes
    # the twins share one slot's bytes; nobody else shares anybody's
    assert got[0].data == got[1].data
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, req.dag, req.ranges, req.start_ts, dict(req.context)))
        assert resp.data == want.data and resp.from_device
    assert got[0].data != got[3].data and got[2].data != got[4].data


def test_mixed_eligibility_batch(engines):
    """Ineligible requests (non-agg DAG, checksum) ride the same batch and
    answer per-request; order is preserved; eligible ones still fuse."""
    dev, cpu = engines
    reqs = [
        _region_req(0, ROWS_PER, _sum_dag(50)),
        _region_req(1, ROWS_PER, _scan_dag()),       # no aggregation
        _region_req(1, ROWS_PER, _sum_dag(50)),
        CoprRequest(105, None, [record_range(TABLE_ID)], 100, context={}),
        _region_req(2, ROWS_PER, _sum_dag(50)),
    ]
    got = dev.handle_batch(reqs)
    assert len(got) == len(reqs)
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(req.tp, req.dag, req.ranges, req.start_ts,
                        dict(req.context or {})))
        assert resp.data == want.data
    assert got[0].from_device and got[2].from_device and got[4].from_device
    assert not got[3].from_device


def test_priority_lane_stamped(engines):
    dev, _cpu = engines
    reqs = [_region_req(r, ROWS_PER, _sum_dag(50), priority="high")
            for r in range(N_REGIONS)]
    got = dev.handle_batch(reqs)
    lanes = {r.metrics.get("sched_lane") for r in got}
    assert lanes == {"high"}


def test_cold_cache_first_fill_then_fused():
    """cache_version-keyed block cache, cold: the first request fills the
    shared cache per-request, the rest fuse — every response byte-identical
    to the CPU pipeline (the pre-scheduler _try_fused_batch contract)."""
    eng = LocalEngine(product_engine())
    dev = Endpoint(eng, enable_device=True)
    cpu = Endpoint(eng, enable_device=False)

    def agg_dag(fn, target):
        return DagRequest(executors=[
            TableScan(PRODUCT_TABLE, PRODUCT_COLUMNS),
            Aggregation([], [AggDescriptor(fn, col(target))]),
        ])

    dags = [agg_dag("count", 0), agg_dag("sum", 0), agg_dag("max", 0),
            agg_dag("min", 2)]
    ctx = {"region_id": 1, "cache_version": 3}
    reqs = [CoprRequest(103, d, [record_range(PRODUCT_TABLE)], 200, dict(ctx))
            for d in dags]
    resps = dev.handle_batch(reqs)
    assert all(r.from_device for r in resps)
    kinds = [r.metrics.get("sched_batch") for r in resps]
    assert kinds[0] == "fill" and all(k == "fused" for k in kinds[1:]), kinds
    for d, got in zip(dags, resps):
        want = cpu.handle_request(
            CoprRequest(103, d, [record_range(PRODUCT_TABLE)], 200, dict(ctx)))
        assert got.data == want.data


def test_fused_latency_one_observation_per_request():
    """The duration histogram gets ONE observation per fused request (not a
    single mean observation), so count-weighted percentiles stay honest
    against the unary path."""
    eng = LocalEngine(product_engine())
    dev = Endpoint(eng, enable_device=True)

    def agg_dag(fn):
        return DagRequest(executors=[
            TableScan(PRODUCT_TABLE, PRODUCT_COLUMNS),
            Aggregation([], [AggDescriptor(fn, col(0))]),
        ])

    ctx = {"region_id": 1, "cache_version": 9}
    reqs = [CoprRequest(103, agg_dag(fn), [record_range(PRODUCT_TABLE)], 200,
                        dict(ctx)) for fn in ("count", "sum", "max")]
    dev.handle_batch(reqs)  # cold: fill + fuse
    h = REGISTRY.histogram("tikv_coprocessor_request_duration_seconds", "")
    key = (("tp", "103"),)
    before = h._n.get(key, 0)
    resps = dev.handle_batch(reqs)  # warm: all three fuse
    assert all(r.from_device for r in resps)
    assert h._n.get(key, 0) >= before + len(reqs)


def test_device_failure_mid_batch_falls_back(engines, monkeypatch):
    """A device failure during the cross-region program sheds every slot to
    the per-request path — responses stay correct and nothing is lost."""
    dev, cpu = engines
    reqs = [_region_req(r, ROWS_PER, _sum_dag(60)) for r in range(N_REGIONS)]
    dev.handle_batch([_region_req(r, ROWS_PER, _sum_dag(60))
                      for r in range(N_REGIONS)])  # warm images

    def boom(*a, **k):
        raise RuntimeError("device lost mid-batch")

    monkeypatch.setattr(jax_eval, "launch_xregion_cached", boom)
    fallbacks = dev.device_fallbacks
    got = dev.handle_batch(reqs)
    assert dev.device_fallbacks > fallbacks
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, req.dag, req.ranges, req.start_ts, dict(req.context)))
        assert resp.data == want.data
    monkeypatch.undo()
    # the region images survived the failure: next batch is fused again
    got2 = dev.handle_batch(reqs)
    assert all(r.from_device for r in got2)
    assert any(r.metrics.get("sched_batch") == "xregion" for r in got2)


def test_cold_fill_failure_leaves_no_partial_cache(monkeypatch):
    """A device failure during the cold fill must not leave a partially
    filled block cache behind (it would double-append and serve wrong data
    forever)."""
    eng = LocalEngine(product_engine())
    dev = Endpoint(eng, enable_device=True)
    cpu = Endpoint(eng, enable_device=False)

    def agg_dag(fn):
        return DagRequest(executors=[
            TableScan(PRODUCT_TABLE, PRODUCT_COLUMNS),
            Aggregation([], [AggDescriptor(fn, col(0))]),
        ])

    ctx = {"region_id": 1, "cache_version": 77}
    reqs = [CoprRequest(103, agg_dag(fn), [record_range(PRODUCT_TABLE)], 200,
                        dict(ctx)) for fn in ("count", "sum")]

    calls = {"n": 0}
    orig = jax_eval.JaxDagEvaluator.run

    def failing_run(self, source, cache=None, params=()):
        calls["n"] += 1
        if cache is not None and not cache.filled:
            # crash mid-fill, after blocks were appended
            for cols, n_valid in self._blocks(source):
                break
            raise RuntimeError("device died during fill")
        return orig(self, source, cache=cache)

    monkeypatch.setattr(jax_eval.JaxDagEvaluator, "run", failing_run)
    got = dev.handle_batch(reqs)
    monkeypatch.undo()
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, req.dag, req.ranges, req.start_ts, dict(req.context)))
        assert resp.data == want.data
    cache = dev._block_cache_for(reqs[0])
    assert cache.filled or not cache.blocks, "partially-filled cache left behind"


def test_padding_budget_sheds_block_count_outlier():
    """One region with 8x the blocks of its peers sheds to the per-request
    path instead of padding every peer up to its geometry."""
    eng = _engine(ROWS_PER * 8, seed=9)
    # tiny blocks so region 0's wider range spans many blocks
    dev = Endpoint(LocalEngine(eng), enable_device=True, block_rows=256,
                   sched_config=SchedulerConfig(padding_budget=0.5))
    cpu = Endpoint(LocalEngine(eng), enable_device=False)
    big = CoprRequest(103, _sum_dag(70),
                      [(record_key(TABLE_ID, 0), record_key(TABLE_ID, 5 * ROWS_PER))],
                      100, context={"region_id": 1, "region_epoch": (1, 1),
                                    "apply_index": 7})
    smalls = [CoprRequest(
        103, _sum_dag(70),
        [(record_key(TABLE_ID, (5 + i) * ROWS_PER),
          record_key(TABLE_ID, (6 + i) * ROWS_PER))],
        100, context={"region_id": 10 + i, "region_epoch": (1, 1),
                      "apply_index": 7}) for i in range(3)]
    reqs = [big] + smalls
    dev.handle_batch([CoprRequest(r.tp, r.dag, r.ranges, r.start_ts,
                                  dict(r.context)) for r in reqs])  # warm
    before = REGISTRY.counter("tikv_coprocessor_sched_shed_total", "").get(
        reason="padding")
    got = dev.handle_batch(reqs)
    after = REGISTRY.counter("tikv_coprocessor_sched_shed_total", "").get(
        reason="padding")
    assert after > before
    assert got[0].metrics.get("sched_batch", "").startswith("shed:padding")
    assert all(r.metrics.get("sched_batch") == "xregion" for r in got[1:])
    for req, resp in zip(reqs, got):
        want = cpu.handle_request(
            CoprRequest(103, req.dag, req.ranges, req.start_ts, dict(req.context)))
        assert resp.data == want.data


def test_aliased_image_slots_keep_snapshot_isolation():
    """Two requests over the SAME region at different start_ts around a
    write: the region cache holds ONE mutable image per (region, ranges,
    schema), so resolving the later request delta-applies it in place.
    Only the last resolution may batch; the earlier one must shed and still
    return the bytes its snapshot demands."""
    rows = ROWS_PER * 2
    eng = _engine(rows, seed=13)
    dev = Endpoint(LocalEngine(eng), enable_device=True, block_rows=1024)
    cpu = Endpoint(LocalEngine(eng), enable_device=False)

    def rq(ts, apply_index):
        return CoprRequest(103, _sum_dag(95),
                           [(record_key(TABLE_ID, 0), record_key(TABLE_ID, rows))],
                           ts, context={"region_id": 1, "region_epoch": (1, 1),
                                        "apply_index": apply_index})

    dev.handle_request(rq(100, 7))  # build the image at ts 100
    # overwrite a row at commit ts 150
    val = encode_row(COLS[1:], [1, b"zz", 424242])
    eng.bulk_load(CF_WRITE, [(
        Key.from_raw(record_key(TABLE_ID, 3)).append_ts(150).encoded,
        Write(WriteType.PUT, 140, short_value=val).to_bytes())])
    before = REGISTRY.counter("tikv_coprocessor_sched_shed_total", "").get(
        reason="aliased_image")
    got = dev.handle_batch([rq(100, 7), rq(200, 8)])
    after = REGISTRY.counter("tikv_coprocessor_sched_shed_total", "").get(
        reason="aliased_image")
    assert after > before
    want_old = cpu.handle_request(rq(100, 7))
    want_new = cpu.handle_request(rq(200, 8))
    assert got[0].data == want_old.data, "ts-100 reader saw the ts-150 write"
    assert got[1].data == want_new.data
    assert want_old.data != want_new.data  # the write is actually visible at 200


def test_continuous_mode_coalesces_across_threads(engines):
    """start() turns on the continuous lanes: concurrent unary submissions
    coalesce into scheduler batches and every caller gets its own bytes."""
    dev, cpu = engines
    sched = dev.scheduler
    # slow lanes a little so the submissions actually meet in one batch
    sched.cfg.max_wait_s = 0.05
    sched.start()
    try:
        want = {r: cpu.handle_request(_region_req(r, ROWS_PER, _sum_dag(33))).data
                for r in range(N_REGIONS)}
        dev.handle_batch([_region_req(r, ROWS_PER, _sum_dag(33))
                          for r in range(N_REGIONS)])  # warm images/compile
        results: dict[int, bytes] = {}
        errors: list = []

        def client(r):
            try:
                resp = sched.execute(_region_req(r, ROWS_PER, _sum_dag(33)),
                                     timeout=30.0)
                results[r] = resp.data
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(r,))
                   for r in range(N_REGIONS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not errors, errors
        assert results == want
    finally:
        sched.stop()
    assert not sched.running


def test_continuous_mode_isolates_per_request_errors(engines, monkeypatch):
    """One rider's failure (lock conflict, decode error) must not poison the
    other requests that coalesced into the same dispatcher batch."""
    dev, cpu = engines
    sched = dev.scheduler
    sched.cfg.max_wait_s = 0.05
    orig = type(dev).handle_request

    def failing(self, req):
        if (req.context or {}).get("region_id") == 99:
            raise RuntimeError("injected per-request failure")
        return orig(self, req)

    monkeypatch.setattr(type(dev), "handle_request", failing)
    dev.handle_batch([_region_req(r, ROWS_PER, _sum_dag(37))
                      for r in range(N_REGIONS)])  # warm
    sched.start()
    try:
        results: dict[int, bytes] = {}
        errs: dict[int, BaseException] = {}

        def client(r, req):
            try:
                results[r] = sched.execute(req, timeout=30.0).data
            except BaseException as e:  # noqa: BLE001
                errs[r] = e

        bad = CoprRequest(103, _sum_dag(37), [(record_key(TABLE_ID, 0),
                                               record_key(TABLE_ID, 10))],
                          100, context={"region_id": 99})  # no cache -> shed
        reqs = [(r, _region_req(r, ROWS_PER, _sum_dag(37)))
                for r in range(N_REGIONS)] + [(99, bad)]
        threads = [threading.Thread(target=client, args=a) for a in reqs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    finally:
        sched.stop()
    assert isinstance(errs.get(99), RuntimeError)
    for r in range(N_REGIONS):
        assert r not in errs, f"rider {r} poisoned by region 99's failure: {errs.get(r)}"
        assert results[r] == cpu.handle_request(
            _region_req(r, ROWS_PER, _sum_dag(37))).data


def test_concurrent_queue_full_and_busy_reject_exactly_once(engines):
    """ISSUE 15 satellite: concurrent execute() callers racing a FULL
    queue each get exactly ONE typed outcome — batched/direct serve with
    correct bytes, or a busy rejection — with no lost wakeups (every call
    returns) and no double-counted sheds (the busy counter moves once per
    observed rejection)."""
    from tikv_tpu.util import failpoint
    from tikv_tpu.util.retry import ServerBusyError

    dev, cpu = engines
    sched = dev.scheduler
    old_cfg = sched.cfg
    rq = lambda: _region_req(0, ROWS_PER, _sum_dag(21))
    want = cpu.handle_request(rq()).data
    dev.handle_request(rq())  # warm image + compile
    shed = REGISTRY.counter("tikv_coprocessor_sched_shed_total")
    coalesce = REGISTRY.counter("tikv_wire_coalesce_total")
    N_THREADS, N_CALLS = 8, 6

    def drive():
        outcomes: list[str] = []
        mu = threading.Lock()

        def worker():
            for _ in range(N_CALLS):
                try:
                    r = sched.execute(rq(), timeout=30.0)
                    out = "served" if r.data == want else "wrong"
                except ServerBusyError:
                    out = "busy"
                with mu:
                    outcomes.append(out)

        threads = [threading.Thread(target=worker) for _ in range(N_THREADS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
        return outcomes

    # --- busy_reject: rejections are typed, counted exactly once ---
    sched.cfg = SchedulerConfig(max_queue=2, busy_reject=True)
    failpoint.cfg("sched_dispatch", "sleep(10)")  # keep the queue racing
    sched.start()
    try:
        busy0 = shed.get(reason="busy_reject")
        cbusy0 = coalesce.get(outcome="busy_reject")
        outcomes = drive()
        assert len(outcomes) == N_THREADS * N_CALLS, "a caller lost its wakeup"
        assert "wrong" not in outcomes
        n_busy = outcomes.count("busy")
        assert n_busy > 0, "the race never hit the full queue"
        assert shed.get(reason="busy_reject") == busy0 + n_busy, \
            "each rejection must count exactly once"
        assert coalesce.get(outcome="busy_reject") == cbusy0 + n_busy
    finally:
        failpoint.remove("sched_dispatch")
        sched.stop()

    # --- queue_full (busy_reject off): every racing caller is SERVED ---
    sched.cfg = SchedulerConfig(max_queue=1)
    failpoint.cfg("sched_dispatch", "sleep(10)")
    sched.start()
    try:
        qf0 = shed.get(reason="queue_full")
        cqf0 = coalesce.get(outcome="queue_full")
        outcomes = drive()
        assert len(outcomes) == N_THREADS * N_CALLS
        assert set(outcomes) == {"served"}, \
            "queue_full without busy_reject serves on the caller's thread"
        n_qf = shed.get(reason="queue_full") - qf0
        assert n_qf > 0, "the race never hit the full queue"
        assert coalesce.get(outcome="queue_full") == cqf0 + n_qf, \
            "direct-path sheds must count once on each series"
    finally:
        failpoint.remove("sched_dispatch")
        sched.stop()
        sched.cfg = old_cfg


def test_scheduler_stop_drains_queue(engines):
    dev, _cpu = engines
    sched = dev.scheduler
    sched.start()
    sched.stop()
    assert not sched.running
    # stopped scheduler serves directly
    resp = sched.execute(_region_req(0, ROWS_PER, _sum_dag(21)))
    assert resp.data


def test_mesh_serves_warm_cache_no_bypass(engines):
    """The PR-2 cache→mesh bypass is GONE: with a real mesh, a warm cached
    aggregation request serves THROUGH the sharded launcher
    (``mesh_cache_hit`` counts it), byte-identical to the meshless path;
    a plan with no mesh merge rule (``first``) declines to the
    single-device warm path without touching the counter."""
    from tikv_tpu.parallel.mesh import make_mesh

    dev, cpu = engines
    mesh_ep = Endpoint(LocalEngine(dev.engine.kv), enable_device=True,
                       block_rows=1024, mesh=make_mesh(groups=2))
    req = lambda d: _region_req(0, ROWS_PER, d)
    mesh_ep.handle_request(req(_sum_dag(44)))  # warm image (miss)
    before = REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get()
    resp = mesh_ep.handle_request(req(_sum_dag(44)))
    after = REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get()
    assert resp.from_device and resp.from_cache
    assert after == before + 1
    assert resp.data == cpu.handle_request(req(_sum_dag(44))).data
    # no merge rule for `first` -> documented decline, single-device warm
    first_dag = DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Aggregation([], [AggDescriptor("first", col(1)),
                         AggDescriptor("count", None)]),
    ])
    mesh_ep.handle_request(req(first_dag))  # warm its image
    b2 = REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get()
    r2 = mesh_ep.handle_request(req(first_dag))
    assert r2.from_device
    assert REGISTRY.counter("tikv_coprocessor_mesh_cache_hit_total", "").get() == b2
    assert r2.data == cpu.handle_request(req(first_dag)).data


# ---------------------------------------------------------------------------
# the linger ends when the store's inbound pipeline is empty (ISSUE 30)
# ---------------------------------------------------------------------------
#
# max_wait_s is 0.5 s in all of these, so "did not wait" (well under 0.25 s)
# and "waited" (0.5 s) are far apart; nothing is driven by a sleep shorter
# than the linger: the tests wait for events and read the dispatcher's own
# record of its passes.

LINGER = 0.5


class _WatchedInbound(InboundReads):
    """The store's inbound count with one addition: an event set whenever the
    DISPATCHER reads the count, which it only does with riders queued and
    their linger still running."""

    def __init__(self):
        super().__init__()
        self.looked = threading.Event()

    def pending(self):
        n = super().pending()
        if threading.current_thread().name == "copr-sched":
            self.looked.set()
        return n


def _passes():
    """(passes, riders) by ``why`` from the dispatcher's own series."""
    c = REGISTRY.counter("tikv_coprocessor_sched_dispatch_total")
    h = REGISTRY.histogram("tikv_coprocessor_sched_dispatch_riders",
                           buckets=(1, 2, 4, 8, 16, 32, 64))
    return {why: (c.get(why=why), h.total(why=why))
            for why in ("drained", "deadline", "full", "stop")}


def _moved(before: dict) -> dict:
    after = _passes()
    return {why: (after[why][0] - before[why][0], after[why][1] - before[why][1])
            for why in after
            if after[why] != before[why]}


@pytest.fixture()
def lingering(engines):
    """A scheduler of its own over the module's device endpoint, lingering
    half a second on every lane unless a test says otherwise."""
    from tikv_tpu.copr.scheduler import CoprReadScheduler

    dev, cpu = engines
    # images, and the programs of every shape the tests dispatch: a batch
    # of all regions, of three, of two, and a lone task
    for regions in (range(N_REGIONS), range(3), range(2)):
        dev.handle_batch([_region_req(r, ROWS_PER, _sum_dag(33))
                          for r in regions])
    for r in range(N_REGIONS):
        dev.handle_request(_region_req(r, ROWS_PER, _sum_dag(33)))
    sched = CoprReadScheduler(dev, SchedulerConfig(
        max_wait_s=LINGER, high_max_wait_s=LINGER, low_max_wait_s=LINGER))
    yield sched, cpu
    assert sched.stop()


def _submit(sched, inbound, region, out, priority=None, dag=None,
            arrived=False):
    """What the server does for one frame: count it off the socket (unless
    the test did, ahead of time: ``arrived``), then serve it on a thread that
    owes the count."""
    if inbound is not None and not arrived:
        inbound.arrived()

    def serve():
        t0 = time.perf_counter()
        with inbound.handling() if inbound is not None else contextlib.nullcontext():
            resp = sched.execute(
                _region_req(region, ROWS_PER, dag or _sum_dag(33),
                            priority=priority),
                timeout=30.0)
        out[region] = (resp.data, time.perf_counter() - t0)

    t = threading.Thread(target=serve)
    t.start()
    return t


def _want(cpu, region):
    return cpu.handle_request(_region_req(region, ROWS_PER, _sum_dag(33))).data


def _join(threads):
    for t in threads:
        t.join(30.0)
        assert not t.is_alive()


def test_riders_of_one_plan_leave_at_once_when_nothing_is_inbound(lingering):
    sched, cpu = lingering
    inbound = _WatchedInbound()
    sched.watch_inbound(inbound)
    sched.start()
    before, out = _passes(), {}
    _join([_submit(sched, inbound, r, out) for r in range(2)])
    for r in range(2):
        data, took = out[r]
        assert data == _want(cpu, r)
        assert took < LINGER / 2, f"waited {took:.3f} s with nobody inbound"
    assert _moved(before) == {"drained": (1, 2)}
    assert inbound.pending() == 0 and inbound.low == 0


@pytest.mark.parametrize("parked_first", [1, 2])
def test_riders_wait_for_the_one_on_its_way(lingering, parked_first):
    """One more frame is off the socket than riders are parked: the
    dispatcher must see that and hold them (two of one plan could leave, but
    for the count), and the last one's arrival (the count's fall to zero)
    releases all in ONE pass, long before the linger ends."""
    sched, cpu = lingering
    inbound = _WatchedInbound()
    sched.watch_inbound(inbound)
    sched.start()
    before, out = _passes(), {}
    inbound.arrived()  # the last frame, read right behind the others
    ts = [_submit(sched, inbound, r, out) for r in range(parked_first)]
    t_end = time.monotonic() + 10.0
    while sum(len(q) for q in sched._queues.values()) < parked_first:
        assert time.monotonic() < t_end
        time.sleep(0.001)
    # the dispatcher has looked at a queue that holds the first riders and
    # a count that holds the last: it must not have let them go
    inbound.looked.clear()
    assert inbound.looked.wait(10.0)
    assert inbound.pending() == 1
    assert _moved(before) == {} and not out
    t0 = time.perf_counter()
    ts.append(_submit(sched, inbound, parked_first, out, arrived=True))
    _join(ts)
    took = time.perf_counter() - t0
    for r in range(parked_first + 1):
        assert out[r][0] == _want(cpu, r)
    assert _moved(before) == {"drained": (1, parked_first + 1)}, "not ONE pass"
    assert took < LINGER / 2, f"released by the linger, not the arrival: {took:.3f} s"
    assert inbound.pending() == 0 and inbound.low == 0


@pytest.mark.parametrize("source", ["stuck_at_one", "none", "lone_rider"])
def test_linger_runs_to_its_deadline_without_a_zero(lingering, source):
    """A count that never falls to zero (a leak upward, or a frame that
    really is on its way), a scheduler nobody gave a count, and a rider alone
    of its plan with nobody inbound (its query's next task may not have been
    SENT yet, which no store can see): the pass leaves at the oldest rider's
    deadline, as it always did."""
    sched, cpu = lingering
    inbound = None
    if source != "none":
        inbound = _WatchedInbound()
        sched.watch_inbound(inbound)
        if source == "stuck_at_one":
            inbound.arrived()  # never served
    sched.start()
    before, out = _passes(), {}
    _join([_submit(sched, inbound, 0, out)])
    data, took = out[0]
    assert data == _want(cpu, 0)
    assert took >= LINGER * 0.9, f"left after {took:.3f} s of a {LINGER} s linger"
    assert _moved(before) == {"deadline": (1, 1)}
    if inbound is not None:
        assert inbound.pending() == (source == "stuck_at_one") and inbound.low == 0


def test_a_rider_alone_of_its_plan_holds_the_pass_it_would_leave_in(lingering):
    """Two riders of one plan and one of another, nobody inbound: leaving now
    would serve the third per request, so the pass keeps the linger it always
    had, and the third's partner, a while later, releases all four."""
    sched, cpu = lingering
    sched.ep.handle_batch([_region_req(r, ROWS_PER, _group_dag()) for r in (2, 3)])
    inbound = _WatchedInbound()
    sched.watch_inbound(inbound)
    inbound.arrived()  # holds the pass until all three riders are parked
    sched.start()
    before, out = _passes(), {}
    ts = [_submit(sched, inbound, r, out) for r in range(2)]
    ts.append(_submit(sched, inbound, 2, out, dag=_group_dag()))
    t_end = time.monotonic() + 10.0
    while sum(len(q) for q in sched._queues.values()) < 3:
        assert time.monotonic() < t_end
        time.sleep(0.001)
    inbound.left()  # nobody else is coming, and the dispatcher is told so
    inbound.looked.clear()
    assert inbound.looked.wait(10.0)  # looked at three riders and a zero
    assert _moved(before) == {} and not out
    t0 = time.perf_counter()
    ts.append(_submit(sched, inbound, 3, out, dag=_group_dag()))
    _join(ts)
    assert time.perf_counter() - t0 < LINGER / 2
    assert _moved(before) == {"drained": (1, 4)}
    for r in range(4):
        dag = _group_dag() if r >= 2 else _sum_dag(33)
        assert out[r][0] == cpu.handle_request(_region_req(r, ROWS_PER, dag)).data
    assert inbound.pending() == 0 and inbound.low == 0


@pytest.mark.parametrize("lane,wait_s", [("high", 0.4), ("low", 0.7)])
def test_lanes_obey_the_rule_with_their_own_deadlines(lingering, lane, wait_s):
    """With a frame on its way two riders of one plan wait out THEIR lane's
    linger; with none they leave at once, whatever the lane."""
    sched, cpu = lingering
    setattr(sched.cfg, f"{lane}_max_wait_s", wait_s)
    inbound = _WatchedInbound()
    sched.watch_inbound(inbound)
    sched.start()
    before, out = _passes(), {}
    _join([_submit(sched, inbound, r, out, priority=lane) for r in range(2)])
    assert max(took for _d, took in out.values()) < wait_s / 2
    assert _moved(before) == {"drained": (1, 2)}
    inbound.arrived()  # one more on its way, for good
    before, out = _passes(), {}
    _join([_submit(sched, inbound, r, out, priority=lane) for r in range(2)])
    slowest = max(took for _d, took in out.values())
    assert wait_s * 0.9 <= slowest < wait_s + 0.2, slowest
    assert _moved(before) == {"deadline": (1, 2)}
    assert out[1][0] == _want(cpu, 1)
    assert inbound.pending() == 1 and inbound.low == 0


def test_a_full_batch_and_a_stop_are_counted_as_such(lingering):
    sched, _cpu = lingering
    sched.cfg.max_batch = 2
    inbound = _WatchedInbound()
    sched.watch_inbound(inbound)
    inbound.arrived()  # keeps the linger running: only `full` can release
    sched.start()
    before, out = _passes(), {}
    ts = [_submit(sched, inbound, r, out) for r in range(2)]
    for t in ts:
        t.join(30.0)
        assert not t.is_alive()
    assert max(took for _d, took in out.values()) < LINGER / 2
    assert _moved(before) == {"full": (1, 2)}
    before = _passes()
    inbound.looked.clear()
    t = _submit(sched, inbound, 2, out)
    assert inbound.looked.wait(10.0)  # parked, and held by the count
    assert sched.stop()
    t.join(30.0)
    assert not t.is_alive()
    assert _moved(before) == {"stop": (1, 1)}


class _Refuses:
    """An overload control that turns every request away at admission."""

    def admit(self, ctx, where="", wait=True):
        from tikv_tpu.util.retry import ServerBusyError

        raise ServerBusyError("tenant over quota", retry_after_s=0.01)


@pytest.fixture()
def served(engines, monkeypatch):
    """The module's device endpoint behind a real ``Server``, wired as a
    store wires it: one inbound count shared by server and scheduler."""
    from tikv_tpu.server.server import Client, Server
    from tikv_tpu.server.service import KvService
    from tikv_tpu.storage.storage import Storage

    dev, _cpu = engines
    sched = dev.scheduler
    old_cfg = sched.cfg
    sched.cfg = SchedulerConfig(max_wait_s=LINGER)
    inbound = InboundReads()
    sched.watch_inbound(inbound)
    svc = KvService(Storage(engine=dev.engine), dev)
    srv = Server(svc, inbound=inbound)
    srv.start()
    sched.start()
    client = Client(*srv.addr)
    try:
        yield dev, svc, client, inbound
    finally:
        client.close()
        sched.stop()
        srv.stop()
        sched.cfg = old_cfg
        sched._inbound = None


def _wire_req(region: int, dag: DagRequest, **ctx) -> dict:
    from tikv_tpu.copr.dag_wire import dag_to_wire

    req = _region_req(region, ROWS_PER, dag)
    return {"dag": dag_to_wire(dag), "ranges": [list(r) for r in req.ranges],
            "start_ts": req.start_ts, "context": {**req.context, **ctx}}


@pytest.mark.parametrize("exit_", [
    "batched", "bypass", "queue_full", "busy_reject", "dead_on_arrival",
    "stale_not_ready", "overload", "parse_error", "handler_raises",
    "kv_get", "kv_scan",
])
def test_every_exit_counts_the_request_down_once(served, monkeypatch, exit_):
    """However a read leaves its handler, the inbound count is back at zero
    when the answer is, and it never went below."""
    dev, svc, client, inbound = served
    sched = dev.scheduler
    shed = REGISTRY.counter("tikv_coprocessor_sched_shed_total")
    coalesce = REGISTRY.counter("tikv_wire_coalesce_total")
    method, req = "coprocessor", _wire_req(0, _sum_dag(33))
    moved = None  # (counter, labels) that must move by one
    want_error = True
    if exit_ == "batched":
        # parks alone of its plan: leaves at its deadline, served direct
        want_error, moved = False, (coalesce, {"outcome": "direct"})
    elif exit_ == "bypass":
        req = _wire_req(0, _scan_dag())
        want_error, moved = False, (coalesce, {"outcome": "bypass"})
    elif exit_ == "queue_full":
        sched.cfg.max_queue = 0
        want_error, moved = False, (coalesce, {"outcome": "queue_full"})
    elif exit_ == "busy_reject":
        sched.cfg.max_queue, sched.cfg.busy_reject = 0, True
        moved = (shed, {"reason": "busy_reject"})
    elif exit_ == "dead_on_arrival":
        req = _wire_req(0, _sum_dag(33), timeout_ms=-1)
        moved = (REGISTRY.counter("tikv_coprocessor_deadline_expired_total"),
                 {"at": "admission"})
    elif exit_ == "stale_not_ready":
        class DataNotReadyError(Exception):
            pass

        def not_ready(ctx):
            raise DataNotReadyError("read_ts above the watermark")

        monkeypatch.setattr(dev.engine, "check_read_ready", not_ready,
                            raising=False)
        req = _wire_req(0, _sum_dag(33), stale_read=True)
        moved = (shed, {"reason": "data_not_ready"})
    elif exit_ == "overload":
        monkeypatch.setattr(dev, "overload", _Refuses(), raising=False)
        moved = (shed, {"reason": "tenant_quota"})
    elif exit_ == "parse_error":
        req = dict(req, dag={"executors": [{"no": "such executor"}]})
    elif exit_ == "handler_raises":
        def boom(method, request):
            raise RuntimeError("handler fell over")

        monkeypatch.setattr(svc, "dispatch", boom)
    elif exit_ == "kv_get":
        method, req, want_error = "kv_get", {"key": b"k", "version": 10}, False
    elif exit_ == "kv_scan":
        method, want_error = "kv_scan", False
        req = {"start_key": b"a", "end_key": b"z", "limit": 4, "version": 10}
    before = moved[0].get(**moved[1]) if moved else None
    resp = client.call(method, req, timeout=30.0)
    assert bool(resp.get("error")) == want_error, resp
    if moved:
        assert moved[0].get(**moved[1]) == before + 1, (exit_, resp)
    assert inbound.pending() == 0, f"{exit_} left the count at {inbound.pending()}"
    assert inbound.low == 0, f"{exit_} took the count to {inbound.low}"
