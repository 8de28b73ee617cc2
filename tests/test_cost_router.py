"""Cost-based path router + geometry auto-tuner (copr/costmodel.py,
docs/cost_router.md): measured routing with bounded exploration, strict
static fallback, the kill switch's byte-and-metric identity, the hill-climb
tuner's convergence and automatic revert, and the operator surfaces.

Run under TIKV_TPU_SANITIZE=1 by scripts/check.sh — routing sits on the
serving hot path and must share no lock with the observatory or metrics."""

import json
import os
import sys

import numpy as np
import pytest

from copr_fixtures import TABLE_ID as PRODUCT_TABLE  # noqa: F401 (path setup)
from tikv_tpu.copr import observatory as obs
from tikv_tpu.copr.aggr import AggDescriptor
from tikv_tpu.copr.costmodel import (
    CostRouter, Decision, GeometryTuner, RouterConfig, TunerConfig,
)
from tikv_tpu.copr.dag import Aggregation, DagRequest, Selection, TableScan
from tikv_tpu.copr.datatypes import ColumnInfo, FieldType
from tikv_tpu.copr.encoding import candidate_paths
from tikv_tpu.copr.endpoint import CoprRequest, Endpoint
from tikv_tpu.copr.overload import AdaptiveController, OverloadConfig
from tikv_tpu.copr.rpn import call, col, const_int
from tikv_tpu.copr.table import encode_row, record_key
from tikv_tpu.storage.btree_engine import BTreeEngine
from tikv_tpu.storage.engine import CF_WRITE
from tikv_tpu.storage.kv import LocalEngine
from tikv_tpu.storage.txn_types import Key, Write, WriteType
from tikv_tpu.util.config import ConfigController, TikvConfig
from tikv_tpu.util.metrics import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TABLE_ID = 93

COLS = [
    ColumnInfo(1, FieldType.int64(), is_pk_handle=True),
    ColumnInfo(2, FieldType.int64()),
    ColumnInfo(3, FieldType.int64()),
]


def _engine(n: int, seed: int = 0) -> BTreeEngine:
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 50, n)
    b = rng.integers(0, 100000, n)
    eng = BTreeEngine()
    items = []
    for i in range(n):
        rk = record_key(TABLE_ID, i)
        val = encode_row(COLS[1:], [int(a[i]), int(b[i])])
        items.append((Key.from_raw(rk).append_ts(20).encoded,
                      Write(WriteType.PUT, 10, short_value=val).to_bytes()))
    eng.bulk_load(CF_WRITE, items)
    return eng


def _sum_dag(cut: int = 40) -> DagRequest:
    return DagRequest(executors=[
        TableScan(TABLE_ID, COLS),
        Selection([call("lt", col(1), const_int(cut))]),
        Aggregation([], [AggDescriptor("sum", col(2)),
                         AggDescriptor("count", None)]),
    ])


def _req(rows: int, dag: DagRequest) -> CoprRequest:
    lo = record_key(TABLE_ID, 0)
    hi = record_key(TABLE_ID, rows)
    return CoprRequest(103, dag, [(lo, hi)], 100, context={
        "region_id": 1, "region_epoch": (1, 1), "apply_index": 7,
    })


@pytest.fixture(autouse=True)
def _fresh_observatory():
    obs.OBSERVATORY.reset()
    yield
    obs.OBSERVATORY.reset()


def _seed_profiles(sig: str, table: dict[str, float], n: int = 8,
                   rows: int = 400) -> None:
    """Warm per-path profiles directly: ``table`` maps path -> latency_s."""
    for _ in range(n):
        for path, lat in table.items():
            obs.OBSERVATORY.record_serve(sig, path, lat, rows=rows)


# ---------------------------------------------------------------------------
# RouterConfig / candidate set
# ---------------------------------------------------------------------------

def test_router_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(epsilon=0.9)
    with pytest.raises(ValueError):
        RouterConfig(cold_probe_rate=-0.1)
    with pytest.raises(ValueError):
        RouterConfig(min_count=0)
    with pytest.raises(ValueError):
        RouterConfig(compile_amortize_floor=0)
    with pytest.raises(ValueError):
        TunerConfig(revert_ratio=1.5)
    with pytest.raises(ValueError):
        TunerConfig(min_serves=0)


def test_candidate_paths_static_ladder_order():
    agg = _sum_dag()
    assert candidate_paths(agg, device_ok=True, mesh_ok=False) == \
        ["zone", "unary", "cpu"]
    assert candidate_paths(agg, device_ok=True, mesh_ok=True) == \
        ["mesh", "zone", "unary", "cpu"]
    scan = DagRequest(executors=[TableScan(TABLE_ID, COLS)])
    assert candidate_paths(scan, device_ok=True, mesh_ok=False) == \
        ["unary", "cpu"]
    # ineligible for the device: CPU is the only rung
    assert candidate_paths(agg, device_ok=False, mesh_ok=True) == ["cpu"]


# ---------------------------------------------------------------------------
# route(): static fallback, kill switch, measured, explore/cold bounds
# ---------------------------------------------------------------------------

def test_cold_profiles_fall_back_to_static_head():
    r = CostRouter(config=RouterConfig(seed=1))
    d = r.route("sigX", ["zone", "unary", "cpu"])
    assert (d.path, d.reason) == ("zone", "static_fallback")
    assert d.delta_ms is None


def test_kill_switch_is_static_and_counted():
    c = REGISTRY.counter("tikv_coprocessor_cost_route_total", "")
    before = c.get(path="zone", reason="kill_switch")
    r = CostRouter(enabled=False)
    # even with a warm table showing cpu cheapest, the kill switch must
    # return the static head and never consult costs
    _seed_profiles("sigK", {"cpu": 0.001, "zone": 0.5})
    for _ in range(10):
        d = r.route("sigK", ["zone", "unary", "cpu"])
        assert (d.path, d.reason) == ("zone", "kill_switch")
    assert c.get(path="zone", reason="kill_switch") == before + 10


def test_kill_switch_env(monkeypatch):
    monkeypatch.setenv("TIKV_TPU_COST_ROUTER", "0")
    assert CostRouter().enabled is False
    monkeypatch.setenv("TIKV_TPU_COST_ROUTER", "1")
    assert CostRouter().enabled is True


def test_measured_picks_cheapest_and_reports_delta():
    r = CostRouter(config=RouterConfig(seed=5, epsilon=0.0,
                                       cold_probe_rate=0.0))
    _seed_profiles("sigM", {"zone": 0.008, "unary": 0.002, "cpu": 0.030},
                   n=10)
    for _ in range(20):
        d = r.route("sigM", ["zone", "unary", "cpu"])
        assert (d.path, d.reason) == ("unary", "measured")
        assert d.delta_ms == 0.0


def test_explore_share_bounded_and_recovers_after_profile_improves():
    eps = 0.1
    r = CostRouter(config=RouterConfig(seed=7, epsilon=eps,
                                       cold_probe_rate=0.0))
    _seed_profiles("sigE", {"fast": 0.001, "slow": 0.010}, n=50)
    n = 4000
    picks = [r.route("sigE", ["slow", "fast"]).path for _ in range(n)]
    share = picks.count("slow") / n
    # the worse path keeps a BOUNDED probe share: epsilon, not zero and
    # not runaway (3-sigma slack around the configured rate)
    assert 0.05 < share < 0.15
    # the profile improves (the slow path got faster than the incumbent):
    # measured routing must recover its share immediately
    obs.OBSERVATORY.reset()
    _seed_profiles("sigE", {"fast": 0.001, "slow": 0.0002}, n=50)
    picks = [r.route("sigE", ["slow", "fast"]).path for _ in range(1000)]
    assert picks.count("slow") / 1000 > 0.85


def test_cold_paths_probed_at_budgeted_rate_round_robin():
    rate = 0.04
    r = CostRouter(config=RouterConfig(seed=13, epsilon=0.0,
                                       cold_probe_rate=rate))
    _seed_profiles("sigC", {"unary": 0.001}, n=50)
    n = 6000
    picks = [r.route("sigC", ["zone", "unary", "cpu", "fused"])
             for _ in range(n)]
    cold = [d for d in picks if d.reason == "cold"]
    share = len(cold) / n
    assert 0.02 < share < 0.08
    # budget rotates across ALL cold candidates, not just the first
    probed = {d.path for d in cold}
    assert probed == {"zone", "cpu", "fused"}


def test_route_prices_from_the_observatory_alone():
    """No caller can hand ``route`` a price table of its own."""
    with pytest.raises(TypeError):
        CostRouter().route("s", ["unary", "cpu"], costs={})


def test_route_requires_candidates():
    with pytest.raises(ValueError):
        CostRouter().route("s", [])


def test_decision_snapshot_ring_bounded():
    r = CostRouter(config=RouterConfig(seed=2))
    for i in range(200):
        r.route(f"s{i % 3}", ["unary", "cpu"])
    snap = r.snapshot()
    assert len(snap["recent"]) <= 64
    assert snap["decisions_by_reason"]["static_fallback"] == 200


# ---------------------------------------------------------------------------
# endpoint integration: measured routing, byte identity, kill-switch identity
# ---------------------------------------------------------------------------

def _router_ep(eng, **router_kw):
    cfg = dict(seed=3, epsilon=0.0, cold_probe_rate=0.0, min_count=3)
    cfg.update(router_kw)
    return Endpoint(LocalEngine(eng), enable_device=True, block_rows=512,
                    cost_router=CostRouter(config=RouterConfig(**cfg)))


def test_router_routes_around_expensive_device_path():
    eng = _engine(400)
    ep = _router_ep(eng)
    dag = _sum_dag()
    sig, _ = obs.dag_sig(dag)
    fb = REGISTRY.counter("tikv_coprocessor_path_fallback_total", "")
    before = fb.get(path="unary", cause="cost_route")
    # measured profiles say the device path is 100x the CPU pipeline
    _seed_profiles(sig, {"unary": 0.5, "cpu": 0.005})
    resp = ep.handle_request(_req(400, dag))
    assert resp.from_device is False
    assert fb.get(path="unary", cause="cost_route") == before + 1
    # flip the evidence: the device path is cheap again -> device serve
    obs.OBSERVATORY.reset()
    _seed_profiles(sig, {"unary": 0.001, "cpu": 0.5})
    resp = ep.handle_request(_req(400, dag))
    assert resp.from_device is True


def test_byte_identity_on_every_routed_path():
    eng = _engine(400)
    # maximum legal exploration: every candidate path gets chosen
    ep = _router_ep(eng, epsilon=0.5, cold_probe_rate=0.5, min_count=1)
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    dag = _sum_dag()
    oracle = ep_cpu.handle_request(_req(400, dag)).data
    served_paths = set()
    for _ in range(40):
        resp = ep.handle_request(_req(400, dag))
        assert resp.data == oracle
        served_paths.add("device" if resp.from_device else "cpu")
    # the explore/cold churn really did exercise more than one serving path
    assert served_paths == {"device", "cpu"}
    reasons = ep.cost_router.snapshot()["decisions_by_reason"]
    assert reasons["cold"] > 0 or reasons["explore"] > 0


def test_kill_switch_byte_and_metric_identical_to_static_rules():
    eng = _engine(400)
    dag = _sum_dag()
    # static baseline: router enabled but min_count so high nothing ever
    # warms — by construction every decision is the static-ladder head
    ep_static = _router_ep(eng, min_count=10**6)
    ep_kill = Endpoint(LocalEngine(eng), enable_device=True, block_rows=512,
                       cost_router=CostRouter(enabled=False))
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    c = REGISTRY.counter("tikv_coprocessor_cost_route_total", "")
    delta = REGISTRY.counter("tikv_coprocessor_cost_route_delta_ms_total", "")
    kill0 = c.get(path="zone", reason="kill_switch")
    delta0 = delta.get()
    oracle = ep_cpu.handle_request(_req(400, dag)).data
    for _ in range(6):
        a = ep_static.handle_request(_req(400, dag))
        b = ep_kill.handle_request(_req(400, dag))
        assert a.data == b.data == oracle
        assert a.from_device == b.from_device
    # the kill switch took the same serving path, emitted ONLY
    # reason="kill_switch" decisions, and never accrued chosen-vs-best delta
    assert c.get(path="zone", reason="kill_switch") == kill0 + 6
    assert delta.get() == delta0
    sig, _ = obs.dag_sig(dag)
    routes = obs.OBSERVATORY.snapshot(sig=sig)["sigs"][sig]["routes"]
    assert routes.get("zone|kill_switch") == 6
    assert routes.get("zone|static_fallback") == 6


# ---------------------------------------------------------------------------
# chosen-vs-best deltas feed the AdaptiveController (route waste != saturation)
# ---------------------------------------------------------------------------

def test_route_waste_vetoes_relax_but_never_tightens():
    clk = [0.0]
    ctrl = AdaptiveController(OverloadConfig(window_s=1.0),
                              clock=lambda: clk[0])
    ctrl.scale = 0.5
    # persistent routing waste: chosen 5ms over a 1ms best, many samples
    for _ in range(12):
        ctrl.note_route_delta(5.0, 1.0)
    clk[0] += 2.0
    ctrl.note_queue(0, 100)  # idle queues would otherwise relax
    assert ctrl.last_evidence["route_pressure"] is True
    assert ctrl.last_evidence["route_samples"] == 12
    assert ctrl.scale == 0.5  # relax vetoed, NOT tightened
    # waste clears -> the relax branch resumes
    clk[0] += 2.0
    ctrl.note_queue(0, 100)
    assert ctrl.last_evidence["route_pressure"] is False
    assert ctrl.scale > 0.5


def test_endpoint_forwards_route_deltas_to_overload():
    from tikv_tpu.copr.overload import OverloadControl

    eng = _engine(400)
    ep = _router_ep(eng)
    ep.overload = OverloadControl(OverloadConfig(enabled=True, adaptive=True),
                                  region_cache=ep.region_cache)
    dag = _sum_dag()
    sig, _ = obs.dag_sig(dag)
    _seed_profiles(sig, {"unary": 0.001, "cpu": 0.5})
    ep.handle_request(_req(400, dag))
    # a measured decision carries delta 0 vs best — the controller saw it
    assert ep.overload.controller._route[2] >= 1


# ---------------------------------------------------------------------------
# geometry auto-tuner: hill-climb, one change in flight, revert on regression
# ---------------------------------------------------------------------------

class _FakeObs:
    """Deterministic throughput source: rate is a pure function of the
    registered knob's current value, rows/busy_s advance per drive()."""

    def __init__(self):
        self.serves = 0
        self.rows = 0
        self.busy = 0.0

    def totals(self):
        return {"serves": self.serves, "rows": self.rows,
                "busy_s": self.busy}

    def drive(self, serves: int, busy_per_serve: float, rows: int = 1024):
        self.serves += serves
        self.rows += serves * rows
        self.busy += serves * busy_per_serve


def test_tuner_walks_bad_block_rows_down_within_bounds():
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake,
                          config=TunerConfig(min_serves=8, warmup_ticks=0))
    knob = {"block_rows": 1 << 18}
    lo, hi = 1 << 10, 1 << 18
    tuner.register("coprocessor.block_rows",
                   lambda: knob["block_rows"],
                   lambda v: knob.__setitem__("block_rows", int(v)),
                   lo, hi, integer=True)
    for _ in range(40):
        # padded-tile cost model: busy scales with block_rows, so every
        # halving improves the measured rate and is kept
        fake.drive(16, busy_per_serve=knob["block_rows"] / 1e6)
        tuner.tick()
    snap = tuner.snapshot()
    assert lo <= knob["block_rows"] <= 1 << 12  # converged to the floor
    assert snap["counts"]["keep"] >= 6
    assert snap["counts"]["reject"] == 0
    # every proposal stayed inside the validated bounds
    for ev in snap["history"]:
        if "new" in ev:
            assert lo <= ev["new"] <= hi


def test_tuner_tunes_bad_max_wait_back():
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake,
                          config=TunerConfig(min_serves=8, warmup_ticks=0))
    knob = {"max_wait_s": 0.05}  # pathologically long linger
    tuner.register("coprocessor.max_wait_s",
                   lambda: knob["max_wait_s"],
                   lambda v: knob.__setitem__("max_wait_s", float(v)),
                   0.0005, 0.05)
    for _ in range(40):
        fake.drive(16, busy_per_serve=knob["max_wait_s"])
        tuner.tick()
    assert 0.0005 <= knob["max_wait_s"] <= 0.004


def test_tuner_reverts_on_floor_regression_and_flips_direction():
    c = REGISTRY.counter("tikv_coprocessor_geometry_tune_total", "")
    before = c.get(knob="coprocessor.block_rows", action="revert")
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake,
                          config=TunerConfig(min_serves=8, warmup_ticks=0,
                                             revert_ratio=0.7))
    knob = {"block_rows": 1 << 14}
    tuner.register("coprocessor.block_rows",
                   lambda: knob["block_rows"],
                   lambda v: knob.__setitem__("block_rows", int(v)),
                   1 << 10, 1 << 18, integer=True)
    # seeded regression: the smaller geometry is 10x SLOWER (per-dispatch
    # overhead dominates) — the tuner must put the old value back
    fake.drive(16, busy_per_serve=0.001)
    tuner.tick()           # baseline window
    fake.drive(16, busy_per_serve=0.001)
    assert tuner.tick()["action"] == "propose"
    assert knob["block_rows"] == 1 << 13
    fake.drive(16, busy_per_serve=0.010)
    ev = tuner.tick()
    assert ev["action"] == "revert"
    assert knob["block_rows"] == 1 << 14  # old value restored
    assert c.get(knob="coprocessor.block_rows", action="revert") == before + 1
    # direction flipped: the next proposal climbs instead (the judging
    # tick re-anchored the baseline window, so one drive suffices)
    fake.drive(16, busy_per_serve=0.001)
    ev = tuner.tick()
    assert ev["action"] == "propose" and ev["new"] == 1 << 15


def test_tuner_warmup_discards_post_change_transient():
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake,
                          config=TunerConfig(min_serves=8, warmup_ticks=1))
    knob = {"block_rows": 1 << 14}
    tuner.register("coprocessor.block_rows",
                   lambda: knob["block_rows"],
                   lambda v: knob.__setitem__("block_rows", int(v)),
                   1 << 10, 1 << 18, integer=True)
    fake.drive(16, busy_per_serve=0.001)
    tuner.tick()
    fake.drive(16, busy_per_serve=0.001)
    assert tuner.tick()["action"] == "propose"
    # the rebuild/recompile transient: 20x the steady rate, discarded
    fake.drive(16, busy_per_serve=0.020)
    assert tuner.tick() is None  # warmup tick re-anchors, no judgment
    fake.drive(16, busy_per_serve=0.0005)
    assert tuner.tick()["action"] == "keep"
    assert knob["block_rows"] == 1 << 13


def test_tuner_reject_via_validated_config_path():
    ctl = ConfigController(TikvConfig())
    ctl.update({"coprocessor.block_rows": 256})
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake,
                          config=TunerConfig(min_serves=8, warmup_ticks=0))
    # bounds WIDER than the config's own validation: the proposal to 128
    # must be rejected by TikvConfig.validate, counted, and change nothing
    tuner.register("coprocessor.block_rows",
                   lambda: ctl.config.coprocessor.block_rows,
                   lambda v: ctl.update({"coprocessor.block_rows": int(v)}),
                   64, 1 << 18, integer=True)
    fake.drive(16, busy_per_serve=0.001)
    tuner.tick()
    fake.drive(16, busy_per_serve=0.001)
    ev = tuner.tick()
    assert ev["action"] == "reject"
    assert ctl.config.coprocessor.block_rows == 256
    assert tuner.snapshot()["counts"]["reject"] == 1


def test_tuner_disabled_is_inert():
    fake = _FakeObs()
    tuner = GeometryTuner(observatory=fake, enabled=False)
    knob = {"v": 8}
    tuner.register("k", lambda: knob["v"],
                   lambda v: knob.__setitem__("v", v), 1, 64)
    fake.drive(100, busy_per_serve=0.001)
    assert tuner.tick() is None
    assert knob["v"] == 8


# ---------------------------------------------------------------------------
# runtime-tunable scheduler geometry + config bounds (POST /config)
# ---------------------------------------------------------------------------

def test_config_validates_geometry_bounds():
    ctl = ConfigController(TikvConfig())
    with pytest.raises(ValueError):
        ctl.update({"coprocessor.block_rows": 64})       # below 2^8
    with pytest.raises(ValueError):
        ctl.update({"coprocessor.block_rows": 1 << 21})  # above 2^20
    with pytest.raises(ValueError):
        ctl.update({"coprocessor.block_rows": 3000})     # not a power of two
    with pytest.raises(ValueError):
        ctl.update({"coprocessor.max_wait_s": 0.0})
    with pytest.raises(ValueError):
        ctl.update({"coprocessor.low_max_wait_s": 2.0})
    # a rejected update changes NOTHING
    assert ctl.config.coprocessor.block_rows == 1 << 16
    diff = ctl.update({"coprocessor.block_rows": 4096,
                       "coprocessor.max_wait_s": 0.01})
    assert diff["coprocessor"] == {"block_rows": 4096, "max_wait_s": 0.01}


def test_scheduler_reconfigure_lane_waits():
    eng = _engine(64)
    ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=512)
    ep.scheduler.reconfigure({"max_wait_s": 0.01,
                              "high_max_wait_s": 0.002,
                              "low_max_wait_s": 0.08})
    assert ep.scheduler.cfg.max_wait_s == 0.01
    assert ep.scheduler.cfg.high_max_wait_s == 0.002
    assert ep.scheduler.cfg.low_max_wait_s == 0.08


def test_endpoint_set_block_rows_invalidates_geometry():
    eng = _engine(400)
    ep = Endpoint(LocalEngine(eng), enable_device=True, block_rows=512)
    dag = _sum_dag()
    ep_cpu = Endpoint(LocalEngine(eng), enable_device=False)
    oracle = ep_cpu.handle_request(_req(400, dag)).data
    assert ep.handle_request(_req(400, dag)).data == oracle
    ep.set_block_rows(1024)
    assert ep.block_rows == 1024
    assert ep.region_cache.block_rows == 1024
    # the warm image was invalidated; the rebuilt geometry serves the
    # same bytes
    assert ep.handle_request(_req(400, dag)).data == oracle
    # no-op change keeps evaluator caches intact
    evs = ep._evaluators
    ep.set_block_rows(1024)
    assert ep._evaluators is evs


# ---------------------------------------------------------------------------
# observability: /debug/cost_router, RPC, ctl, observatory declines
# ---------------------------------------------------------------------------

def test_debug_cost_router_rpc_http_and_ctl(capsys):
    import urllib.error
    import urllib.request

    from tikv_tpu.server.server import Client, Server
    from tikv_tpu.server.service import KvService
    from tikv_tpu.server.status_server import StatusServer
    from tikv_tpu.storage.storage import Storage

    eng = _engine(400)
    ep = _router_ep(eng)
    dag = _sum_dag()
    ep.handle_request(_req(400, dag))
    svc = KvService(Storage(), ep)
    srv = Server(svc)
    srv.start()
    c = Client(*srv.addr)
    try:
        snap = c.call("debug_cost_router", {})
        assert snap["router"]["enabled"] is True
        assert snap["router"]["decisions_by_reason"]["static_fallback"] >= 1
        sys.path.insert(0, REPO)
        try:
            import ctl
        finally:
            sys.path.pop(0)
        addr = f"{srv.addr[0]}:{srv.addr[1]}"
        assert ctl.main(["--addr", addr, "cost-router"]) == 0
        out = capsys.readouterr().out
        assert "decisions_by_reason" in out
    finally:
        c.close()
        srv.stop()

    ss = StatusServer(cost_router=lambda: ep.cost_router_snapshot())
    ss.start()
    try:
        host, port = ss.addr
        js = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/debug/cost_router").read())
        assert js["router"]["decisions_by_reason"]["static_fallback"] >= 1
    finally:
        ss.stop()

    ss = StatusServer()  # not wired -> 404, not a crash
    ss.start()
    try:
        host, port = ss.addr
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(f"http://{host}:{port}/debug/cost_router")
        assert exc.value.code == 404
    finally:
        ss.stop()


def test_observatory_text_surfaces_decline_causes():
    import urllib.request

    from tikv_tpu.copr import encoding
    from tikv_tpu.server.status_server import StatusServer

    encoding.count_decline("device_plan", "router_test_cause")
    ss = StatusServer()
    ss.start()
    try:
        host, port = ss.addr
        body = urllib.request.urlopen(
            f"http://{host}:{port}/debug/observatory").read().decode()
        assert "device-plan declines" in body
        assert "cause=router_test_cause" in body
    finally:
        ss.stop()


def test_cost_router_snapshot_includes_tuner():
    eng = _engine(64)
    ep = _router_ep(eng)
    assert "tuner" not in ep.cost_router_snapshot()
    ep.geometry_tuner = GeometryTuner(observatory=_FakeObs())
    snap = ep.cost_router_snapshot()
    assert snap["tuner"]["enabled"] is True
